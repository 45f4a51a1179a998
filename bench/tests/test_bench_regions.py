"""The program's named regions and data spans, read back.

On the CPU: the harness's small cell and a small MoE step on the concat
kernel path (Pallas in interpret mode) compile with every working
instruction of the scan body in a region, and with regions or without
compile to the same instructions.  The reduction (`bench.regions`) on HLO
lines laid out by hand, on a profiler trace of the prefetcher, and on a
small trace recorded on a TPU v5e (`data/regions_chip.*`, written by
`data/record_regions.py`, then compressed): a scoped scanned MoE step, five dispatches of
two steps inside a ``bench.window`` span, fed by `data.prefetch_chunks`."""
import contextlib
import gzip
import re
import tempfile
from pathlib import Path

import jax
import pytest

from bench import regions as G
from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
CHIP_TRACE = DATA / "regions_chip.xplane.pb.gz"
CHIP_HLO = DATA / "regions_chip.hlo.txt.gz"
# instructions that do no work of their own
NO_WORK = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast",
           "copy")
# what lax.scan itself does in its body: slice the inputs, stack the
# outputs, count
SCAN_OWN = ("dynamic_slice", "dynamic_update_slice", "add")


# ---------------------------------------------------------------------------
# Regions in the compiled step (CPU)
# ---------------------------------------------------------------------------

def _program(which: str, tmp: Path):
    """(scanned program, abstract state, chunk, keys) of the harness's small
    cell or of a small MoE step on the concat kernel path."""
    import harness_util as H

    from bench import run as R
    from repro.core import init_state
    from repro.data import make_lm_pipeline
    from repro.launch.steps import per_step_keys
    from repro.launch.train import build_parser
    if which == "cell":
        tmp.mkdir()
        cell = R.find_cell(H.make_root(tmp), H.CELL)
        cfg, flags = R.arch_config(cell.config), cell.traffic["flags"]
    else:
        from repro.configs import get_config
        cfg = get_config("granite-moe-1b-a400m-tiny")
        flags = ["--agents", "4", "--unroll-k", "2", "--per-agent-batch",
                 "1", "--seq-len", "16"]
    pargs = build_parser().parse_args(flags)
    prog = R.make_program(cfg, pargs)
    bundle, scanned = prog.bundle, prog.scanned
    state = jax.eval_shape(lambda p: init_state(p, pargs.agents),
                           bundle.abstract())
    pipe = make_lm_pipeline(cfg.vocab_size, pargs.agents,
                            pargs.per_agent_batch, pargs.seq_len, seed=0)
    chunk = jax.eval_shape(lambda: pipe.chunk_at(0, pargs.unroll_k))
    keys = jax.eval_shape(lambda: per_step_keys(jax.random.key(0), 0,
                                                pargs.unroll_k))
    return scanned, state, chunk, keys


def _compile_text(which: str, tmp: Path) -> str:
    with pytest.MonkeyPatch.context() as mp:
        if which == "moe":      # the fused kernels, interpreted
            mp.setenv("REPRO_USE_PALLAS", "1")
        scanned, *args = _program(which, tmp)
        return scanned.lower(*args).compile().as_text()


@pytest.fixture(scope="module", params=["cell", "moe"])
def built(request, tmp_path_factory):
    """(scoped text, text with every region a null context) of one
    program, compiled with the persistent cache off so that neither build
    loads the other's executable (the cache key ignores metadata)."""
    from repro import trace as rt
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        tmp = tmp_path_factory.mktemp(request.param)
        scoped = _compile_text(request.param, tmp / "scoped")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(rt, "region", lambda name: contextlib.nullcontext())
            plain = _compile_text(request.param, tmp / "plain")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return request.param, scoped, plain


def _computations(text: str) -> dict:
    """{computation name: its instruction lines}; ENTRY under "ENTRY"."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if m:
            cur = comps.setdefault("ENTRY" if m.group(1) else m.group(2), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _scan_body(text: str):
    """[(instruction, opcode, op_name or None)] of the top level of the
    scan's while body (the while loop of the entry computation), less the
    materialized constants (work whose operands are all constants)."""
    comps = _computations(text)
    (body,) = [re.search(r"body=%?([\w.\-]+)", ln).group(1)
               for ln in comps["ENTRY"] if " while(" in ln]
    out, constants = [], set()
    for line in comps[body]:
        m = G._INSTRUCTION.match(line)
        if not m:
            continue
        name, rhs = m.group(1), line[m.end():]
        opcode = re.search(r"\s([a-z][\w\-]*)\(", rhs).group(1)
        operands = re.findall(r"%([\w.\-]+)", rhs.split("), ")[0])
        if opcode == "constant" or operands and set(operands) <= constants:
            constants.add(name)
            continue
        op = G._OP_NAME.search(line)
        out.append((name, opcode, op and op.group(1)))
    return out


def test_scan_body_work_maps_to_a_region(built):
    which, text, _ = built
    module, regions = G.instruction_regions(text)
    assert module == "jit_scanned"
    stray = [(n, code, op) for n, code, op in _scan_body(text)
             if code not in NO_WORK and regions[n] is None
             and not (op and op.rsplit("/", 1)[-1] in SCAN_OWN
                      and op.rsplit("/", 2)[-2] == "body")]
    assert stray == []
    found = {r.split("/")[0] for r in regions.values() if r}
    assert found == {"step.model", "step.mix", "step.update", "step.report"}


def test_model_has_forward_and_backward(built):
    _, text, _ = built
    regions = set(G.instruction_regions(text)[1].values())
    assert any(r and r.startswith("step.model/fwd") for r in regions)
    assert any(r and r.startswith("step.model/bwd") for r in regions)


def test_concat_layout_and_model_subscopes(built):
    which, text, _ = built
    regions = set(G.instruction_regions(text)[1].values())
    if which == "cell":     # the jnp update on the CPU: no concat layout
        assert "step.update/obfuscate" in regions
        return
    assert {"step.update/layout", "step.update/obfuscate",
            "step.update/gossip"} <= regions
    for sub in ("embed", "attn", "mlp", "mlp/moe.router",
                "mlp/moe.experts", "head"):
        assert f"step.model/fwd/{sub}" in regions
        assert f"step.model/bwd/{sub}" in regions


def test_regions_change_no_instruction(built):
    _, scoped, plain = built
    assert "step.update/" in scoped and "step.update" not in plain
    assert G.strip_metadata(scoped) == G.strip_metadata(plain)


# ---------------------------------------------------------------------------
# The reduction, on lines laid out by hand
# ---------------------------------------------------------------------------

HLO = """HloModule jit_scanned, is_scheduled=true

FileNames
1 "/somewhere/pdsgd.py"

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %fusion.1 = f32[8] fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(scanned)/while/body/closed_call/step.model/vmap(jvp(attn))/dot_general" stack_frame_id=1}
  %fusion.2 = f32[8] fusion(%fusion.1), kind=kLoop, calls=%f.2, metadata={op_name="jit(scanned)/while/body/closed_call/step.model/vmap(transpose(jvp(step.model)))/vmap(jvp())/checkpoint/rematted_computation/attn/tanh"}
  %copy.3 = f32[8] copy(%fusion.2)
  %fusion.4 = f32[8] fusion(%copy.3), metadata={op_name="jit(scanned)/while/body/closed_call/step.update/layout/concatenate"}
  %_gossip_update.5 = f32[8] custom-call(%fusion.4), metadata={op_name="jit(scanned)/while/body/closed_call/step.update/gossip/jit(_gossip_update)/pallas_call"}
  %rng.6 = u32[8] fusion(%p), metadata={op_name="jit(scanned)/while/body/closed_call/step.update/step.mix/random_bits"}
  %copy.8 = f32[8] copy(%p)
  %fusion.9 = f32[8] fusion(%copy.8), metadata={op_name="jit(scanned)/while/body/closed_call/step.report/reduce_sum"}
  ROOT %dynamic-update-slice.7 = f32[8] fusion(%fusion.4), metadata={op_name="jit(scanned)/while/body/dynamic_update_slice"}
}
"""


def test_op_names_map_to_regions():
    module, regions = G.instruction_regions(HLO)
    assert module == "jit_scanned"
    assert regions == {
        "p": None,
        "fusion.1": "step.model/fwd/attn",
        # the transpose wrapper marks backward work, remat recompute too
        "fusion.2": "step.model/bwd/attn",
        # made by a pass without metadata: its operand's region
        "copy.3": "step.model/bwd/attn",
        "fusion.4": "step.update/layout",
        "_gossip_update.5": "step.update/gossip",
        # a step region inside another owns its work
        "rng.6": "step.mix",
        # ... or else of its user
        "copy.8": "step.report", "fusion.9": "step.report",
        "dynamic-update-slice.7": None}
    assert G.region_of("jit(f)/attn/transpose") is None   # a primitive
    assert G.unwrap("vmap(transpose(jvp(attn)))") == (
        "attn", ["vmap", "transpose", "jvp"])


def test_strip_metadata_drops_metadata_and_source_tables():
    out = G.strip_metadata(HLO)
    assert "metadata" not in out and "FileNames" not in out
    assert "pdsgd.py" not in out
    assert "%fusion.1 = f32[8] fusion(%p), kind=kLoop, calls=%f.1\n" in out


def test_only_the_step_modules_operations_count():
    regions = {"fusion.1": "step.model/fwd", "fusion.2": "step.update/gossip",
               "while.3": None}
    modules = [("jit_scanned(17)", 0, 100), ("jit_other(4)", 100, 130),
               ("jit_scanned(17)", 130, 200)]
    ops = [("while.3", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 90),
           ("fusion.1", 105, 125),      # another program's fusion.1
           ("fusion.2", 140, 180)]
    iv = G.module_intervals(modules, "jit_scanned")
    assert iv == [(0, 100), (130, 200)]
    got = G.region_ns(ops, iv, regions, 0, 200)
    assert got == {"unscoped": 30, "step.model/fwd": 30,
                   "step.update/gossip": 80}
    # regions and unscoped add up to the module's busy time
    assert sum(got.values()) == T.busy_ns(G.inside(ops, iv, 0, 200), 0, 200)
    assert G.summary(got, 2) == pytest.approx(
        {"model_fwd_ms": 15e-6, "model_bwd_ms": 0.0,
         "update_layout_ms": 0.0})


def test_data_produce_reads_the_consumed_chunks():
    P, L, W = "repro.data.produce", "repro.data.place", "repro.data.wait"
    spans = [(P, "w", 0, 10, {"step": 0}), (L, "w", 10, 12, {"step": 0}),
             (P, "w", 12, 20, {"step": 4}), (L, "w", 20, 26, {"step": 4}),
             (P, "w", 26, 40, {"step": 8}), (L, "w", 40, 41, {"step": 8}),
             (W, "m", 5, 13, {"step": 0}), (W, "m", 60, 61, {"step": 4}),
             (W, "m", 80, 81, {"step": 12})]
    # in [50, 100] the loop took steps 4 and 12; 12 was made before the
    # trace began, 8 never taken
    assert G.data_produce_ms(spans, 50, 100) == pytest.approx(14e-6)
    assert G.data_produce_ms(spans, 200, 300) is None


# ---------------------------------------------------------------------------
# The data layer's spans in a profiler trace (CPU)
# ---------------------------------------------------------------------------

def test_prefetch_spans_carry_the_chunks_step():
    from repro.data import make_lm_pipeline, prefetch_chunks
    pipe = make_lm_pipeline(64, 2, 1, 8, seed=3)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            with prefetch_chunks(pipe, 4, start_step=8, num_chunks=3,
                                 depth=1) as chunks:
                taken = [c["tokens"].shape for c in chunks]
        finally:
            jax.profiler.stop_trace()
        reduced = T.load(T.find_xplane(d), span_prefix="repro.data.")
        names = [s[0] for s in reduced.host_spans]
        spans = reduced.program_spans
    assert taken == [(4, 2, 1, 8)] * 3
    steps = lambda name: sorted(st["step"] for n, _, _, _, st in spans
                                if n == name)
    assert steps("repro.data.produce")[:3] == [8, 12, 16]
    assert steps("repro.data.place") == [8, 12, 16]
    assert steps("repro.data.wait")[:3] == [8, 12, 16]
    assert names.count("repro.data.place") == 3
    lo = min(s for *_, s, e, st in spans)
    hi = max(e for *_, s, e, st in spans)
    assert G.data_produce_ms(spans, lo, hi) > 0


# ---------------------------------------------------------------------------
# A scoped step traced on a TPU v5e
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    """The readers' context (`bench.run`) of the recorded window."""
    path = tmp_path_factory.mktemp("chip") / "regions_chip.xplane.pb"
    path.write_bytes(gzip.decompress(CHIP_TRACE.read_bytes()))
    reduced = T.load(str(path))
    module, regions = G.instruction_regions(
        gzip.decompress(CHIP_HLO.read_bytes()).decode())
    return {"trace": reduced, "trace_window": reduced.window(),
            "planes": sorted(reduced.device_ops)[:1], "steps": 10,
            "module": module, "regions": regions}


def test_chip_regions_add_up_to_the_step_modules_busy_time(chip):
    reduced = chip["trace"]
    (plane,) = reduced.device_ops
    line = G.regions_line(dict(chip))
    assert line["module"] == "jit_scanned"
    # equal up to the trace's rounding to whole nanoseconds
    assert line["sum_ms"] == pytest.approx(line["module_busy_ms"], rel=1e-5)
    ms = line["ms"]
    assert ms.get("unscoped", 0) < 0.05 * line["sum_ms"]
    assert line["model_fwd_ms"] > 0 and line["model_bwd_ms"] > 0
    assert line["update_layout_ms"] > 0
    assert line["data_produce_ms"] > 0
    # the update kernels matched by name are the obfuscate and gossip
    # regions' kernels
    lo, hi = reduced.window()
    kernels = sum(e - s for _, s, e in T.matching(
        T.clip(reduced.device_ops[plane], lo, hi), ("obfuscate", "gossip")))
    assert kernels / 1e6 / 10 <= line["obfuscate_gossip_ms"] * (1 + 1e-9)


@pytest.mark.parametrize("name", ["model_fwd_ms", "model_bwd_ms",
                                  "update_layout_ms", "data_produce_ms"])
def test_readers_give_what_the_regions_line_prints(chip, name):
    from bench.run import ROOT, metric_reader
    line = G.regions_line(dict(chip))
    assert metric_reader(ROOT, name)(dict(chip)) == line[name] > 0
    # no trace, or no device plane: nothing to read, never 0
    assert metric_reader(ROOT, name)(dict(chip, planes=[], trace=None)) \
        is None


def test_regions_tool_drives_the_harness(tmp_path):
    """`bench/regions.py` runs the small cell through `bench.run` unchanged
    (the CPU trace has no device plane, so no region times), and prints
    its lines before the harness's result."""
    import json
    import os
    import subprocess
    import sys

    import harness_util as H
    root = H.make_root(tmp_path)
    hlo = tmp_path / "step.hlo"
    code = ("import sys; from pathlib import Path; root = Path(sys.argv[1]);"
            " sys.path[:0] = [str(root), str(root / 'src')];"
            " from bench import regions;"
            " sys.exit(regions.main(sys.argv[2:], root=root,"
            " require_tpu=False))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".cache"))
    p = subprocess.run(
        [sys.executable, "-c", code, str(root), "--workload", H.CELL,
         "--seed", "7", "--seconds", "0.5", "--hlo-out", str(hlo)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    by_phase = {x.get("phase"): x for x in lines}
    memory = by_phase["memory_stats"]
    assert set(memory) >= {"before", "after"}
    assert by_phase["regions"]["module"] == "jit_scanned"
    assert lines[-1]["correct"] is True and "metrics" in lines[-1]
    text = hlo.read_text()
    assert "HloModule jit_scanned" in text and "metadata" not in text

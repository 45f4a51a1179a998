"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration file and a
traffic file; per-layer metrics are readers under ``bench/metrics/`` and
the limits of the output comparison sit in ``bench/limits/<cell>.json``.
All are found by name.  The run builds the program's own decentralized
step (`core.make_decentralized_step`, `make_scanned_steps`, per-step keys
from `launch.steps.per_step_keys`, the program's synthetic token stream
`data.make_lm_pipeline` through `data.prefetch_chunks`) with the settings
`launch.train.build_parser` gives the traffic's flags, on one chip or, where
the flags ask for a mesh, over the cell's chips (`make_program`),
makes the weights on the device from ``--seed``, compiles the step ahead of
time, drives its first chunk (kept for the output check), then measures
for ``--seconds``.  After the window it reads the device's memory, frees
the program's state and checks the first chunk against the plain
reference.  The last line of standard output is one JSON object; earlier
lines are diagnostics.  Without a TPU, or with fewer chips than the cell
asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# a program compiled, or asked of the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/compile_requests_use_cache")


class Fail(Exception):
    """The run cannot produce a result."""


def note(**rec) -> None:
    """A diagnostic line on standard output (never the last one); ``t`` is
    seconds since the process started."""
    print(json.dumps({"t": time.perf_counter() - T_START, **rec}),
          flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    limits: dict          # {number: limit} of the output comparison
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise Fail(f"missing {path}") from None


def _applies(metric: dict, name: str) -> bool:
    return name in metric.get("workloads", [name])


def find_cell(root: Path, name: str) -> Cell:
    bm = _read_json(root / "BENCHMARK.json")
    wl = [w for w in bm["workloads"] if w["name"] == name]
    if len(wl) != 1:
        raise Fail(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    wl = wl[0]
    conf = [c for c in bm["configs"] if c["name"] == wl["config"]]
    if len(conf) != 1:
        raise Fail(f"no configuration {wl['config']!r}")
    bench = root / "bench"
    limits = _read_json(bench / "limits" / f"{name}.json")["limits"]
    return Cell(workload=wl,
                config=_read_json(root / conf[0]["file"]),
                traffic=_read_json(bench / "traffic" /
                                   f"{wl['traffic']}.json"),
                limits=limits,
                end_to_end=[m for m in bm["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def metric_reader(root: Path, name: str):
    """The ``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise Fail(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def arch_config(config: dict):
    """The program's ArchConfig for a configuration file: the registry's
    ``base`` entry with ``replace`` applied; every number the file lists
    under ``sizes`` must be what that gives."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(config["base"]),
                              **config.get("replace", {}))
    got = dataclasses.asdict(cfg)
    wrong = {k: (v, got.get(k)) for k, v in config["sizes"].items()
             if got.get(k) != v}
    if wrong:
        raise Fail(f"configuration file and program disagree "
                   f"(file, program): {wrong}")
    return cfg


@dataclasses.dataclass
class Program:
    bundle: object           # the model (`models.build_model`)
    scanned: object          # the scanned K-step program
    mesh: object             # None on one device
    params_shardings: object  # per parameter leaf, agent axis first; or None
    state_shardings: object  # of the whole state; or None
    place: object            # puts a chunk where the step reads it


def make_program(cfg, pargs) -> Program:
    """The program as `launch.train.run_training` builds it for the same
    flags: on one device, or, where they ask for ``--mesh-fsdp`` or
    ``--mesh-tensor`` above 1, over `launch.mesh.make_sharded_mesh`'s
    mesh, with the program's sharding audit, leaf specs and state
    placement (`optim.shard_like`) and the step told the mesh.  The
    layout ``auto`` is ``leafwise`` on a mesh and ``concat`` on one
    device; ``ring`` does not compose with a mesh."""
    import jax
    from repro.core import (init_state, make_decentralized_step,
                            make_scanned_steps)
    from repro.core.schedules import warmup_harmonic
    from repro.data import make_placer
    from repro.launch.train import build_faults, build_mixing
    from repro.models import build_model
    sharded = pargs.mesh_fsdp > 1 or pargs.mesh_tensor > 1
    layout, use_pallas = pargs.kernel_layout, None
    if layout == "auto":
        layout = "leafwise" if sharded else "concat"
    elif layout == "ring":
        if sharded:
            raise Fail("--kernel-layout ring flattens each agent's leaves; "
                       "it does not compose with --mesh-fsdp/--mesh-tensor")
        use_pallas = True
    mesh = leaf_specs = params_sh = state_sh = None
    if sharded:
        from repro.launch.mesh import make_sharded_mesh
        try:
            mesh = make_sharded_mesh(agents=pargs.agents,
                                     fsdp=pargs.mesh_fsdp,
                                     tensor=pargs.mesh_tensor)
        except ValueError as e:
            raise Fail(f"the traffic's mesh: {e}") from None
    bundle = build_model(cfg, mesh=mesh)
    if sharded:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.dist.sharding import TRAIN_RULES, audit_rules, \
            logical_spec
        from repro.launch.specs import with_agent_axis
        from repro.optim import shard_like
        errors = [f for f in audit_rules(bundle.abstract(),
                                         bundle.logical_axes(), mesh)
                  if f["severity"] == "error"]
        if errors:
            raise Fail("sharding audit failed (unknown logical axes): "
                       + "; ".join(f"{f['path']}: {f['issue']}"
                                   for f in errors))
        p_abs, p_log = with_agent_axis(bundle.abstract(),
                                       bundle.logical_axes(), pargs.agents)
        leaf_specs = jax.tree.map(
            lambda a, log: logical_spec(mesh, a.shape, log, TRAIN_RULES),
            p_abs, p_log)
        params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 leaf_specs)
        state = jax.eval_shape(lambda p: init_state(
            p, pargs.agents, algorithm=pargs.algorithm), bundle.abstract())
        state_sh = shard_like(state, state.params, params_sh,
                              scalar_sharding=NamedSharding(
                                  mesh, PartitionSpec()))
    step = make_decentralized_step(
        bundle.loss_fn, build_mixing(pargs),
        warmup_harmonic(pargs.lr, hold=pargs.warmup_hold),
        algorithm=pargs.algorithm, sigma_dp=pargs.sigma_dp,
        grad_clip=pargs.grad_clip_kappa, faults=build_faults(pargs),
        nan_policy=pargs.nan_policy, use_pallas=use_pallas,
        spmd_axis_name="data" if sharded else None, kernel_layout=layout,
        mesh=mesh, leaf_specs=leaf_specs)
    return Program(bundle, make_scanned_steps(step, pargs.unroll_k), mesh,
                   params_sh, state_sh, make_placer(mesh))


def _listed(chunk: dict) -> dict:
    return {k: [float(x) for x in v] for k, v in chunk.items()}


def kernel_calls(hlo_text: str) -> dict:
    """tpu_custom_call instructions in a compiled program, by name."""
    counts = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split("=", 1)[0].strip().lstrip("%").split(".")[0]
            counts[name] = counts.get(name, 0) + 1
    return counts


def compiled_bytes(compiled) -> int:
    """What the compiler says the program needs: arguments + outputs -
    aliased + temporaries."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


class Spans:
    """The harness's host spans: kept in memory on the host clock, and
    written into the profiler's trace when one is being taken."""

    def __init__(self):
        self.done = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.done.append((name, t, time.perf_counter()))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run(args, root: Path, require_tpu: bool, keep: dict | None = None
        ) -> dict:
    """One run of the cell ``args.workload``: the result line.  Where
    ``keep`` is given, the compiled step's text (``hlo``) and, in a traced
    run, the readers' context (``ctx``) are left in it."""
    cell = find_cell(root, args.workload)
    if not (root / "src" / "repro").is_dir():
        raise Fail(f"no program (src/repro) under {root}")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    chips = cell.workload["chips"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if require_tpu and dev.platform != "tpu":
        raise Fail(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise Fail(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    note(phase="device", **device)

    from bench import check, counts, peaks
    from bench.seeds import jax_key
    from repro.core import init_state
    from repro.data import make_lm_pipeline, prefetch_chunks
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.steps import per_step_keys
    from repro.launch.train import build_parser

    note(phase="compile_cache", dir=use_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peak = peaks.peaks(dev.device_kind) if require_tpu else None

    cfg = arch_config(cell.config)
    sizes = cell.config["sizes"]
    ref = importlib.import_module(f"bench.refs.{cell.config['reference']}")
    pargs = build_parser().parse_args(cell.traffic["flags"])
    m, K = pargs.agents, pargs.unroll_k
    B, S = pargs.per_agent_batch, pargs.seq_len
    prog = make_program(cfg, pargs)
    if prog.mesh is not None and prog.mesh.devices.size != chips:
        raise Fail(f"the traffic's mesh {dict(prog.mesh.shape)} spans "
                   f"{prog.mesh.devices.size} devices, the cell {chips} "
                   "chips")
    bundle, scanned = prog.bundle, prog.scanned

    wkey = jax_key(args.seed, "weights")
    shapes = jax.eval_shape(lambda: ref.init(wkey, sizes))
    want = bundle.abstract()
    if jax.tree.structure(shapes) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(shapes), jax.tree.leaves(want))):
        raise Fail("the reference's weight layout is not the program's")
    D = counts.params_per_agent(want)
    itemsize = jnp.dtype(cfg.dtype).itemsize

    def placed(shardings):
        # on a mesh, each chip makes only its own shards
        return {} if shardings is None else {"out_shardings": shardings}

    # the key is an argument, not a constant: one program for every seed
    weights = jax.jit(lambda k: ref.init(k, sizes), **placed(
        check.without_agent_axis(prog.params_shardings)))
    state = jax.jit(lambda k: init_state(ref.init(k, sizes), m,
                                         algorithm=pargs.algorithm),
                    **placed(prog.state_shardings))(wkey)
    if prog.mesh is not None:
        leaves = jax.tree.leaves(state.params)
        note(phase="placement", mesh=dict(prog.mesh.shape),
             leaves=len(leaves), on_every_chip=sum(
                 {sh.device for sh in x.addressable_shards}
                 == set(devices[:chips])
                 and not x.sharding.is_fully_replicated for x in leaves))
    # the tokens are the program's own stream, seeded as the driver seeds it
    pipeline = make_lm_pipeline(cfg.vocab_size, m, B, S, seed=args.seed)
    key = jax_key(args.seed, "step_keys")
    spans = Spans()
    n_compiles = [0]

    def on_event(name, *_, **__):
        if name in COMPILE_EVENTS:
            n_compiles[0] += 1

    with prefetch_chunks(pipeline, K, start_step=0, place=prog.place,
                         depth=pargs.prefetch_depth) as chunks:
        # set-up: compile ahead of time, then the first chunk through the
        # window's own call and feed; it is the chunk the check compares
        chunk = next(chunks)
        keys = keys0 = per_step_keys(key, 0, K)
        t = time.perf_counter()
        compiled = scanned.lower(state, chunk, keys).compile()
        hlo = compiled.as_text()
        calls = kernel_calls(hlo)
        if keep is not None:
            keep["hlo"] = hlo
        note(phase="compile", seconds=time.perf_counter() - t,
             compiled_bytes=compiled_bytes(compiled),
             tpu_custom_calls=calls)
        if require_tpu:
            missing = [k for k in cell.traffic["kernels"]
                       if not any(k in c for c in calls)]
            if missing:
                raise Fail(f"no {missing} kernel in the compiled step; "
                           f"custom calls: {calls}")
        state, aux = compiled(state, chunk, keys)
        first = {name: [float(x) for x in np.asarray(aux[k])]
                 for name, k in (("losses", "loss"),
                                 ("consensus", "consensus_error"))}
        x0 = weights(wkey)
        first["change"] = np.asarray(check.leaf_change_norms(state.params,
                                                             x0))
        del x0, chunk, aux
        note(phase="first_chunk", losses=first["losses"],
             consensus=first["consensus"])
        k = K
        per_step_keys(key, k, K).block_until_ready()

        tracer = None
        if args.trace:
            tracer = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tracer)
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_event)
        losses = []
        t_window = time.perf_counter()
        setup_s = t_window - T_START
        with spans("window"):
            pending = None
            while True:
                with spans("next_chunk"):
                    chunk = next(chunks)
                with spans("dispatch"):
                    keys = per_step_keys(key, k, K)
                    state, aux = compiled(state, chunk, keys)
                k += K
                if pending is not None:
                    with spans("wait"):
                        losses += np.asarray(pending).tolist()
                pending = aux["loss"]
                if time.perf_counter() - t_window >= args.seconds:
                    break
            with spans("wait"):
                jax.block_until_ready(state)
                losses += np.asarray(pending).tolist()
        window_s = time.perf_counter() - t_window
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_event)
    note(phase="window", seconds=window_s, steps=len(losses),
         compiles_in_window=n_compiles[0])

    stats = [d.memory_stats() or {} for d in devices[:chips]]
    mem = {"peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
           "bytes_in_use": [s.get("bytes_in_use") for s in stats],
           "compiled_bytes": compiled_bytes(compiled)}
    note(phase="memory", **mem)
    # measured only: the compiler's count stays on the diagnostic line
    device["memory_peak_bytes"] = max(
        [v for v in mem["peak_bytes_in_use"] if v], default=0)

    reduced = None
    if tracer:
        from bench import trace as T
        jax.profiler.stop_trace()
        reduced = T.load(T.find_xplane(tracer))
        shutil.rmtree(tracer, ignore_errors=True)

    del state, aux, pending, compiled
    gc.collect()

    # the output check: the first chunk against the plain reference
    refres = check.reference_chunk(
        ref, sizes, weights(wkey), pipeline.chunk_at(0, K), keys0,
        m=m, algorithm=pargs.algorithm, lr=pargs.lr,
        hold=pargs.warmup_hold, seed=args.seed,
        shardings=prog.params_shardings)
    nums = check.numbers(first, refres)
    correct, rows = check.judge(nums, cell.limits)
    note(phase="compare", program=_listed(first), reference=_listed(refres),
         numbers=nums)

    tokens = len(losses) * B * S * m
    failed = sum(1 for x in losses if not math.isfinite(x))
    metrics = {}
    if not args.trace:
        e2e = {"tokens_per_s_per_chip": tokens / window_s / chips,
               "setup_s": setup_s}
        for mtr in cell.end_to_end:
            metrics[mtr["name"]] = {"value": e2e[mtr["name"]],
                                    "unit": mtr["unit"]}
    else:
        from bench.regions import instruction_regions
        module, regions = instruction_regions(hlo)
        ctx = {"window_s": window_s, "steps": len(losses), "tokens": tokens,
               "chips": chips, "agents": m, "params_per_agent": D,
               "itemsize": itemsize, "peaks": peak,
               "flops_per_token": ref.flops_per_token(sizes, S),
               "spans": spans.done, "trace": reduced,
               "module": module, "regions": regions,
               "counts": counts, "read": lambda n: metric_reader(root, n)(ctx)}
        from bench import trace as T
        lo, hi = reduced.window()
        planes = sorted(reduced.device_ops)[:chips]
        note(phase="trace", planes=planes, of=sorted(reduced.device_ops))
        busy = [T.busy_ns(reduced.device_ops[p], lo, hi) for p in planes]
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        ctx.update(trace_window=(lo, hi), planes=planes)
        if keep is not None:
            keep["ctx"] = ctx
        for mtr in cell.per_layer:
            v = metric_reader(root, mtr["name"])(ctx)
            if v is not None:
                metrics[mtr["name"]] = {"value": v, "unit": mtr["unit"]}
        ops = [o for p in planes for o in T.clip(reduced.device_ops[p],
                                                 lo, hi)]
        breakdown = {
            "device_ops": T.top_ops(ops),
            "idle_gaps": T.labelled_gaps(
                [o for o in reduced.device_ops[planes[0]]],
                reduced.host_spans, lo, hi)} if planes else None
    out = {"correct": correct, "attempted": len(losses), "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace and breakdown:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"check {n} = {v!r} (limit {lim!r})", file=sys.stderr)
    return out


def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> int:
    args = parse(argv)
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    try:
        result = run(args, root, require_tpu)
    except Fail as e:
        print(f"bench: {e}", file=sys.stderr)
        # the last line of standard output is never a result, nor a note
        print(f"bench: no result: {e}", flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

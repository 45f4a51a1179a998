"""The full-depth granite cell on a v5e 2x2 mesh, what it adds, on the CPU.

* `refs/moe_scan.py` walks the layers with ``lax.scan`` and must give
  `refs/moe.py`'s loss and gradient on the same seeded weights: the two
  compute the same float32 sums, in the same order inside a layer, so
  they agree to float32 round-off (LOSS_RTOL 1e-6, GRAD_RTOL 1e-5; the
  scan compiles the layer once, which may fuse and so round differently).
* The configuration file is the published model: ``arch_config`` reads
  it, every size equals the registry's, and nothing is reduced.
* The four readers on a trace laid out by hand, with known region and
  collective times.
"""
import json

import jax
import numpy as np
import pytest

from bench import collectives as C
from bench import trace as T
from bench.refs import moe, moe_scan
from bench.run import ROOT, arch_config, metric_reader

LOSS_RTOL = 1e-6
GRAD_RTOL = 1e-5
CELL = "granite-moe-1b-a400m-fsdp2x2.pdsgd.b4s1024"
CONFIG = ROOT / "bench" / "configs" / "granite-moe-1b-a400m-fsdp2x2.json"

SMALL = {"d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
         "d_ff": 32, "vocab_size": 200, "num_experts": 4,
         "num_experts_per_tok": 2, "capacity_factor": 1.25,
         "rope_theta": 10000.0, "dtype": "float32"}


@pytest.mark.parametrize("layers", [2, 3])
@pytest.mark.parametrize("remat", [False, True])
def test_scanned_reference_is_the_unrolled_one(layers, remat):
    s = dict(SMALL, num_layers=layers)
    params = moe.init(jax.random.key(5), s)
    rng = np.random.default_rng(5)
    t = rng.integers(0, s["vocab_size"], (2, 17)).astype(np.int32)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:]}
    with jax.default_matmul_precision("highest"):
        lu, gu = jax.value_and_grad(moe.loss)(params, batch, s, remat=remat)
        ls, gs = jax.jit(jax.value_and_grad(
            lambda p, b: moe_scan.loss(p, b, s, remat=remat)))(params, batch)
    assert abs(float(ls) - float(lu)) <= LOSS_RTOL * abs(float(lu))
    for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(gu)):
        assert float(np.linalg.norm(a - b)) <= GRAD_RTOL * float(
            np.linalg.norm(b))


def test_scanned_reference_shares_the_layout_and_counts():
    s = dict(SMALL, num_layers=2)
    assert moe_scan.layout(s) == moe.layout(s)
    assert moe_scan.flops_per_token(s, 16) == moe.flops_per_token(s, 16)
    a = moe_scan.init(jax.random.key(1), s)
    b = moe.init(jax.random.key(1), s)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_configuration_is_the_published_model():
    from repro.configs import get_config
    conf = json.loads(CONFIG.read_text())
    cfg = arch_config(conf)
    reg = get_config(conf["base"])
    assert cfg.num_layers == reg.num_layers == 24
    assert cfg.scan_layers
    for k, v in conf["sizes"].items():
        if k != "scan_layers":
            assert getattr(reg, k) == v, k
    assert conf["reduced"] == [] and conf["reference"] == "moe_scan"
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bm["configs"] if c["file"] ==
                "bench/configs/granite-moe-1b-a400m-fsdp2x2.json"]
    assert entry["reduced"] == [] and entry["source"] == conf["source"]
    (cell,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["config"] == entry["name"]


# ---------------------------------------------------------------------------
# The readers on a trace laid out by hand
# ---------------------------------------------------------------------------

# instruction -> region; the program's region names (`repro.trace`)
REGIONS = {
    "fusion.1": "step.model/fwd",
    "all-gather-start.1": "step.model/fwd",
    "all-gather-done.1": "step.model/fwd",
    "fusion.2": "step.model/bwd",
    "reduce-scatter.4": "step.model/bwd",
    "fusion.3": "step.update/obfuscate",
    "_obfuscate_update.5": "step.update/obfuscate",
    "all-gather.7": "step.update/gossip",
    "multiply_reduce_fusion.6": "step.update/gossip",
    "all-reduce-start.2": "step.update/gossip",
    "all-reduce-done.2": "step.update/gossip",
    "copy.9": "step.update/layout",
    "fusion.11": "step.update",
    "fusion.10": "step.report",
    "all-gather-start.12": None,
}
# (name, start, end) ns of one step module run; the collective's own
# time is what no op nested in it takes
OPS = [("fusion.1", 0, 100), ("all-gather-start.1", 100, 120),
       ("all-gather-done.1", 120, 130), ("fusion.2", 130, 300),
       ("reduce-scatter.4", 300, 340), ("fusion.3", 340, 400),
       ("_obfuscate_update.5", 400, 450), ("all-gather.7", 450, 500),
       ("multiply_reduce_fusion.6", 500, 540),
       ("all-reduce-start.2", 540, 550), ("all-reduce-done.2", 550, 560),
       ("copy.9", 560, 580), ("fusion.11", 580, 585),
       ("fusion.10", 585, 620), ("all-gather-start.12", 620, 630)]
STEPS = 2     # per plane: the module runs once, holding two steps' work


def _ctx(planes=2):
    names = [f"/device:TPU:{i}" for i in range(planes)]
    ops = OPS + [("all-gather.7", 700, 900)]   # another module's op
    reduced = T.Reduced(
        device_ops={p: list(ops) for p in names}, host_spans=[],
        modules={p: [("jit_scanned(3)", 0, 650), ("jit_other(4)", 650, 1000)]
                 for p in names},
        program_spans=[])
    from bench import counts
    ctx = {"trace": reduced, "trace_window": (0, 1000), "planes": names,
           "steps": STEPS, "module": "jit_scanned", "regions": REGIONS,
           "agents": 2, "params_per_agent": 1000, "itemsize": 2,
           "chips": planes, "counts": counts,
           "peaks": {"hbm_bytes_per_s": 1e12}}
    ctx["read"] = lambda n: metric_reader(ROOT, n)(ctx)
    return ctx


def _ms(ns):    # ns of one plane's run -> ms per step and chip
    return ns / 1e6 / STEPS


def test_collectives_are_named_by_their_opcode():
    for name in ("all-gather.7", "all-gather-start.1", "all-reduce-done.2",
                 "reduce-scatter.4", "collective-permute-start.3",
                 "all-to-all", "all-reduce", "async-collective-start.3",
                 "async-collective-done.12"):
        assert C.is_collective(name), name
    for name in ("fusion.1", "all-gather-fusion.2", "copy.9",
                 "_obfuscate_update.5", "multiply_reduce_fusion.6",
                 "async-collective-fusion.1", "copy-start.4"):
        assert not C.is_collective(name), name


@pytest.mark.parametrize("name, ns", [
    ("update_ms", 60 + 50 + 50 + 40 + 10 + 10 + 20 + 5),
    ("update_collective_ms", 50 + 10 + 10),
    ("model_collective_ms", 20 + 10 + 40),
])
def test_readers_on_a_known_trace(name, ns):
    assert metric_reader(ROOT, name)(_ctx()) == pytest.approx(_ms(ns))
    assert metric_reader(ROOT, name)(_ctx(planes=1)) == pytest.approx(
        _ms(ns))
    # no trace or no plane: nothing to read, never 0
    for empty in (dict(trace=None), dict(planes=[])):
        assert metric_reader(ROOT, name)(dict(_ctx(), **empty)) is None


def test_update_children_sum_to_update_ms_and_collectives_fit():
    ctx = _ctx()
    read = ctx["read"]
    children = sum(C.region_ms(ctx, "step.update/" + c) for c in
                   ("layout", "obfuscate", "gossip"))
    own = C.region_ms(ctx, "step.update") - children
    assert own == pytest.approx(_ms(5))           # the update's own work
    assert read("update_collective_ms") <= C.region_ms(
        ctx, "step.update/gossip")
    assert read("model_collective_ms") <= C.region_ms(ctx, "step.model")


def test_update_all_roofline_is_the_least_bytes_over_update_ms():
    ctx = _ctx()
    least = 3 * 2 * 1000 * 2 / 2 / 1e12            # s, each chip's share
    want = 100 * least / (_ms(245) / 1e3)
    assert metric_reader(ROOT, "update_all_roofline")(ctx) == \
        pytest.approx(want)
    assert metric_reader(ROOT, "update_all_roofline")(
        dict(ctx, peaks=None)) is None


def test_readers_read_nothing_where_the_program_has_no_such_work():
    """On one chip the gossip and the model run no collective: those
    readers are silent, not 0."""
    ctx = _ctx()
    ctx["regions"] = {k: v for k, v in REGIONS.items()
                      if not C.is_collective(k)}
    for name in ("update_collective_ms", "model_collective_ms"):
        assert metric_reader(ROOT, name)(ctx) is None
    assert metric_reader(ROOT, "update_ms")(ctx) > 0

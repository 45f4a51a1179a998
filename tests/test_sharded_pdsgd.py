"""Sharded big-model PDSGD (the FSDP/tensor x gossip composition).

Three layers of pins:

* kernels: `sharded_pdsgd_tree` (leafwise) is bit-identical to
  `fused_pdsgd_tree` (concat) across random pytrees and agent counts —
  obfuscate is elementwise and the gossip matmuls contract only the
  agent dim, so per-leaf == same columns of the concatenated buffer.
* steps: on a trivially-sharded (1,1,1) mesh the whole training step —
  mesh-built model, spmd_axis_name'd agent vmap, leafwise kernels — is
  bit-identical to the historical dense path.
* mesh: the real composition (agents=2, fsdp=2) under fake devices in a
  subprocess: params/optimizer state actually shard over "fsdp", the
  step runs, and the loss stays finite.

Plus unit coverage for `dist.sharding.audit_rules` and
`optim.shard_like`.
"""
import json
import os
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.privacy import sample_B
from repro.kernels import fused_pdsgd_tree
from repro.kernels.ops import sharded_pdsgd_tree

RNG = np.random.default_rng(0)


def _coupling(m, seed):
    sup = jnp.ones((m, m), jnp.float32)
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.dirichlet(np.ones(m), m).T.astype(np.float32))
    B = sample_B(jax.random.key(seed), sup)
    return W, B


def _trees(m, seed, shapes):
    rng = np.random.default_rng(seed + 1)
    x = {k: jnp.asarray(rng.standard_normal((m,) + s).astype(np.float32))
         for k, s in shapes.items()}
    g = {k: jnp.asarray(rng.standard_normal((m,) + s).astype(np.float32))
         for k, s in shapes.items()}
    bits = {k: jax.random.bits(jax.random.fold_in(jax.random.key(seed), i),
                               (m,) + s, dtype=jnp.uint32)
            for i, (k, s) in enumerate(shapes.items())}
    return x, g, bits


# deliberately awkward leaf shapes: odd column counts, rank 1-3, so the
# per-leaf pad/unpad never lines up with the concat pad
_SHAPES = {"emb": (5, 7), "w": (33,), "b": (3, 2, 2)}


@settings(max_examples=12, deadline=None)
@given(m=st.sampled_from([2, 3, 5]), seed=st.integers(0, 40),
       masked=st.sampled_from([False, True]))
def test_leafwise_matches_concat_bitwise(m, seed, masked):
    """Property: per-leaf kernel results == the same columns of the one
    concatenated (m, ΣD) pass, bit for bit — plain and masked gossip."""
    W, B = _coupling(m, seed)
    x, g, bits = _trees(m, seed, _SHAPES)
    mask = None
    if masked:
        mask = jnp.asarray((np.random.default_rng(seed)
                            .random((m, m)) > 0.3).astype(np.float32))
        mask = mask * mask.T * (1 - jnp.eye(m))
    lam = jnp.float32(0.05)
    ref = fused_pdsgd_tree(W, B, x, g, bits, lam, mask=mask, interpret=True)
    out = sharded_pdsgd_tree(W, B, x, g, bits, lam, mask=mask,
                             interpret=True)
    for k in _SHAPES:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(out[k])), k


def test_leafwise_mesh_trivial_matches_concat_bitwise():
    """The mesh flavor (the kernel's Lambda o g as an XLA fusion, einsum
    gossip) on a trivially-sharded 1-device mesh matches concat.  The
    obfuscate stage is the kernel's math, but the gossip stage is an XLA
    einsum over each (m, ...) leaf instead of the kernel's blocked
    (m, m) @ (m, bn) dot: XLA may order or FMA-contract the m-term sums
    differently, so the two agree to float32 rounding (a few ulp on O(1)
    values), not bitwise."""
    from jax.sharding import PartitionSpec as P
    m, seed = 4, 7
    mesh = jax.make_mesh((1, 1, 1), ("data", "fsdp", "model"),
                         devices=jax.devices()[:1])
    W, B = _coupling(m, seed)
    x, g, bits = _trees(m, seed, _SHAPES)
    specs = {k: P(*((None,) * (len(s) + 1))) for k, s in _SHAPES.items()}
    lam = jnp.float32(0.1)
    ref = fused_pdsgd_tree(W, B, x, g, bits, lam, interpret=True)
    out = sharded_pdsgd_tree(W, B, x, g, bits, lam, interpret=True,
                             mesh=mesh, leaf_specs=specs)
    for k in _SHAPES:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-6, atol=1e-6, err_msg=k)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_mesh_obfuscation_is_the_kernels_bit_for_bit(dtype):
    """The mesh path's u = Lambda o g, rounded to the parameters' dtype,
    is what the obfuscate kernel writes for the same bits."""
    from repro.kernels.obfuscate import obfuscate_update, obfuscated
    m, n = 2, 4096
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k1, (m, n)).astype(dtype)
    g = jax.random.normal(k2, (m, n)).astype(dtype)
    bits = jax.random.bits(k3, (m, n), dtype=jnp.uint32)
    lam = jnp.float32(0.05)
    want = obfuscate_update(x, g, bits, lam, jnp.float32(0.0),
                            jnp.float32(-1.0), interpret=True)
    got = jax.jit(lambda g, b: obfuscated(g, b, lam).astype(dtype))(g, bits)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


def test_sharded_tree_mesh_needs_specs_and_refuses_corrupt():
    m, seed = 2, 3
    mesh = jax.make_mesh((1, 1, 1), ("data", "fsdp", "model"),
                         devices=jax.devices()[:1])
    W, B = _coupling(m, seed)
    x, g, bits = _trees(m, seed, _SHAPES)
    with pytest.raises(ValueError, match="leaf_specs"):
        sharded_pdsgd_tree(W, B, x, g, bits, 0.1, mesh=mesh)
    with pytest.raises(NotImplementedError, match="fault"):
        sharded_pdsgd_tree(W, B, x, g, bits, 0.1, mesh=mesh,
                           leaf_specs={}, corrupt=jnp.ones((m,)))


# -- audit_rules ----------------------------------------------------------


def _duck_mesh(**shape):
    return types.SimpleNamespace(shape=dict(shape))


def test_audit_rules_flags_unknown_axes_as_errors():
    from repro.dist.sharding import audit_rules
    abstract = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
    logical = {"w": ("embed", "made_up_axis")}
    out = audit_rules(abstract, logical, _duck_mesh(data=2, fsdp=2, model=1))
    assert len(out) == 1
    f = out[0]
    assert f["severity"] == "error"
    assert "made_up_axis" in f["issue"] and "w" in f["path"]


def test_audit_rules_info_on_replicated_with_spare_capacity():
    from repro.dist.sharding import audit_rules
    # 'embed' with dim 7 divides neither fsdp=2 nor anything else ->
    # fully replicated while the mesh has spare capacity: info, not error
    abstract = {"w": jax.ShapeDtypeStruct((7, 3), jnp.float32)}
    logical = {"w": ("embed", "seq")}
    out = audit_rules(abstract, logical, _duck_mesh(data=1, fsdp=2, model=1))
    assert [f["severity"] for f in out] == ["info"]
    # ...and silence on a trivial mesh, where replication is the point
    assert audit_rules(abstract, logical,
                       _duck_mesh(data=1, fsdp=1, model=1)) == []


def test_audit_rules_clean_on_every_model_bundle():
    """Every registered arch resolves every logical axis — the lint that
    found (and now guards) the missing 'ssm_heads' rule."""
    from repro.configs import ARCH_NAMES, get_config, tiny_variant
    from repro.dist.sharding import audit_rules
    from repro.models import build_model
    mesh = _duck_mesh(data=2, fsdp=2, model=2)
    for name in ARCH_NAMES:
        bundle = build_model(tiny_variant(get_config(name)))
        errs = [f for f in audit_rules(bundle.abstract(),
                                       bundle.logical_axes(), mesh)
                if f["severity"] == "error"]
        assert errs == [], (name, errs)


# -- optim.shard_like -----------------------------------------------------


def test_shard_like_matches_params_congruent_subtrees():
    from repro.optim import adam, shard_like
    params = {"w": jnp.zeros((2, 4, 4)), "b": jnp.zeros((2, 4))}
    state = adam(1e-3).init(params)
    psh = {"w": "W_SHARDING", "b": "B_SHARDING"}
    out = shard_like(state, params, psh, scalar_sharding="SCALAR")
    leaves = jax.tree.leaves(out)
    # adam: count scalar + mu + nu params-shaped subtrees
    assert leaves.count("W_SHARDING") == 2
    assert leaves.count("B_SHARDING") == 2
    assert leaves.count("SCALAR") == 1


def test_shard_like_rejects_shape_mismatched_lookalikes():
    from repro.optim import shard_like
    params = {"w": jnp.zeros((4, 4))}
    # same treedef, different leaf shape: must NOT shard like params
    state = {"stats": {"w": jnp.zeros((3,))}, "buf": {"w": jnp.zeros((4, 4))}}
    out = shard_like(state, params, {"w": "PSH"}, scalar_sharding="SC")
    assert out["buf"] == {"w": "PSH"}
    # the lookalike is NOT matched as a params subtree; its array leaf
    # falls through to the scalar sharding
    assert out["stats"] == {"w": "SC"}


def test_shard_like_on_decentralized_state():
    from repro.core.pdsgd import DecentralizedState
    from repro.optim import shard_like
    params = {"w": jnp.zeros((2, 8))}
    state = DecentralizedState(params=params, step=jnp.int32(0))
    out = shard_like(state, state.params, {"w": "PSH"},
                     scalar_sharding="SC")
    assert out.params == {"w": "PSH"}
    assert out.step == "SC"


# -- trivial-mesh bit-parity of the whole training step -------------------


def _tiny_problem(mesh=None, scan_layers=False):
    import dataclasses
    from repro.configs import get_config, tiny_variant
    from repro.models import build_model
    cfg = tiny_variant(get_config("stablelm-3b"))
    if scan_layers:
        cfg = dataclasses.replace(cfg, scan_layers=True)
    return cfg, build_model(cfg, mesh=mesh)


def _run_steps(step_fn, bundle, m, n_steps, batch_fn):
    from repro.core import init_state
    state = init_state(bundle.init(jax.random.key(0)), m)
    losses = []
    for k in range(n_steps):
        state, aux = step_fn(state, batch_fn(k), jax.random.fold_in(
            jax.random.key(1), k))
        losses.append(float(aux["loss"]))
    return state, losses


def _leaf_specs_for(bundle, mesh, m):
    from repro.dist.sharding import TRAIN_RULES, logical_spec
    from repro.launch.specs import with_agent_axis
    p_abs, p_log = with_agent_axis(bundle.abstract(), bundle.logical_axes(),
                                   m)
    return jax.tree.map(
        lambda a, log: logical_spec(mesh, a.shape, log, TRAIN_RULES),
        p_abs, p_log)


def test_trivial_mesh_step_bitwise_identical_to_dense():
    """make_decentralized_step with the full sharded plumbing engaged —
    mesh-built model, spmd_axis_name, leafwise layout, leaf_specs — on a
    1-device (1,1,1) mesh walks the EXACT dense trajectory."""
    from repro.core import make_decentralized_step, make_topology
    from repro.core.mixing import as_process
    from repro.core.schedules import warmup_harmonic
    from repro.data import make_lm_pipeline

    m, steps = 4, 3
    process = as_process(make_topology("ring", m))
    sched = warmup_harmonic(0.4, hold=10)

    cfg, dense = _tiny_problem()
    mesh = jax.make_mesh((1, 1, 1), ("data", "fsdp", "model"),
                         devices=jax.devices()[:1])
    _, sharded = _tiny_problem(mesh=mesh)
    pipeline = make_lm_pipeline(cfg.vocab_size, m, 2, 16, seed=3)
    batch = lambda k: pipeline.batch_at(k)

    step_a = make_decentralized_step(dense.loss_fn, process, sched)
    step_b = make_decentralized_step(
        sharded.loss_fn, process, sched, spmd_axis_name="data",
        kernel_layout="leafwise", mesh=mesh,
        leaf_specs=_leaf_specs_for(sharded, mesh, m))

    state_a, loss_a = _run_steps(step_a, dense, m, steps, batch)
    state_b, loss_b = _run_steps(step_b, sharded, m, steps, batch)
    assert loss_a == loss_b
    for ka, kb in zip(jax.tree.leaves(state_a.params),
                      jax.tree.leaves(state_b.params)):
        assert np.array_equal(np.asarray(ka), np.asarray(kb))


def test_leafwise_step_matches_concat_step():
    """kernel_layout='leafwise' vs 'concat' on the fused-Pallas path:
    identical losses, params equal to FMA tolerance.  The kernels
    themselves are bit-identical (the property above pins that outside
    jit); inside the jitted step the CPU interpreter inlines the kernel
    bodies as ordinary ops, and XLA's fusion choices around the two
    different graph shapes reassociate an FMA or two — a few 1e-10-level
    ULPs on ~2 leaves, not a math difference."""
    from repro.core import make_decentralized_step, make_topology
    from repro.core.mixing import as_process
    from repro.core.schedules import warmup_harmonic
    from repro.data import make_lm_pipeline

    m, steps = 4, 2
    process = as_process(make_topology("ring", m))
    sched = warmup_harmonic(0.4, hold=10)
    cfg, bundle = _tiny_problem()
    pipeline = make_lm_pipeline(cfg.vocab_size, m, 1, 8, seed=5)
    batch = lambda k: pipeline.batch_at(k)

    step_c = make_decentralized_step(bundle.loss_fn, process, sched,
                                     use_pallas=True, interpret=True,
                                     kernel_layout="concat")
    step_l = make_decentralized_step(bundle.loss_fn, process, sched,
                                     use_pallas=True, interpret=True,
                                     kernel_layout="leafwise")
    state_c, loss_c = _run_steps(step_c, bundle, m, steps, batch)
    state_l, loss_l = _run_steps(step_l, bundle, m, steps, batch)
    assert loss_c == loss_l
    for kc, kl in zip(jax.tree.leaves(state_c.params),
                      jax.tree.leaves(state_l.params)):
        np.testing.assert_allclose(np.asarray(kc), np.asarray(kl),
                                   rtol=0, atol=1e-8)


def test_scan_layers_loss_matches_unrolled():
    """cfg.scan_layers rolls the layer stack into one lax.scan; same
    params, same batch, same loss bits as the unrolled loop."""
    cfg, unrolled = _tiny_problem()
    _, scanned = _tiny_problem(scan_layers=True)
    params = unrolled.init(jax.random.key(2))
    from repro.data import make_lm_pipeline
    batch = make_lm_pipeline(cfg.vocab_size, 1, 2, 16, seed=9).batch_at(0)
    one = {k: jnp.asarray(v[0]) for k, v in batch.items()}
    la = unrolled.loss_fn(params, one)
    lb = scanned.loss_fn(params, one)
    assert np.array_equal(np.asarray(la), np.asarray(lb))


# -- the real composition: agents x fsdp under fake devices ---------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, {src!r})
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, tiny_variant
    from repro.core import (init_state, make_decentralized_step,
                            make_topology)
    from repro.core.mixing import as_process
    from repro.core.schedules import warmup_harmonic
    from repro.data import make_lm_pipeline
    from repro.dist.sharding import TRAIN_RULES, audit_rules, logical_spec
    from repro.launch.mesh import make_sharded_mesh
    from repro.launch.specs import with_agent_axis
    from repro.models import build_model
    from repro.optim import shard_like

    m = 2
    mesh = make_sharded_mesh(agents=m, fsdp=2, tensor=1)
    assert dict(mesh.shape) == {{"data": 2, "fsdp": 2, "model": 1}}, \\
        dict(mesh.shape)

    import dataclasses
    cfg = tiny_variant(get_config("stablelm-3b"))
    cfg = dataclasses.replace(cfg, d_model=64, d_ff=128)  # divisible by 2
    bundle = build_model(cfg, mesh=mesh)
    assert [f for f in audit_rules(bundle.abstract(),
                                   bundle.logical_axes(), mesh)
            if f["severity"] == "error"] == []

    p_abs, p_log = with_agent_axis(bundle.abstract(),
                                   bundle.logical_axes(), m)
    leaf_specs = jax.tree.map(
        lambda a, log: logical_spec(mesh, a.shape, log, TRAIN_RULES),
        p_abs, p_log)
    # the composition is real: agents ride "data", embed dims ride "fsdp"
    flat_specs = jax.tree.leaves(
        leaf_specs, is_leaf=lambda s: isinstance(s, P))
    assert any("fsdp" in s for s in flat_specs), flat_specs
    assert all(s[0] == "data" for s in flat_specs), flat_specs

    params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), leaf_specs)
    state = init_state(bundle.init(jax.random.key(0)), m)
    state_sh = shard_like(state, state.params, params_sh,
                          scalar_sharding=NamedSharding(mesh, P()))
    state = jax.device_put(state, state_sh)
    # placement took: at least one param leaf is physically split
    n_sharded = sum(
        0 if l.sharding.is_fully_replicated else 1
        for l in jax.tree.leaves(state.params))
    assert n_sharded > 0

    process = as_process(make_topology("ring", m))
    step = make_decentralized_step(
        bundle.loss_fn, process, warmup_harmonic(0.4, hold=10),
        spmd_axis_name="data", kernel_layout="leafwise", mesh=mesh,
        leaf_specs=leaf_specs)
    pipeline = make_lm_pipeline(cfg.vocab_size, m, 2, 16, seed=0)
    losses = []
    for k in range(3):
        state, aux = step(state, pipeline.batch_at(k),
                          jax.random.fold_in(jax.random.key(1), k))
        losses.append(float(aux["loss"]))
    out_sharded = sum(
        0 if l.sharding.is_fully_replicated else 1
        for l in jax.tree.leaves(state.params))
    print(json.dumps({{"losses": losses, "n_sharded": n_sharded,
                       "out_sharded": out_sharded}}))
""")


def test_agents_times_fsdp_mesh_composition_subprocess():
    """agents=2 x fsdp=2 on 4 fake devices: the audit passes, params and
    optimizer state land sharded, the leafwise step runs, the loss is
    finite, and the update preserves the sharding (no silent gather)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _MESH_SCRIPT.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res["losses"]) == 3
    assert all(np.isfinite(l) for l in res["losses"])
    assert res["n_sharded"] > 0
    assert res["out_sharded"] == res["n_sharded"]


_REPORT_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {src!r})
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, tiny_variant
    from repro.core import (init_state, make_decentralized_step,
                            make_topology)
    from repro.core.mixing import as_process
    from repro.core.schedules import warmup_harmonic
    from repro.data import make_lm_pipeline
    from repro.dist.sharding import TRAIN_RULES, logical_spec
    from repro.launch.mesh import make_sharded_mesh
    from repro.launch.specs import with_agent_axis
    from repro.models import build_model
    from repro.optim import shard_like

    m = 2
    mesh = make_sharded_mesh(agents=m, fsdp=2, tensor=1)
    cfg = dataclasses.replace(tiny_variant(get_config("stablelm-3b")),
                              d_model=64, d_ff=128, dtype="bfloat16")
    bundle = build_model(cfg, mesh=mesh)
    p_abs, p_log = with_agent_axis(bundle.abstract(),
                                   bundle.logical_axes(), m)
    leaf_specs = jax.tree.map(
        lambda a, log: logical_spec(mesh, a.shape, log, TRAIN_RULES),
        p_abs, p_log)
    params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), leaf_specs)
    state = init_state(bundle.init(jax.random.key(0)), m)
    state = jax.device_put(state, shard_like(
        state, state.params, params_sh,
        scalar_sharding=NamedSharding(mesh, P())))
    step = make_decentralized_step(
        bundle.loss_fn, as_process(make_topology("ring", m)),
        warmup_harmonic(0.4, hold=200), spmd_axis_name="data",
        kernel_layout="leafwise", mesh=mesh, leaf_specs=leaf_specs)
    batch = make_lm_pipeline(cfg.vocab_size, m, 4, 16, seed=0).batch_at(0)
    state, aux = step(state, batch, jax.random.key(3))
    stored = 0.0
    for leaf in jax.tree.leaves(state.params):
        p = np.asarray(leaf.astype(jnp.float32))
        mean = np.asarray(jnp.asarray(p.mean(0), leaf.dtype)
                          .astype(jnp.float32))
        stored += float(np.sum(np.square(p - mean), dtype=np.float64))
    print(json.dumps({{"dtype": str(jax.tree.leaves(state.params)[0].dtype),
                       "reported": float(aux["consensus_error"]),
                       "stored": stored}}))
""")


def test_mesh_step_reports_the_consensus_of_its_stored_parameters():
    """The consensus error a mesh step reports is the statistic of the
    parameters it stores: each agent's bf16 values less their agent mean
    rounded to bf16, squared and summed in float32 (the benchmark
    reference's rule), to float32 round-off (RTOL 1e-5 over a few
    thousand terms).  A report computed in bf16, or one the compiler may
    feed the update before its rounding, misses it: the bf16 squares and
    sums alone are off by ~1e-3."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _REPORT_SCRIPT.format(src=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["dtype"] == "bfloat16"
    assert res["stored"] > 0
    assert abs(res["reported"] - res["stored"]) <= 1e-5 * res["stored"]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_stored_consensus_rounds_to_the_parameter_dtype(dtype):
    """`consensus_error(stored=True)` of a leaf held in ``dtype`` is the
    float32 statistic of its values with the agent mean rounded to
    ``dtype``; `_as_stored` rounds float32 to nearest even exactly as a
    cast to ``dtype`` and back does."""
    from repro.core.pdsgd import _as_stored, consensus_error
    x = jax.random.normal(jax.random.key(0), (3, 257)) * 0.02
    assert np.array_equal(np.asarray(_as_stored(x, dtype)),
                          np.asarray(x.astype(dtype).astype(jnp.float32)))
    p = (x + 1e-4 * jax.random.normal(jax.random.key(1), (3, 257))
         ).astype(dtype)
    q = np.asarray(p.astype(jnp.float32))
    mean = np.asarray(jnp.asarray(q.mean(0), dtype).astype(jnp.float32))
    want = float(np.sum(np.square(q - mean), dtype=np.float64))
    got = float(consensus_error({"w": p}, stored=True))
    assert abs(got - want) <= 1e-6 * want

"""Compile the main path's Pallas kernels for a TPU v5e chip, without one.

The TPU compiler is installed with JAX, and compiles for a chip that is
described rather than attached (``topologies.get_topology_desc``).  Each
test lowers one kernel at xlstm-125m width (m=4 agents, 95.6M bf16 params
each, flattened and padded as `kernels.ops.fused_pdsgd_tree` does) or at
the granite-moe one-chip cut's width with the column block that
`kernels.blocks` picks, and compiles it through Mosaic: what interpret
mode accepts but the chip's compiler refuses (tile alignment, VMEM
budget, PRNG seeding) fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gossip import (_gossip_update, _guarded_gossip_update,
                                  _masked_gossip_update_krng,
                                  _ring_obfuscate_gossip_krng, gossip_block)
from repro.kernels.obfuscate import (_obfuscate_update,
                                     _obfuscate_update_krng, obfuscate_block)

M = 4
D = 95_626_240  # xlstm-125m params per agent, padded to the 512 grid
BLOCK = (M, 256)
# granite-moe-1b-a400m at 3 layers: params per agent, a multiple of 512, so
# the fused update adds no pad
GRANITE = 211_393_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    return hlo


def _scalar(one_chip):
    return _sds(one_chip, (), jnp.float32)


def test_obfuscate_update_compiles(one_chip):
    x = _sds(one_chip, (M, D), jnp.bfloat16)
    bits = _sds(one_chip, (M, D), jnp.uint32)
    s = _scalar(one_chip)
    _compile(lambda x, g, b, lam: _obfuscate_update(
        x, g, b, lam, 0.0, -1.0, block=BLOCK, interpret=False),
        x, x, bits, s)


def test_obfuscate_update_krng_compiles(one_chip):
    x = _sds(one_chip, (M, D), jnp.bfloat16)
    seed = _sds(one_chip, (2,), jnp.uint32)
    s = _scalar(one_chip)
    _compile(lambda x, g, seed, lam: _obfuscate_update_krng(
        x, g, seed, lam, 0.0, -1.0, block=BLOCK, interpret=False),
        x, x, seed, s)


def _gossip_call(hlo):
    """The name of the compiled program's one gossip custom call."""
    names = [line.split("=", 1)[0].split("%")[-1].split(".")[0]
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(names) == 1, names
    return names[0]


# the granite cell's 4 agents, and 32, contract on the VPU
GOSSIP_FORMS = [(M, "_gossip_update_vpu"), (32, "_gossip_update_vpu")]


@pytest.mark.parametrize("m,form", GOSSIP_FORMS)
def test_gossip_update_compiles(one_chip, m, form):
    mat = _sds(one_chip, (m, m), jnp.float32)
    x = _sds(one_chip, (m, D * M // m), jnp.bfloat16)
    hlo = _compile(lambda w, b, x, u: _gossip_update(
        w, b, x, u, block_n=512, interpret=False), mat, mat, x, x)
    assert _gossip_call(hlo) == form


def test_masked_gossip_update_krng_compiles(one_chip):
    seed = _sds(one_chip, (2,), jnp.uint32)
    mat = _sds(one_chip, (M, M), jnp.float32)
    x = _sds(one_chip, (M, D), jnp.bfloat16)
    _compile(lambda seed, p, adj, b, x, u: _masked_gossip_update_krng(
        seed, p, adj, b, x, u, block_n=512, interpret=False),
        seed, _scalar(one_chip), mat, mat, x, x)


def test_ring_obfuscate_gossip_krng_compiles(one_chip):
    from repro.dist.collectives import perm_stack
    ndirs = perm_stack(M, 1).shape[0]
    tab = _sds(one_chip, (M, 1 + ndirs), jnp.float32)
    perms = _sds(one_chip, (ndirs, M, M), jnp.float32)
    x = _sds(one_chip, (M, D), jnp.bfloat16)
    seed = _sds(one_chip, (2,), jnp.uint32)
    _compile(lambda w, b, p, x, g, seed, lam: _ring_obfuscate_gossip_krng(
        w, b, p, x, g, seed, lam, capture=False, block_n=512,
        interpret=False), tab, tab, perms, x, x, seed, _scalar(one_chip))


def test_granite_block_is_wide():
    """The rule gives the granite update blocks of tens of thousands of
    columns, not 256 or 512: a few thousand grid steps a kernel, the last
    block overhanging the buffer."""
    for bc in (obfuscate_block(M, GRANITE, jnp.bfloat16, jnp.bfloat16),
               gossip_block(M, GRANITE, jnp.bfloat16)):
        assert bc >= 32768 and -(-GRANITE // bc) < 8000
        assert GRANITE % 512 == 0 and GRANITE % bc


def test_obfuscate_update_krng_compiles_granite(one_chip):
    x = _sds(one_chip, (M, GRANITE), jnp.bfloat16)
    seed = _sds(one_chip, (2,), jnp.uint32)
    _compile(lambda x, g, seed, lam: _obfuscate_update_krng(
        x, g, seed, lam, 0.0, -1.0, block=None, interpret=False),
        x, x, seed, _scalar(one_chip))


def test_obfuscate_update_compiles_granite(one_chip):
    x = _sds(one_chip, (M, GRANITE), jnp.bfloat16)
    bits = _sds(one_chip, (M, GRANITE), jnp.uint32)
    _compile(lambda x, g, b, lam: _obfuscate_update(
        x, g, b, lam, 0.0, -1.0, block=None, interpret=False),
        x, x, bits, _scalar(one_chip))


@pytest.mark.parametrize("m,form", GOSSIP_FORMS)
def test_gossip_update_compiles_granite(one_chip, m, form):
    """The granite update's gossip with the rule's block, at its 4 agents
    and over as many bytes at 32."""
    mat = _sds(one_chip, (m, m), jnp.float32)
    x = _sds(one_chip, (m, GRANITE * M // m), jnp.bfloat16)
    hlo = _compile(lambda w, b, x, u: _gossip_update(
        w, b, x, u, block_n=None, interpret=False), mat, mat, x, x)
    assert _gossip_call(hlo) == form


def test_masked_gossip_update_krng_compiles_granite(one_chip):
    seed = _sds(one_chip, (2,), jnp.uint32)
    mat = _sds(one_chip, (M, M), jnp.float32)
    x = _sds(one_chip, (M, GRANITE), jnp.bfloat16)
    _compile(lambda seed, p, adj, b, x, u: _masked_gossip_update_krng(
        seed, p, adj, b, x, u, block_n=None, interpret=False),
        seed, _scalar(one_chip), mat, mat, x, x)


def test_guarded_gossip_update_compiles_many_agents(one_chip):
    """The guarded kernel holds (m, m, bn) float32 per-link tensors: at
    32 agents its rule-picked block must still fit VMEM."""
    m, n = 32, 1 << 24
    mat = _sds(one_chip, (m, m), jnp.float32)
    x = _sds(one_chip, (m, n), jnp.bfloat16)
    _compile(lambda mk, b, x, u, xt, ut: _guarded_gossip_update(
        mk, b, x, u, xt, ut, clip=1e3, block_n=None, interpret=False),
        mat, mat, x, x, x, x)


@pytest.mark.parametrize("kernel_rng", [True, False])
def test_fused_pdsgd_tree_compiles(one_chip, kernel_rng):
    """The whole concat update over the real xlstm-125m parameter tree:
    both kernels of the step must reach the chip as Mosaic calls."""
    from repro.configs import get_config
    from repro.kernels import fused_pdsgd_tree
    from repro.models import build_model
    abstract = build_model(get_config("xlstm-125m")).abstract()
    tree = jax.tree.map(
        lambda a: _sds(one_chip, (M,) + a.shape, a.dtype), abstract)
    bits = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, jnp.uint32), tree)
    mat = _sds(one_chip, (M, M), jnp.float32)
    seed = _sds(one_chip, (2,), jnp.uint32)

    def update(w, b, x, g, bits, seed, lam):
        return fused_pdsgd_tree(
            w, b, x, g, None if kernel_rng else bits, lam, interpret=False,
            kernel_rng=kernel_rng, seed=seed if kernel_rng else None)

    hlo = _compile(update, mat, mat, tree, tree, bits, seed,
                   _scalar(one_chip))
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2, calls


def test_sharded_update_code_does_not_grow_with_depth(topo):
    """The leafwise update on a 2 agents x fsdp 2 mesh of the four chips,
    over one layer-stacked granite attention leaf: Lambda o g is an XLA
    fusion on each shard, no kernel, and the executable's code does not
    grow with the stack.  A kernel per shard needs the shard flattened to
    one row, a relayout whose code grew with the leaf: 29 MB a layer of
    the full granite step, which then compiled in 525 s."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import sharded_pdsgd_tree
    mesh = jax.make_mesh((2, 2, 1), ("data", "fsdp", "model"),
                         devices=topo.devices)
    spec = P("data", None, "fsdp", None)
    mat = _sds(NamedSharding(mesh, P()), (2, 2), jnp.float32)

    def code(layers):
        shape = (2, layers, 1024, 1024)
        x = _sds(NamedSharding(mesh, spec), shape, jnp.bfloat16)
        bits = _sds(NamedSharding(mesh, spec), shape, jnp.uint32)
        c = jax.jit(lambda w, b, x, g, bits: sharded_pdsgd_tree(
            w, b, {"w": x}, {"w": g}, {"w": bits}, jnp.float32(0.1),
            interpret=False, mesh=mesh, leaf_specs={"w": spec})).lower(
                mat, mat, x, x, bits).compile()
        assert "tpu_custom_call" not in c.as_text()
        return c.memory_analysis().generated_code_size_in_bytes

    assert code(8) <= code(2) + (256 << 10)

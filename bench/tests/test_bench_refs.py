"""The plain references against the program at a small size on the CPU,
on weights drawn from a seed, and the control precision failing the same
tolerances.

Tolerances, with their reasons:

* LOSS_RTOL 1e-5: program and reference both compute in float32 here;
  they differ in summation order (the program's chunked mLSTM and sorted
  expert buffers against the reference's parallel and dense forms), which
  moves a loss of ~7 by float32 round-off, ~1e-7 relative.
* GRAD_RTOL 1e-4: per leaf, ||g_prog - g_ref|| / ||g_ref||; the backward
  pass accumulates more round-off than the loss, ~1e-6 here.
* The control computes every contraction in bfloat16, the precision below
  the float32 these small configurations state; its gradients are off by
  ~1e-3 relative, so it must fail GRAD_RTOL.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.refs import common, moe, update, xlstm

LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4

SMALL = {
    "xlstm": ("xlstm-125m-smoke", xlstm,
              {"num_layers": 2, "d_model": 256, "num_heads": 4,
               "vocab_size": 1024, "slstm_every": 2, "dtype": "float32"}),
    "moe": ("granite-moe-1b-a400m-smoke", moe,
            {"num_layers": 2, "d_model": 256, "num_heads": 8,
             "num_kv_heads": 8, "head_dim": 32, "d_ff": 512,
             "vocab_size": 1024, "num_experts": 4, "num_experts_per_tok": 2,
             "capacity_factor": 1.25, "rope_theta": 10000.0,
             "dtype": "float32"}),
}


def setup(name, seed=3, B=2, S=16):
    from repro.configs import get_config
    from repro.models import build_model
    arch, ref, s = SMALL[name]
    cfg = get_config(arch)
    assert {k: getattr(cfg, k) for k in s} == s
    params = ref.init(jax.random.key(seed), s)
    rng = np.random.default_rng(seed)
    t = rng.integers(0, s["vocab_size"], (B, S + 1)).astype(np.int32)
    batch = {"tokens": jnp.asarray(t[:, :-1]), "labels": jnp.asarray(t[:, 1:])}
    return build_model(cfg).loss_fn, ref, s, params, batch


def grad_gap(ga, gb):
    return max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
               for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)))


@pytest.mark.parametrize("name", sorted(SMALL))
def test_program_matches_reference(name):
    prog_loss, ref, s, params, batch = setup(name)
    lp, gp = jax.value_and_grad(prog_loss)(params, batch)
    lr, gr = jax.value_and_grad(ref.loss)(params, batch, s)
    assert abs(float(lp) - float(lr)) / float(lr) < LOSS_RTOL
    assert grad_gap(gp, gr) < GRAD_RTOL


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_precision_fails_the_tolerance(name):
    _, ref, s, params, batch = setup(name)
    mode = common.CONTROL_MODE[s["dtype"]]
    _, gr = jax.value_and_grad(ref.loss)(params, batch, s)
    _, gc = jax.value_and_grad(ref.loss)(params, batch, s, mode)
    assert grad_gap(gc, gr) > GRAD_RTOL


def test_int8_control_of_bf16_differs_more_than_bf16():
    _, ref, s, params, batch = setup("xlstm")
    _, gr = jax.value_and_grad(ref.loss)(params, batch, s)
    _, gb = jax.value_and_grad(ref.loss)(params, batch, s, "bf16")
    _, g8 = jax.value_and_grad(ref.loss)(params, batch, s, "int8")
    assert grad_gap(g8, gr) > grad_gap(gb, gr)


def stacked(seed, m=4, n=200_000):
    k1, k2 = jax.random.split(jax.random.key(seed))
    x = {"w": jnp.broadcast_to(jax.random.normal(k1, (n,)), (m, n)) +
         0.01 * jax.random.normal(k2, (m, n))}
    g = {"w": jax.random.normal(jax.random.fold_in(k2, 1), (m, n))}
    return x, g


def test_dsgd_reference_matches_program():
    from repro.core import make_topology
    from repro.core.pdsgd import dsgd_update
    x, g = stacked(0)
    W = np.asarray(make_topology("ring", 4).weights)
    assert np.allclose(W, update.metropolis(update.ring_support(4)))
    got = dsgd_update(x, g, W=jnp.asarray(W, jnp.float32),
                      lam=jnp.float32(0.01))
    want = update.dsgd(x, g, W, 0.01, jnp.float32)
    np.testing.assert_allclose(got["w"], want["w"], rtol=1e-5, atol=1e-6)


def test_reference_draws_the_programs_b():
    """Fed the program's step key, the reference realizes the program's
    B^k bit for bit."""
    from repro.core.privacy import agent_key, sample_B
    support = update.ring_support(4)
    for step in (0, 3, 2**20):
        key = jax.random.fold_in(jax.random.key(11), step)
        got = sample_B(agent_key(jax.random.fold_in(key, 2), step, 0),
                       jnp.asarray(support, jnp.float32))
        np.testing.assert_array_equal(
            got, update.sample_b(key, step, support))


def test_consensus_matches_program_in_stored_dtype():
    """The reference's consensus error of bfloat16 parameters is the
    program's, which rounds the agent mean to the stored dtype, to the
    bfloat16 the program reports it in."""
    from repro.core.pdsgd import consensus_error
    x, _ = stacked(2)
    x = jax.tree.map(lambda t: t.astype(jnp.bfloat16), x)
    got = float(consensus_error(x))
    want = float(update.consensus(x, jnp.bfloat16))
    assert got == pytest.approx(want, rel=2 ** -7)


def test_pdsgd_reference_matches_program_in_law():
    """Lambda is random: with g = 0 the two updates are W x exactly;
    with the program's B^k, each agent's change of a 200k-element leaf
    agrees in norm to its Lambda sampling noise (~0.2%, limit 1%)."""
    from repro.core.pdsgd import pdsgd_update
    x, g = stacked(1)
    support = update.ring_support(4)
    W = update.metropolis(support)
    kw = dict(W=jnp.asarray(W, jnp.float32),
              support=jnp.asarray(support, jnp.float32),
              lam_bar=jnp.float32(0.05), use_pallas=False)
    key = jax.random.key(5)
    zero = jax.tree.map(jnp.zeros_like, g)
    got = pdsgd_update(x, zero, key=key, step=jnp.int32(0), **kw)
    B = update.sample_b(key, 0, support)
    want = update.pdsgd(x, zero, W, B, 0.05, jax.random.key(6),
                        jnp.float32)
    np.testing.assert_allclose(got["w"], want["w"], rtol=1e-6, atol=1e-6)
    got = pdsgd_update(x, g, key=key, step=jnp.int32(0), **kw)
    want = update.pdsgd(x, g, W, B, 0.05, jax.random.key(6), jnp.float32)
    norms = lambda t: np.linalg.norm(np.asarray(t["w"] - W @ x["w"]),
                                     axis=1)
    np.testing.assert_allclose(norms(got), norms(want), rtol=1e-2)

"""Mixture-of-Experts FFN (olmoe 64e/top-8, granite-moe 32e/top-8).

Train/prefill path: sort-based capacity routing *per sequence group* —
tokens are replicated k ways, sorted by expert id, packed into a fixed
(E, C, d) buffer (capacity C = ceil(T*k/E * capacity_factor); overflow
drops, like GShard/Switch), run through batched expert matmuls, and
scattered back weighted by the router gates.  Unlike the classic one-hot
dispatch-einsum formulation this keeps HLO FLOPs at the *active-expert*
level (T*k*d*ff) instead of T*E*C*d dispatch FLOPs — important for the
MODEL_FLOPS/HLO_FLOPs roofline ratio (EXPERIMENTS.md §Roofline).

Decode path (single token): dense mixture over all experts with the top-k
mask.  With B>=64 decode tokens every expert is hit in expectation, so all
expert weights stream from HBM either way; decode is memory-bound and the
extra FLOPs are roofline-free (documented in DESIGN.md).

Experts are tensor-parallel: the expert mlp dim shards over "model"; the
expert dim stays local so routing never crosses chips (the all-to-all
expert-parallel variant is a §Perf experiment).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .. import trace as tr
from ..configs.base import ArchConfig
from .common import ArrayDef

Pytree = Any


def moe_defs(L: int, cfg: ArchConfig) -> dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": ArrayDef((L, d, E), ("layers", "embed", "experts"),
                           scale=0.02),
        "w_gate": ArrayDef((L, E, d, ff),
                           ("layers", "experts", "embed", "expert_mlp")),
        "w_up": ArrayDef((L, E, d, ff),
                         ("layers", "experts", "embed", "expert_mlp")),
        "w_down": ArrayDef((L, E, ff, d),
                           ("layers", "experts", "expert_mlp", "embed")),
    }


def _route_group(x: jax.Array, probs: jax.Array, w_gate: jax.Array,
                 w_up: jax.Array, w_down: jax.Array,
                 cfg: ArchConfig) -> jax.Array:
    """Route one group of T tokens.  x: (T, d); probs: (T, E)."""
    T, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = int(-(-T * k // E) * cfg.capacity_factor)
    C = max(1, min(C, T))

    with tr.region(tr.MOE_ROUTER):
        gates, eidx = jax.lax.top_k(probs, k)  # (T, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    with tr.region(tr.MOE_EXPERTS):
        flat_e = eidx.reshape(T * k)
        order = jnp.argsort(flat_e, stable=True)  # (T*k,)
        sorted_e = flat_e[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
        pos = jnp.arange(T * k) - seg_start[sorted_e]
        valid = pos < C
        buf_idx = jnp.where(valid, sorted_e * C + pos, E * C)

        x_sorted = x[order // k]  # (T*k, d)
        buf = jnp.zeros((E * C, d), x.dtype).at[buf_idx].set(
            x_sorted, mode="drop").reshape(E, C, d)

        g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        u = jnp.einsum("ecd,edf->ecf", buf, w_up)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y_buf = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E * C, d)

        y_sorted = jnp.where(valid[:, None],
                             y_buf[jnp.minimum(buf_idx, E * C - 1)], 0.0)
        inv = jnp.argsort(order, stable=True)
        y_flat = y_sorted[inv].reshape(T, k, d)
        return jnp.einsum("tkd,tk->td", y_flat, gates.astype(x.dtype))


def moe_ffn_train(pl: Pytree, x: jax.Array, cfg: ArchConfig,
                  mesh=None) -> jax.Array:
    """x: (B, S, d) -> (B, S, d).  Groups = sequences (tokens never leave
    their data shard)."""
    if cfg.moe_impl == "deferred" and mesh is not None:
        return _moe_ffn_deferred(pl, x, cfg, mesh)
    with tr.region(tr.MOE_ROUTER):
        logits = jnp.einsum("bsd,de->bse", x,
                            pl["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
    route = lambda x_row, p_row: _route_group(
        x_row, p_row, pl["w_gate"], pl["w_up"], pl["w_down"], cfg)
    return jax.vmap(route)(x, probs)


def _moe_ffn_deferred(pl: Pytree, x: jax.Array, cfg: ArchConfig,
                      mesh) -> jax.Array:
    """§Perf beyond-paper path: shard_map over the tensor-parallel axis with
    a *deferred* partial-sum combine.

    The baseline lets GSPMD place the all-reduce right after the w_down
    contraction, i.e. on the padded (E, C, d) dispatch buffer — k·cf× more
    bytes than the token activations — and (observed in the dry-run HLO) it
    additionally replicates the sort-based routing over the batch axis.
    Inside shard_map both problems vanish: batch stays sharded over
    ("pod","data"), every chip computes its f-shard partial of the expert
    matmuls, the (linear) unsort+gate combine is applied to the *partials*,
    and one psum over "model" of the (B_local, S, d) token activations
    finishes the job — an ~E·C/T reduction in all-reduce operand bytes.
    """
    from ..dist.sharding import SERVE_RULES, logical_spec
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    x_spec = logical_spec(mesh, x.shape, ("batch", "seq", "embed"),
                          SERVE_RULES)
    w3 = ("experts", "embed", "expert_mlp")
    specs = {
        "router": logical_spec(mesh, pl["router"].shape,
                               ("embed", "experts"), SERVE_RULES),
        "w_gate": logical_spec(mesh, pl["w_gate"].shape, w3, SERVE_RULES),
        "w_up": logical_spec(mesh, pl["w_up"].shape, w3, SERVE_RULES),
        "w_down": logical_spec(mesh, pl["w_down"].shape,
                               ("experts", "expert_mlp", "embed"),
                               SERVE_RULES),
    }

    def body(x_blk, router, w_gate, w_up, w_down):
        logits = jnp.einsum("bsd,de->bse", x_blk,
                            router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        # _route_group's unsort+gate combine is linear in the expert output,
        # so running it on the f-shard partials commutes with the psum.
        route = lambda x_row, p_row: _route_group(
            x_row, p_row, w_gate, w_up, w_down, cfg)
        y_partial = jax.vmap(route)(x_blk, probs)      # f-shard partial sums
        return jax.lax.psum(y_partial, "model")

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, specs["router"], specs["w_gate"],
                  specs["w_up"], specs["w_down"]),
        out_specs=x_spec,
        check_vma=False)
    return mapped(x, pl["router"], pl["w_gate"], pl["w_up"], pl["w_down"])


def moe_ffn_decode(pl: Pytree, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """x: (B, 1, d): dense top-k mixture over all experts."""
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = jnp.einsum("bsd,de->bse", x, pl["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)  # (B, 1, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # (B, 1, k, E) one-hot x gates -> dense per-expert mixture weights
    mask = (jax.nn.one_hot(eidx, E, dtype=gates.dtype)
            * gates[..., None]).sum(axis=-2)
    g = jnp.einsum("bsd,edf->bsef", x, pl["w_gate"])
    u = jnp.einsum("bsd,edf->bsef", x, pl["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("bsef,efd->bsed", h, pl["w_down"])
    return jnp.einsum("bsed,bse->bsd", y, mask.astype(x.dtype))


def aux_load_balance_loss(logits: jax.Array, eidx: jax.Array,
                          num_experts: int) -> jax.Array:
    """Switch-style load-balance auxiliary (available for training drivers)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))
    one_hot = jax.nn.one_hot(eidx, num_experts)
    ce = one_hot.mean(axis=tuple(range(one_hot.ndim - 1)))
    return num_experts * jnp.sum(me * ce.sum(0) if ce.ndim > 1 else me * ce)

"""Decoder-only transformer LM (dense + MoE + VLM-prefix).

Layer parameters are stacked on a leading "layers" dim and, by default, the
stack is traversed with an *unrolled* Python loop (static indexing), NOT
lax.scan: XLA's cost analysis counts a while-loop body exactly once, which
would make the dry-run roofline FLOPs off by a factor of num_layers.
Unrolling keeps ``compiled.cost_analysis()`` faithful; compile time stays
manageable because each layer body is wrapped in ``jax.checkpoint`` (full
remat).  ``cfg.scan_layers=True`` opts into a lax.scan traversal for the
sharded big-model path (compile time O(1) in depth); with a ``mesh`` the
residual stream carries MaxText-style logical constraints
(``common.constrain``) so GSPMD keeps activations on the fsdp axis.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .. import trace as tr
from ..configs.base import ArchConfig
from .common import (ArrayDef, apply_rope, attention, chunked_attention,
                     constrain, cross_entropy, decode_attention,
                     decode_cache_valid, decode_positions, gelu_mlp,
                     layer_norm, pad_vocab, ring_buffer_write, rms_norm,
                     swiglu)
from .moe import moe_defs, moe_ffn_train, moe_ffn_decode

Pytree = Any


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def _norm_defs(L: int, d: int, cfg: ArchConfig, name: str) -> dict:
    shape, log = (L, d), ("layers", "embed")
    out = {f"{name}_gamma": ArrayDef(shape, log, init="ones")}
    if cfg.norm == "layernorm":
        out[f"{name}_beta"] = ArrayDef(shape, log, init="zeros")
    return out


def attn_defs(L: int, cfg: ArchConfig) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": ArrayDef((L, d, H, hd), ("layers", "embed", "heads", "head_dim")),
        "wk": ArrayDef((L, d, KV, hd), ("layers", "embed", "kv_heads", "head_dim")),
        "wv": ArrayDef((L, d, KV, hd), ("layers", "embed", "kv_heads", "head_dim")),
        "wo": ArrayDef((L, H, hd, d), ("layers", "heads", "head_dim", "embed"),
                       scale=1.0 / (H * hd) ** 0.5),
    }


def mlp_defs(L: int, cfg: ArchConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    if cfg.mlp == "swiglu":
        return {
            "w_gate": ArrayDef((L, d, ff), ("layers", "embed", "mlp")),
            "w_up": ArrayDef((L, d, ff), ("layers", "embed", "mlp")),
            "w_down": ArrayDef((L, ff, d), ("layers", "mlp", "embed")),
        }
    return {
        "w_up": ArrayDef((L, d, ff), ("layers", "embed", "mlp")),
        "w_down": ArrayDef((L, ff, d), ("layers", "mlp", "embed")),
    }


def param_defs(cfg: ArchConfig) -> Pytree:
    L, d = cfg.num_layers, cfg.d_model
    V = pad_vocab(cfg.vocab_size)
    layers = {}
    layers.update(_norm_defs(L, d, cfg, "attn_norm"))
    layers.update(_norm_defs(L, d, cfg, "mlp_norm"))
    layers.update(attn_defs(L, cfg))
    if cfg.num_experts:
        layers["moe"] = moe_defs(L, cfg)
    else:
        layers.update(mlp_defs(L, cfg))
    defs = {
        "embed": ArrayDef((V, d), ("vocab", "embed"), scale=0.02),
        "final_norm_gamma": ArrayDef((d,), ("embed",), init="ones"),
        "layers": layers,
    }
    if cfg.norm == "layernorm":
        defs["final_norm_beta"] = ArrayDef((d,), ("embed",), init="zeros")
    if not cfg.tie_embeddings:
        defs["unembed"] = ArrayDef((d, V), ("embed", "vocab"), scale=0.02)
    return defs


# ---------------------------------------------------------------------------
# Forward pieces
# ---------------------------------------------------------------------------

def _norm(x, p, name, cfg):
    if cfg.norm == "layernorm":
        return layer_norm(x, p[f"{name}_gamma"], p[f"{name}_beta"])
    return rms_norm(x, p[f"{name}_gamma"])


def _ffn(pl: Pytree, x: jax.Array, cfg: ArchConfig, *, decode: bool,
         mesh=None) -> jax.Array:
    if cfg.num_experts:
        if decode:
            return moe_ffn_decode(pl["moe"], x, cfg)
        return moe_ffn_train(pl["moe"], x, cfg, mesh=mesh)
    if cfg.mlp == "swiglu":
        return swiglu(x, pl["w_gate"], pl["w_up"], pl["w_down"])
    return gelu_mlp(x, pl["w_up"], pl["w_down"])


def _attn(q, k, v, cfg: ArchConfig, window: int | None) -> jax.Array:
    if cfg.attn_impl == "chunked":
        return chunked_attention(q, k, v, causal=True, window=window,
                                 chunk=cfg.attn_chunk)
    return attention(q, k, v, causal=True, window=window)


def _qkv(pl: Pytree, x: jax.Array, positions: jax.Array, cfg: ArchConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, pl["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, pl["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, pl["wv"])
    q = apply_rope(q, positions, cfg.rotary_frac, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rotary_frac, cfg.rope_theta)
    return q, k, v


def _layer_train(pl: Pytree, x: jax.Array, cfg: ArchConfig,
                 window: int | None, mesh=None) -> jax.Array:
    from jax.ad_checkpoint import checkpoint_name
    B, S, d = x.shape
    with tr.region(tr.ATTN):
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h = _norm(x, pl, "attn_norm", cfg)
        q, k, v = _qkv(pl, h, positions, cfg)
        o = _attn(q, k, v, cfg, window)
        # the wo / w_down einsums contract the model-sharded dim — their
        # outputs are the post-all-reduce activations (named for the remat
        # policy)
        x = x + checkpoint_name(jnp.einsum("bshk,hkd->bsd", o, pl["wo"]),
                                "attn_out")
        x = constrain(x, mesh, ("batch", "seq", None))
    with tr.region(tr.MLP):
        h = _norm(x, pl, "mlp_norm", cfg)
        x = x + checkpoint_name(_ffn(pl, h, cfg, decode=False, mesh=mesh),
                                "ffn_out")
        return constrain(x, mesh, ("batch", "seq", None))


def _layer_prefill(pl: Pytree, x: jax.Array, cfg: ArchConfig,
                   window: int | None, cache_len: int, mesh=None):
    """Like train but also emits the (ring-layout) KV cache for the layer."""
    B, S, d = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = _norm(x, pl, "attn_norm", cfg)
    q, k, v = _qkv(pl, h, positions, cfg)
    o = _attn(q, k, v, cfg, window)
    x = x + jnp.einsum("bshk,hkd->bsd", o, pl["wo"])
    h = _norm(x, pl, "mlp_norm", cfg)
    x = x + _ffn(pl, h, cfg, decode=False, mesh=mesh)
    # Cache: last `cache_len` positions, laid out so that absolute position p
    # lives at slot p % cache_len (matches ring_buffer_write in decode).
    if cache_len == S:
        k_c, v_c = k, v
    else:
        k_tail, v_tail = k[:, -cache_len:], v[:, -cache_len:]
        shift = S % cache_len
        k_c = jnp.roll(k_tail, shift, axis=1)
        v_c = jnp.roll(v_tail, shift, axis=1)
    return x, (k_c, v_c)


def _layer_decode(pl: Pytree, x: jax.Array, k_cache, v_cache,
                  pos: jax.Array, cfg: ArchConfig, cache_valid: jax.Array):
    B = x.shape[0]
    positions = decode_positions(pos, B)
    h = _norm(x, pl, "attn_norm", cfg)
    q, k, v = _qkv(pl, h, positions, cfg)
    o = decode_attention(q, k, v, k_cache, v_cache, cache_valid)
    x = x + jnp.einsum("bshk,hkd->bsd", o, pl["wo"])
    h = _norm(x, pl, "mlp_norm", cfg)
    x = x + _ffn(pl, h, cfg, decode=True)
    new_k = ring_buffer_write(k_cache, k, pos)
    new_v = ring_buffer_write(v_cache, v, pos)
    return x, new_k, new_v


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_tokens(params: Pytree, batch: dict, cfg: ArchConfig) -> jax.Array:
    x = params["embed"][batch["tokens"]]
    prefix = batch.get("prefix_embeds")
    if prefix is not None:
        # VLM/audio-LM: the first P positions are modality embeddings coming
        # from the (stubbed) frontend; they replace the token embeddings.
        P = prefix.shape[1]
        x = jnp.concatenate([prefix.astype(x.dtype), x[:, P:]], axis=1)
    return x


def unembed(params: Pytree, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["unembed"])


def _final_norm(params, x, cfg):
    if cfg.norm == "layernorm":
        return layer_norm(x, params["final_norm_gamma"], params["final_norm_beta"])
    return rms_norm(x, params["final_norm_gamma"])


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def layer_slice(layers: Pytree, i: int) -> Pytree:
    """Static index into the stacked layer parameters."""
    return jax.tree.map(lambda a: a[i], layers)


def forward_train(params: Pytree, batch: dict, cfg: ArchConfig,
                  mesh=None) -> jax.Array:
    """Full-sequence logits for training (per-layer remat; unrolled layers by
    default, lax.scan over the stacked layer params when cfg.scan_layers)."""
    with tr.region(tr.EMBED):
        x = embed_tokens(params, batch, cfg)
        x = constrain(x, mesh, ("batch", "seq", None))
    if cfg.remat_policy == "save_collectives":
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ffn_out")
    else:
        policy = None
    body = jax.checkpoint(
        lambda pl, x: _layer_train(pl, x, cfg, cfg.attn_window, mesh=mesh),
        policy=policy)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(lambda x, pl: (body(pl, x), None),
                            x, params["layers"])
    else:
        for i in range(cfg.num_layers):
            x = body(layer_slice(params["layers"], i), x)
    with tr.region(tr.HEAD):
        x = _final_norm(params, x, cfg)
        return unembed(params, x, cfg)


def loss_fn(params: Pytree, batch: dict, cfg: ArchConfig,
            mesh=None) -> jax.Array:
    logits = forward_train(params, batch, cfg, mesh=mesh)
    with tr.region(tr.HEAD):
        weights = batch.get("loss_weights")
        if weights is None and cfg.num_prefix_embeds:
            # do not train on modality-prefix positions
            S = batch["labels"].shape[-1]
            weights = (jnp.arange(S) >= cfg.num_prefix_embeds
                       ).astype(jnp.float32)
            weights = jnp.broadcast_to(weights, batch["labels"].shape)
        if weights is None:
            return cross_entropy(logits, batch["labels"], cfg.vocab_size)
        lf = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, batch["labels"][..., None],
                                   axis=-1)[..., 0]
        return (jnp.sum((logz - gold) * weights)
                / jnp.maximum(weights.sum(), 1.0))


def cache_len_for(cfg: ArchConfig, seq_len: int) -> int:
    if cfg.attn_window is not None and cfg.long_context_mode == "window":
        return min(seq_len, cfg.attn_window)
    return seq_len


def cache_spec(cfg: ArchConfig, batch: int, seq_len: int) -> dict:
    """(shape, logical, dtype|None) per cache leaf, for launch.input_specs."""
    C = cache_len_for(cfg, seq_len)
    L = cfg.num_layers
    shape = (L, batch, C, cfg.num_kv_heads, cfg.head_dim)
    logical = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": (shape, logical, None), "v": (shape, logical, None)}


def forward_prefill(params: Pytree, batch: dict, cfg: ArchConfig,
                    mesh=None) -> dict:
    """Process a full prompt; return last-position logits + KV cache."""
    x = embed_tokens(params, batch, cfg)
    S = x.shape[1]
    C = cache_len_for(cfg, S)
    ks, vs = [], []
    body = jax.checkpoint(
        lambda pl, x: _layer_prefill(pl, x, cfg, cfg.attn_window, C,
                                     mesh=mesh))
    for i in range(cfg.num_layers):
        x, (k_c, v_c) = body(layer_slice(params["layers"], i), x)
        ks.append(k_c)
        vs.append(v_c)
    x = _final_norm(params, x, cfg)
    logits = unembed(params, x[:, -1:], cfg)
    cache = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
    return {"logits": logits[:, 0], "cache": cache,
            "pos": jnp.asarray(S, jnp.int32)}


def forward_decode(params: Pytree, token: jax.Array, cache: dict,
                   pos: jax.Array, cfg: ArchConfig, mesh=None) -> dict:
    """One decode step: token (B,) int32, cache from prefill, pos = absolute
    position of `token` — a scalar (whole batch in lockstep, the seed path)
    or (B,) int32 (continuous-batching serve: per-slot positions).  Returns
    next-token logits and the updated cache.  With a ``mesh`` the residual
    stream carries SERVE_RULES logical constraints (no-op when None)."""
    x = params["embed"][token][:, None, :]  # (B, 1, d)
    C = cache["k"].shape[2]
    # ring-buffer validity: slots < min(pos, C) hold real entries
    cache_valid = decode_cache_valid(pos, C)
    if mesh is not None:
        from ..dist.sharding import SERVE_RULES
        x = constrain(x, mesh, ("batch", "seq", None), rules=SERVE_RULES)
    new_ks, new_vs = [], []
    for i in range(cfg.num_layers):
        pl = layer_slice(params["layers"], i)
        x, new_k, new_v = _layer_decode(pl, x, cache["k"][i], cache["v"][i],
                                        pos, cfg, cache_valid)
        new_ks.append(new_k)
        new_vs.append(new_v)
    x = _final_norm(params, x, cfg)
    logits = unembed(params, x, cfg)
    new_cache = {"k": jnp.stack(new_ks), "v": jnp.stack(new_vs)}
    return {"logits": logits[:, 0], "cache": new_cache, "pos": pos + 1}

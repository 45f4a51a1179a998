"""Plain float32 reference of a decoder-only mixture-of-experts language
model (granite-3.0 MoE as the repository defines it), with its weight
layout, its weights drawn from a seed, and its model FLOPs per token.

Each layer: RMSNorm, grouped-query causal attention with rotary position
embedding, residual; RMSNorm, top-k routed SwiGLU experts, residual.
Departures from the published model that the repository makes, and that
this reference follows:

* Capacity routing per sequence: each expert takes at most
  ``C = int(ceil(S * k / E) * capacity_factor)`` (clipped to [1, S]) of
  the sequence's token-slots, in the order token-major then rank; the
  rest are dropped (contribute 0).  Gates are the top-k softmax
  probabilities renormalized to sum to 1.
* Rotary embedding rotates adjacent channel pairs (2i, 2i+1) with
  frequency theta^(-2i/head_dim).
* No embedding, attention, residual or logit multipliers; the output layer
  is the tied embedding over the unpadded vocabulary.

The experts are computed densely for every token and masked by the
routing weights: the plainest form, and the same sum.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, cross_entropy, init as _init, mm, pad_vocab, \
    rms_norm


def layout(s: dict) -> dict:
    L, d, V = s["num_layers"], s["d_model"], pad_vocab(s["vocab_size"])
    H, KV, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    E, ff = s["num_experts"], s["d_ff"]
    return {
        "embed": ((V, d), "normal", 0.02),
        "final_norm_gamma": ((d,), "ones", None),
        "layers/attn_norm_gamma": ((L, d), "ones", None),
        "layers/mlp_norm_gamma": ((L, d), "ones", None),
        "layers/wq": ((L, d, H, hd), "normal", d ** -0.5),
        "layers/wk": ((L, d, KV, hd), "normal", d ** -0.5),
        "layers/wv": ((L, d, KV, hd), "normal", d ** -0.5),
        "layers/wo": ((L, H, hd, d), "normal", (H * hd) ** -0.5),
        "layers/moe/router": ((L, d, E), "normal", 0.02),
        "layers/moe/w_gate": ((L, E, d, ff), "normal", d ** -0.5),
        "layers/moe/w_up": ((L, E, d, ff), "normal", d ** -0.5),
        "layers/moe/w_down": ((L, E, ff, d), "normal", ff ** -0.5),
    }


def init(key, s: dict, dtype=None):
    return _init(key, layout(s), dtype or jnp.dtype(s["dtype"]))


def _rope(x, theta):
    """x: (B, S, H, hd); rotate channel pairs (2i, 2i+1) by pos * f_i."""
    S, hd = x.shape[1], x.shape[-1]
    f = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * f              # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(p, h, s, mode):
    B, S, _ = h.shape
    H, KV, hd = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    q = _rope(mm("bsd,dhk->bshk", h, p["wq"], mode), s["rope_theta"])
    k = _rope(mm("bsd,dhk->bshk", h, p["wk"], mode), s["rope_theta"])
    v = mm("bsd,dhk->bshk", h, p["wv"], mode)
    rep = H // KV                       # query head j reads kv head j // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    logits = mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v, mode)
    return mm("bshk,hkd->bsd", o, p["wo"], mode)


def _experts(p, h, s, mode):
    B, S, _ = h.shape
    E, k = s["num_experts"], s["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("bsd,de->bse", h, p["router"], mode), -1)
    top_p, top_e = jax.lax.top_k(probs, k)                   # (B,S,k)
    gates = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    C = max(1, min(int(-(-S * k // E) * s["capacity_factor"]), S))
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)       # (B,S,k,E)
    # an assignment's place in its expert's queue: assignments of earlier
    # tokens, then of earlier ranks of the same token
    flat = onehot.reshape(B, S * k, E)
    place = (jnp.cumsum(flat, axis=1) - flat).reshape(B, S, k, E)
    kept = onehot * (place < C)
    weight = jnp.einsum("bske,bsk->bse", kept.astype(F32), gates)
    g = mm("bsd,edf->bsef", h, p["w_gate"], mode)
    u = mm("bsd,edf->bsef", h, p["w_up"], mode)
    y = mm("bsef,efd->bsed", jax.nn.silu(g) * u, p["w_down"], mode)
    return jnp.einsum("bsed,bse->bsd", y, weight)


def loss(params, batch, s: dict, mode: str = "f32", remat: bool = False):
    """Mean next-token cross-entropy of one agent's batch
    ({"tokens", "labels"}: (B, S) int32).  ``remat`` recomputes each
    layer's activations in the backward pass instead of keeping them."""
    def layer(x, p):
        x = x + _attention(p, rms_norm(x, p["attn_norm_gamma"]), s, mode)
        return x + _experts(p["moe"], rms_norm(x, p["mlp_norm_gamma"]), s,
                            mode)

    layer = jax.checkpoint(layer) if remat else layer
    x = params["embed"].astype(F32)[batch["tokens"]]
    for i in range(s["num_layers"]):
        x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
    x = rms_norm(x, params["final_norm_gamma"])
    logits = mm("bsd,vd->bsv", x, params["embed"][:s["vocab_size"]], mode)
    return cross_entropy(logits, batch["labels"])


def matmul_params(s: dict) -> int:
    """Weights a token multiplies by once: attention projections, router,
    its k experts' SwiGLU, and the tied output layer (true vocabulary)."""
    d, H, KV, hd = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                    s["head_dim"])
    attn = 2 * d * H * hd + 2 * d * KV * hd
    moe = d * s["num_experts"] + s["num_experts_per_tok"] * 3 * d * s["d_ff"]
    return s["num_layers"] * (attn + moe) + d * s["vocab_size"]


def flops_per_token(s: dict, seq_len: int) -> float:
    """Training FLOPs per token (3x forward): 2 per multiply-add of
    `matmul_params`, plus causal attention's scores and weighted sum,
    2 * 2 * H * hd per key over the (S + 1) / 2 keys a token sees on
    average.  Dropped expert slots and the dense masked form above are
    not model work and do not count."""
    attn_ctx = 2 * s["num_heads"] * s["head_dim"] * (seq_len + 1)
    return 3.0 * (2 * matmul_params(s) + s["num_layers"] * attn_ctx)

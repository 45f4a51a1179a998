"""The plain float32 mixture-of-experts reference of `refs/moe.py`, its
layers traversed with one ``lax.scan`` over the stacked layer weights.

The layer equations, the weight layout, the weights drawn from a seed and
the model FLOPs are `refs/moe.py`'s own, imported from it.  Only the walk
over the layers differs: `moe.loss` unrolls them in Python, so the
compiled reference grows with depth (for a TPU v5e 2x2 at the published
24 layers: a 231 MB persistent-cache entry, 231 s to compile on an x86
host); here one layer is compiled once and run L times (11 MB, 53 s).
``remat`` recomputes each layer's activations in the backward pass, as in
`moe.loss`.
"""
from __future__ import annotations

import jax

from .common import F32, cross_entropy, mm, rms_norm
from .moe import _attention, _experts, flops_per_token, init, layout

__all__ = ["init", "layout", "flops_per_token", "loss"]


def loss(params, batch, s: dict, mode: str = "f32", remat: bool = False):
    """Mean next-token cross-entropy of one agent's batch
    ({"tokens", "labels"}: (B, S) int32), as `moe.loss` computes it."""
    def layer(x, p):
        x = x + _attention(p, rms_norm(x, p["attn_norm_gamma"]), s, mode)
        return x + _experts(p["moe"], rms_norm(x, p["mlp_norm_gamma"]), s,
                            mode), None

    layer = jax.checkpoint(layer) if remat else layer
    x = params["embed"].astype(F32)[batch["tokens"]]
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms_norm(x, params["final_norm_gamma"])
    logits = mm("bsd,vd->bsv", x, params["embed"][:s["vocab_size"]], mode)
    return cross_entropy(logits, batch["labels"])

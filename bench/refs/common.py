"""Plain float32 building blocks of the reference models.

Nothing here imports the program under test.  Every contraction goes
through `mm`, which runs in float32 at ``Precision.HIGHEST`` (on a TPU a
float32 matmul is otherwise rounded to bfloat16), or, for the control, in
int8: both operands and, in the backward pass, the cotangent are quantized
per tensor (symmetric, scale max|x| / 127) before the contraction, or in
bfloat16 (operands rounded, float32 accumulation).  The control of a
configuration is the nearest precision below the one it states:
`CONTROL_MODE`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "int8")
CONTROL_MODE = {"bfloat16": "int8", "float32": "bf16"}


def _q8(x):
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=F32)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _q8_einsum(spec, a, b):
    return _einsum(spec, _q8(a), _q8(b))


def _q8_fwd(spec, a, b):
    return _q8_einsum(spec, a, b), (a, b)


def _q8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(partial(_einsum, spec), _q8(a), _q8(b))
    return vjp(_q8(g))


_q8_einsum.defvjp(_q8_fwd, _q8_bwd)


def mm(spec: str, a, b, mode: str = "f32"):
    """``einsum(spec, a, b)`` in float32, or a lower precision for the
    control."""
    a, b = a.astype(F32), b.astype(F32)
    if mode == "f32":
        return _einsum(spec, a, b)
    if mode == "int8":
        return _q8_einsum(spec, a, b)
    if mode == "bf16":
        bf = lambda t: t.astype(jnp.bfloat16).astype(F32)
        return _einsum(spec, bf(a), bf(b))
    raise ValueError(f"unknown mode {mode!r}; have {MODES}")


def rms_norm(x, gamma, eps: float = 1e-6):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


def cross_entropy(logits, labels):
    """Mean token cross-entropy over every position."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def pad_vocab(vocab: int, multiple: int = 512) -> int:
    """Rows of the embedding table as the program stores it (padded rows
    are never read by the loss)."""
    return -(-vocab // multiple) * multiple


def init(key, layout: dict, dtype):
    """Weights for ``layout`` ({path: (shape, kind, scale)}, kind one of
    normal / ones / zeros) drawn from ``key``: leaf i of the sorted paths
    takes ``fold_in(key, i)``.  Returns a nested dict in ``dtype``."""
    out = {}
    for i, path in enumerate(sorted(layout)):
        shape, kind, scale = layout[path]
        if kind == "ones":
            leaf = jnp.ones(shape, dtype)
        elif kind == "zeros":
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (scale * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, F32)).astype(dtype)
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return out

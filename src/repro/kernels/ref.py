"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
used by tests/test_kernels.py shape/dtype sweeps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Full-precision f32 contractions, as in the gossip kernels (a TPU's
# default would round the f32 operands to bf16).
_EXACT = jax.lax.Precision.HIGHEST


def bits_to_uniform(bits: jax.Array) -> jax.Array:
    """uint32 -> float32 in [0, 1): set mantissa, subtract 1."""
    f = (bits >> 9) | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(f, jnp.float32) - 1.0


def obfuscate_ref(x: jax.Array, g: jax.Array, bits: jax.Array,
                  lam_bar: jax.Array, w_self: jax.Array,
                  b_self: jax.Array) -> jax.Array:
    """Paper Eq. (3) self-term: v_jj = w_jj x_j - b_jj (lambda ∘ g_j) with
    lambda ~ U[0, 2 lam_bar] realized from `bits`."""
    lam = 2.0 * lam_bar * bits_to_uniform(bits)
    u = lam * g.astype(jnp.float32)
    return (w_self * x.astype(jnp.float32) - b_self * u).astype(x.dtype)


def gossip_ref(W: jax.Array, B: jax.Array, X: jax.Array,
               U: jax.Array) -> jax.Array:
    """x' = W @ X - B @ U over the leading agent dim; X/U: (m, n)."""
    out = (jnp.einsum("ij,jn->in", W.astype(jnp.float32),
                      X.astype(jnp.float32), precision=_EXACT)
           - jnp.einsum("ij,jn->in", B.astype(jnp.float32),
                        U.astype(jnp.float32), precision=_EXACT))
    return out.astype(X.dtype)


def ring_gossip_ref(w_tab: jax.Array, b_tab: jax.Array, perms: jax.Array,
                    X: jax.Array, U: jax.Array):
    """Staged-ring oracle for `ring_gossip_update`: per-direction v_d
    staging followed by 0/1-permutation shifts, accumulated self-first
    then directions in order.  Written so that ``jax.jit(ring_gossip_ref)``
    is bit-identical to the Pallas kernel (same op sequence, so XLA's FMA
    contraction applies identically); the eager call matches to ~1 ulp.
    Returns ``(out, v)`` with v the (ndirs, m, n) staged wire stream."""
    x = X.astype(jnp.float32)
    u = U.astype(jnp.float32)
    w = w_tab.astype(jnp.float32)
    b = b_tab.astype(jnp.float32)
    perms = perms.astype(jnp.float32)
    ndirs = perms.shape[0]
    out = w[:, 0:1] * x - b[:, 0:1] * u
    vs = [w[:, d + 1:d + 2] * x - b[:, d + 1:d + 2] * u
          for d in range(ndirs)]
    for d in range(ndirs):
        out = out + jnp.einsum("ij,jn->in", perms[d], vs[d],
                               precision=_EXACT)
    return out.astype(X.dtype), jnp.stack(vs)


def ring_obfuscate_gossip_ref(w_tab: jax.Array, b_tab: jax.Array,
                              perms: jax.Array, X: jax.Array, G: jax.Array,
                              bits: jax.Array, lam_bar):
    """Fused oracle for `ring_obfuscate_gossip`: Λ-draw from `bits` (same
    mantissa math as `obfuscate_ref`), then the staged ring.  Returns
    ``(out, v, u)``; jit it for bitwise kernel parity."""
    lam = (2.0 * jnp.asarray(lam_bar, jnp.float32)) * bits_to_uniform(bits)
    u = lam * G.astype(jnp.float32)
    out, v = ring_gossip_ref(w_tab, b_tab, perms, X, u)
    return out, v, u


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: int | None = None) -> jax.Array:
    """q/k/v: (B, S, H, hd) (same head count — GQA repeat happens outside)."""
    import math
    S = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ssd_intra_chunk_ref(x, dt, a_cum, Bm, Cm):
    """Intra-chunk SSD contribution for one chunk batch:
    x (G, Q, H, P); dt (G, Q, H); a_cum (G, Q, H) inclusive cumsum of dt*A;
    Bm/Cm (G, Q, N).  Returns y_intra (G, Q, H, P) and the chunk state
    contribution (G, H, P, N)."""
    Q = x.shape[1]
    scores = jnp.einsum("gin,gjn->gij", Cm, Bm)[..., None]  # (G,Q,Q,1)
    Lmat = jnp.exp(a_cum[:, :, None, :] - a_cum[:, None, :, :])
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    Lmat = jnp.where(causal, Lmat, 0.0)
    w = scores * Lmat * dt[:, None, :, :]
    y = jnp.einsum("gijh,gjhp->gihp", w.astype(x.dtype), x)
    decay_to_end = jnp.exp(a_cum[:, -1:, :] - a_cum)  # (G,Q,H)
    wx = x * (dt * decay_to_end)[..., None]
    state = jnp.einsum("gqn,gqhp->ghpn", Bm, wx)
    return y, state

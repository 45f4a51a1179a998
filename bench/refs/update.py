"""Plain float32 reference of the decentralized updates, on stacked
per-agent parameters (every leaf has a leading agent axis m).

* pdsgd (paper Eq. 4): x_i' = sum_j w_ij x_j - sum_j b_ij (Lambda_j o g_j),
  Lambda_j diagonal with entries ~ U[0, 2 lam], B column-stochastic on the
  graph's support with each column ~ Dirichlet(1, ..., 1) over the
  sender's closed neighbourhood.  B^k is drawn from the step's key as the
  algorithm states it (`sample_b`), so a walk fed the program's step keys
  realizes the program's B^k; Lambda is the reference's own draw.
* dsgd: x_i' = sum_j w_ij x_j - lam g_i.

W holds Metropolis weights of the graph.  The step size lam follows the
warm-up ramp lr * (k + 1) / (hold + 1) for k < hold, then the harmonic tail
lr * (hold + 1) / (k + 1).  After each update the parameters are rounded to
the dtype the configuration stores them in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, HI


def ring_support(m: int) -> np.ndarray:
    """Closed neighbourhoods of a ring of m agents (self included)."""
    a = np.eye(m, dtype=bool)
    for i in range(m):
        a[i, (i + 1) % m] = a[i, (i - 1) % m] = True
    return a


def metropolis(support: np.ndarray) -> np.ndarray:
    adj = support & ~np.eye(len(support), dtype=bool)
    deg = adj.sum(1)
    W = np.where(adj, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    return W + np.diag(1.0 - W.sum(1))


def step_size(k: int, lr: float, hold: int) -> float:
    return lr * (k + 1) / (hold + 1) if k < hold else lr * (hold + 1) / (k + 1)


def sample_b(key, step: int, support: np.ndarray):
    """B^k from the step's key: Exp(1) draws on the support, each column
    normalized.  The draw's key is the step key folded with 2 (the stream
    of B), then with the step, then with agent 0."""
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, 2), step), 0)
    s = jnp.asarray(support, F32)
    e = jax.random.exponential(k, s.shape, F32) * s
    return e / e.sum(0, keepdims=True)


@jax.jit
def _leaf_consensus(x, mean):
    return jnp.sum(jnp.square(x - mean))


def consensus(x, store_dtype):
    """sum_i ||x_i - x_bar||^2 over every leaf of the stacked tree x, the
    agent mean x_bar rounded to the stored dtype as the stored parameters
    hold it; squares and sums in float32."""
    return sum(_leaf_consensus(
        t.astype(F32), t.astype(F32).mean(0).astype(store_dtype).astype(F32))
        for t in jax.tree.leaves(x))


def _mix(M, x):
    return jnp.einsum("ij,j...->i...", jnp.asarray(M, F32), x.astype(F32),
                      precision=HI)


def pdsgd(x, g, W, B, lam: float, key, store_dtype):
    """One Eq. (4) update of the trees x (params) and g (gradients)."""
    leaves, tdef = jax.tree.flatten(x)
    keys = jax.random.split(key, len(leaves))
    out = []
    for xi, gi, k in zip(leaves, jax.tree.leaves(g), keys):
        lam_d = 2.0 * lam * jax.random.uniform(k, gi.shape, F32)
        out.append((_mix(W, xi) - _mix(B, lam_d * gi.astype(F32))
                    ).astype(store_dtype).astype(F32))
    return jax.tree.unflatten(tdef, out)


def dsgd(x, g, W, lam: float, store_dtype):
    return jax.tree.map(
        lambda xi, gi: (_mix(W, xi) - lam * gi.astype(F32)
                        ).astype(store_dtype).astype(F32), x, g)

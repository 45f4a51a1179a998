"""Plain float32 reference of the xLSTM language model (arXiv:2405.04517)
as the repository defines it, with its weight layout, its weights drawn
from a seed, and its model FLOPs per token.

Blocks alternate mLSTM and sLSTM: block i is sLSTM when
``i % slstm_every == 1``.  Departures from the paper that the repository
documents, and that this reference follows:

* mLSTM: the input gate is ``exp(min(i~, 8))`` and the output is divided by
  ``|n_t| + 1`` in place of the max-stabilizer state m_t; it is written
  here in its quadratic parallel form (decay matrix over all positions),
  not in the program's chunked form.
* The up-projection factor of the mLSTM block is 2 and there is no
  separate feed-forward block (d_ff = 0); the sLSTM block has no
  post-block MLP.
* The output layer is the embedding, tied, over the unpadded vocabulary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, cross_entropy, init as _init, mm, pad_vocab, \
    rms_norm

ICAP = 8.0


def _kinds(s):
    L, every = s["num_layers"], s["slstm_every"]
    return ["slstm" if i % every == 1 else "mlstm" for i in range(L)]


def layout(s: dict) -> dict:
    """{path: (shape, kind, scale)} of the parameter pytree."""
    d, H, V = s["d_model"], s["num_heads"], pad_vocab(s["vocab_size"])
    din = 2 * d
    kinds = _kinds(s)
    nm, ns = kinds.count("mlstm"), max(kinds.count("slstm"), 1)
    ph = d // H
    w = lambda *shape: (shape, "normal", shape[-2] ** -0.5)
    return {
        "embed": ((V, d), "normal", 0.02),
        "final_norm_gamma": ((d,), "ones", None),
        "mlstm/norm_gamma": ((nm, d), "ones", None),
        "mlstm/w_gate": w(nm, d, din),
        "mlstm/w_q": w(nm, d, din),
        "mlstm/w_k": w(nm, d, din),
        "mlstm/w_v": w(nm, d, din),
        "mlstm/w_i": w(nm, d, H),
        "mlstm/w_f": w(nm, d, H),
        "mlstm/b_f": ((nm, H), "ones", None),
        "mlstm/out_norm": ((nm, din), "ones", None),
        "mlstm/w_down": w(nm, din, d),
        "slstm/norm_gamma": ((ns, d), "ones", None),
        "slstm/w_gates": w(ns, d, 4 * d),
        "slstm/r_gates": ((ns, H, ph, 4 * ph), "normal", 0.05),
        "slstm/b_gates": ((ns, 4 * d), "zeros", None),
        "slstm/w_down": w(ns, d, d),
    }


def init(key, s: dict, dtype=None):
    return _init(key, layout(s), dtype or jnp.dtype(s["dtype"]))


def _mlstm(p, x, H, mode):
    B, S, d = x.shape
    h = rms_norm(x, p["norm_gamma"])
    proj = lambda name: mm("bsd,de->bse", h, p[name], mode)
    q, k, v, gate = proj("w_q"), proj("w_k"), proj("w_v"), proj("w_gate")
    i_gate = jnp.exp(jnp.minimum(proj("w_i"), ICAP))          # (B,S,H)
    log_f = jax.nn.log_sigmoid(proj("w_f") + p["b_f"].astype(F32))
    F = jnp.cumsum(log_f, axis=1)                             # (B,S,H)
    P = q.shape[-1] // H
    qh = q.reshape(B, S, H, P)
    kh = k.reshape(B, S, H, P) / P ** 0.5
    vh = v.reshape(B, S, H, P)
    scores = mm("bthp,bshp->bhts", qh, kh, mode)              # (B,H,S,S)
    Ft = jnp.moveaxis(F, 2, 1)                                # (B,H,S)
    causal = jnp.tril(jnp.ones((S, S), bool))
    # decay from s to t: prod_{r=s+1..t} f_r; masked inside the exp
    log_d = jnp.where(causal, Ft[..., :, None] - Ft[..., None, :], -jnp.inf)
    w = scores * jnp.exp(log_d) * jnp.moveaxis(i_gate, 2, 1)[..., None, :]
    y = mm("bhts,bshp->bthp", w, vh, mode)
    n = jnp.moveaxis(w.sum(-1), 1, 2)[..., None]              # (B,S,H,1)
    y = (y / (jnp.abs(n) + 1.0)).reshape(B, S, H * P)
    y = rms_norm(y, p["out_norm"]) * jax.nn.silu(gate)
    return x + mm("bse,ed->bsd", y, p["w_down"], mode)


def _slstm(p, x, H, mode):
    B, S, d = x.shape
    P = d // H
    h = rms_norm(x, p["norm_gamma"])
    wx = (mm("bsd,de->bse", h, p["w_gates"], mode)
          + p["b_gates"].astype(F32)).reshape(B, S, 4, H, P)
    r = p["r_gates"].astype(F32)

    def cell(carry, wx_t):
        hh, c, n, m = carry
        rec = mm("bhp,hpq->bhq", hh, r, mode).reshape(B, H, 4, P)
        pre = wx_t + jnp.moveaxis(rec, 2, 1)                  # (B,4,H,P)
        z, o = jnp.tanh(pre[:, 0]), jax.nn.sigmoid(pre[:, 3])
        i_pre, log_f = pre[:, 1], jax.nn.log_sigmoid(pre[:, 2])
        m_new = jnp.maximum(log_f + m, i_pre)
        i, f = jnp.exp(i_pre - m_new), jnp.exp(log_f + m - m_new)
        c = f * c + i * z
        n = f * n + i
        hh = o * c / jnp.maximum(jnp.abs(n), 1.0)
        return (hh, c, n, m_new), hh

    z = jnp.zeros((B, H, P), F32)
    _, hs = jax.lax.scan(cell, (z, z, z, z - 10.0), jnp.moveaxis(wx, 1, 0))
    y = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)
    return x + mm("bsd,de->bse", y, p["w_down"], mode)


def loss(params, batch, s: dict, mode: str = "f32", remat: bool = False):
    """Mean next-token cross-entropy of one agent's batch
    ({"tokens", "labels"}: (B, S) int32).  ``remat`` recomputes each
    block's activations in the backward pass instead of keeping them."""
    H, V = s["num_heads"], s["vocab_size"]
    block = {k: (lambda p, x, f=f: f(p, x, H, mode))
             for k, f in (("mlstm", _mlstm), ("slstm", _slstm))}
    if remat:
        block = {k: jax.checkpoint(f) for k, f in block.items()}
    x = params["embed"].astype(F32)[batch["tokens"]]
    seen = {"mlstm": 0, "slstm": 0}
    for kind in _kinds(s):
        p = jax.tree.map(lambda a, i=seen[kind]: a[i], params[kind])
        seen[kind] += 1
        x = block[kind](p, x)
    x = rms_norm(x, params["final_norm_gamma"])
    logits = mm("bsd,vd->bsv", x, params["embed"][:V], mode)
    return cross_entropy(logits, batch["labels"])


def matmul_params(s: dict) -> int:
    """Weights that take part in a matrix product once per token: every
    block projection and the tied output layer over the true vocabulary
    (the sLSTM recurrent matrix is counted per step in `flops_per_token`)."""
    d, H = s["d_model"], s["num_heads"]
    kinds = _kinds(s)
    din = 2 * d
    per_m = 4 * d * din + 2 * d * H + din * d
    per_s = d * 4 * d + d * d
    return (kinds.count("mlstm") * per_m + kinds.count("slstm") * per_s
            + d * s["vocab_size"])


def flops_per_token(s: dict, seq_len: int) -> float:
    """Training FLOPs per token (forward + backward = 3x forward), counting
    only what the model needs: 2 per multiply-add of `matmul_params`; the
    mLSTM cell in its recurrent form (state update k v^T and read-out
    q C, 2 P^2 each per head, plus the normalizer's 2 P each); the sLSTM
    recurrent gates, 2 * H * P * 4P per token and layer, as in the
    repository's ``models.xlstm.slstm_flops_correction``.  ``seq_len`` is
    unused: no term grows with the context."""
    d, H = s["d_model"], s["num_heads"]
    kinds = _kinds(s)
    pm, ps = 2 * d // H, d // H
    mlstm_cell = H * (4 * pm * pm + 4 * pm)
    slstm_rec = 2 * H * ps * 4 * ps
    fwd = (2 * matmul_params(s) + kinds.count("mlstm") * mlstm_cell
           + kinds.count("slstm") * slstm_rec)
    return 3.0 * fwd

"""The comparison that decides ``correct`` for a training cell.

Set-up drives the timed step program through its first chunk of K steps
(the window's own call and feed).  From that chunk the run keeps the
agent-mean loss and the consensus error that the step reports for each
step, and, per parameter leaf, the norm of the agent-mean change of the
parameters over the chunk.  After the window the plain reference
(``refs/<name>.py``, float32) walks the same K steps from the same weights,
tokens and step keys, and four numbers are compared with the cell's
limits:

* ``loss0_gap``: |L_prog - L_ref| / L_ref at the first step, which no
  update has touched yet: the model's forward pass alone;
* ``loss_gap``: the same, largest over the later steps of the chunk;
* ``consensus0_gap``: the same gap of the consensus error after the first
  step, sum_i ||x_i - x_bar||^2 over every leaf.  Every agent starts from
  the same weights, so it is the spread of the first update
  -sum_j b_ij (Lambda_j o g_j) across the agents as the stored parameters
  keep it: it grows with the first gradients' norms and falls with every
  agent whose gradient is missing;
* ``update_gap``: | ||d_prog|| - ||d_ref|| | / ||d_ref||, where d is the
  agent-mean parameter change over the chunk, over every leaf together.
  Leaves whose first reference gradient is under a thousandth of the
  median leaf's are left out: they move by round-off alone.

The reference draws B^k from the step key by the rule the algorithm states
(`refs.update.sample_b`), so it realizes the program's B^k.  Lambda^k is
drawn inside the program's kernel from the chip's own generator, which no
reference can replay, so the reference draws its own: norms, not the
elements themselves, are compared, and over many elements the norms agree.
The change is taken over the whole tree, not leaf by leaf: in bfloat16
storage most early updates are under half a unit in the last place and
round away, so one leaf's change is made by the few elements that cross,
and moves by tens of percent with the draws (PERF.md, "output check").

A number missing from a cell's limits is printed and not compared.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .refs import update as U
from .refs.common import F32

NUMBERS = ("loss0_gap", "loss_gap", "consensus0_gap", "update_gap")
EXCLUDE_BELOW = 1e-3


@jax.jit
def leaf_change_norms(params, x0):
    """Per leaf (tree order): || mean over agents of params - x0 ||."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(p.astype(F32).mean(0) - x.astype(F32))))
        for p, x in zip(jax.tree.leaves(params), jax.tree.leaves(x0))])


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
                      for l in jax.tree.leaves(tree)])


def without_agent_axis(shardings):
    """Per leaf, the sharding of one agent's slice of a leaf that
    ``shardings`` (`NamedSharding`s, agent axis first) places."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(
        lambda s: NamedSharding(s.mesh, PartitionSpec(*s.spec[1:])),
        shardings)


def reference_chunk(ref, sizes: dict, x0, chunk: dict, step_keys, *,
                    m: int, algorithm: str, lr: float, hold: int,
                    seed: int, mode: str = "f32", draws: str = "ref",
                    loss_fn=None, live: int | None = None,
                    update_scale=None, shardings=None) -> dict:
    """Walk K steps of the reference from the unstacked weights x0 on
    ``chunk`` ({"tokens", "labels"}: (K, m, B, S)) with the program's step
    keys ``step_keys`` ((K,) keys).  Returns the agent-mean loss and the
    consensus error per step, the per-leaf change norms and the per-leaf
    norms of the first agent-mean gradient.  ``draws`` names the seed
    stream of Lambda ("ref" or "ctl").  ``loss_fn``, ``live`` (only agents
    [0, live) have data: the others get no gradient and no loss) and
    ``update_scale`` (per-leaf factors on the update) let a test plant a
    fault in the reference.

    ``shardings`` (the program's `NamedSharding` of each parameter leaf,
    agent axis first) spreads the walk over the program's mesh, for a
    model whose reference does not fit one chip.  The mathematics is the
    same; three things differ from the walk on one device:

    * the stacked parameters and gradients live sharded as the program's,
      and every agent's float32 weights and gradient inside one jitted
      ``vmap`` over the agents, which GSPMD splits over the mesh (one
      agent after the other, on one device, without it);
    * each layer of ``ref.loss`` is rematerialized (``remat=True``): its
      activations are recomputed in the backward pass, not kept;
    * the update is one jitted call, so that Lambda is drawn in place on
      each chip (the generator's draws do not depend on the sharding).
    """
    from .seeds import jax_key
    store = jnp.dtype(sizes["dtype"])
    support = U.ring_support(m)
    W = U.metropolis(support)
    lam_key = jax_key(seed, draws + "_lambda")
    live = m if live is None else live
    if shardings is None:
        loss_fn = loss_fn or partial(ref.loss, s=sizes, mode=mode)
        vg = jax.jit(jax.value_and_grad(loss_fn))
        x = jax.tree.map(lambda a: jnp.broadcast_to(a, (m,) + a.shape), x0)
        pdsgd, dsgd = U.pdsgd, U.dsgd

        def grads(x, batch):
            ls, gs = [], []
            for a in range(m):
                # float32 weights, so that the gradient is float32 too
                xa = jax.tree.map(lambda t: t[a].astype(F32), x)
                if a < live:
                    l, g = vg(xa, {n: jnp.asarray(v[a])
                                   for n, v in batch.items()})
                    ls.append(float(l))
                else:
                    g = jax.tree.map(jnp.zeros_like, xa)
                gs.append(g)
            return ls, jax.tree.map(lambda *t: jnp.stack(t), *gs)
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        loss_fn = loss_fn or partial(ref.loss, s=sizes, mode=mode,
                                     remat=True)
        mesh = jax.tree.leaves(shardings)[0].mesh
        vg = jax.jit(lambda x, b: jax.vmap(jax.value_and_grad(loss_fn))(
            jax.tree.map(lambda t: t.astype(F32), x), b),
            out_shardings=(NamedSharding(mesh, PartitionSpec()), shardings))
        x = jax.jit(lambda t: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (m,) + a.shape), t),
            out_shardings=shardings)(x0)
        pdsgd = jax.jit(U.pdsgd, static_argnums=6, out_shardings=shardings)
        dsgd = jax.jit(U.dsgd, static_argnums=4, out_shardings=shardings)
        has_data = (jnp.arange(m) < live).astype(F32)

        def grads(x, batch):
            l, g = vg(x, {n: jnp.asarray(v) for n, v in batch.items()})
            if live < m:
                g = jax.tree.map(lambda t: t * has_data.reshape(
                    (m,) + (1,) * (t.ndim - 1)), g)
            return [float(v) for v in np.asarray(l)[:live]], g

    K = chunk["tokens"].shape[0]
    losses, consensus, g0 = [], [], None
    with jax.default_matmul_precision("highest"):
        for k in range(K):
            ls, g = grads(x, {n: v[k] for n, v in chunk.items()})
            if g0 is None:
                g0 = np.asarray(_leaf_norms(jax.tree.map(
                    lambda t: t.mean(0), g)))
            losses.append(float(np.mean(ls)))
            lam = U.step_size(k, lr, hold)
            if algorithm == "pdsgd":
                B = U.sample_b(step_keys[k], k, support)
                new = pdsgd(x, g, W, B, lam, jax.random.fold_in(lam_key, k),
                            store)
            elif algorithm == "dsgd":
                new = dsgd(x, g, W, lam, store)
            else:
                raise ValueError(f"no reference for {algorithm!r}")
            if update_scale is not None:
                new = jax.tree.map(lambda n, o, s: o + s * (n - o), new, x,
                                   update_scale)
            x = jax.tree.map(lambda t: t.astype(store), new)
            consensus.append(float(U.consensus(x, store)))
            del g, new
    return {"losses": losses, "consensus": consensus,
            "change": np.asarray(leaf_change_norms(x, x0)),
            "grad0": g0}


def _gap(p, r) -> float:
    return float(abs(p - r) / abs(r))


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of a program chunk against a reference chunk
    (both as `reference_chunk` returns them; the program's need no
    ``grad0``)."""
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    rel = np.abs(lp - lr_) / np.abs(lr_)
    keep = ref["grad0"] >= EXCLUDE_BELOW * np.median(ref["grad0"])
    dp = np.linalg.norm(np.asarray(prog["change"])[keep])
    dr = np.linalg.norm(np.asarray(ref["change"])[keep])
    out = {"loss0_gap": float(rel[0]),
           "loss_gap": float(np.max(rel[1:], initial=0.0)),
           "consensus0_gap": _gap(prog["consensus"][0],
                                  ref["consensus"][0]),
           "update_gap": _gap(dp, dr)}
    # a NaN anywhere is a failed comparison, never a pass
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in
            out.items()}


def judge(nums: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [[name, value, limit], ...]) of the numbers that have a
    limit."""
    rows = [[k, nums[k], limits[k]] for k in NUMBERS if k in limits]
    return all(v <= lim for _, v, lim in rows), rows

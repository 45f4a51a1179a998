"""Communication-optimal torus gossip for the paper's Eq. (3) exchange.

The dense baseline materializes W x^k - B^k u^k as two (m, m) einsums over
the agent axis, which GSPMD lowers to all-gathers: every agent's variable
visits every device.  On the ("pod","data") device torus the coupling
matrix of `launch.steps.make_torus_W` has only nearest-neighbor support, so
the same update needs just one `ppermute` ring shift per torus direction —
O(deg) point-to-point messages per agent instead of an m-way all-gather,
and each message carries only the already-mixed quantity

    v_ij = w_edge * x_j - b_ij * u_j,

never x_j or u_j alone.  That is exactly the paper's privacy architecture
(Sec. III: only the sum-masked v_ij crosses the wire), so the fast path and
the privacy mechanism are the same code.

On a single host (no mesh, or the agent count does not match the mesh
torus) `torus_gossip_pdsgd` falls back to a dense-W einsum with the same
coupling matrices, which `tests/test_fast_path.py` pins against
`core.pdsgd.gossip_mix` and `topology.metropolis_weights`.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = [
    "sample_b_draws",
    "torus_weights",
    "torus_gossip_pdsgd",
    "dense_coupling",
    "directional_keep",
    "directional_weights",
    "mask_b_draws",
    "perm_stack",
    "rows_from_dense",
]

Pytree = Any


def _directions(n_data: int, n_pod: int) -> list[tuple[str, int, int]]:
    """Distinct neighbor directions (mesh_axis, ring_size, shift) of the
    ("pod","data") torus.  Size-2 rings have a single distinct neighbor
    (+1 == -1 mod 2), matching `topology.torus2d`'s boolean adjacency."""
    dirs: list[tuple[str, int, int]] = []
    if n_data > 1:
        dirs.append(("data", n_data, 1))
    if n_data > 2:
        dirs.append(("data", n_data, -1))
    if n_pod > 1:
        dirs.append(("pod", n_pod, 1))
    if n_pod > 2:
        dirs.append(("pod", n_pod, -1))
    return dirs


def torus_weights(n_data: int, n_pod: int) -> dict:
    """Metropolis weights of the regular torus: every agent has
    deg = len(directions) neighbors, so w_edge = 1/(1+deg) and
    w_self = 1 - deg*w_edge — identical to
    `topology.metropolis_weights(torus2d(n_pod, n_data))`."""
    deg = len(_directions(n_data, n_pod))
    w_edge = 1.0 / (1.0 + deg)
    return {"w_self": 1.0 - deg * w_edge, "w_edge": w_edge}


def sample_b_draws(key: jax.Array, m: int, n_data: int, n_pod: int) -> jax.Array:
    """Per-agent random column weights of B^k on the torus support.

    Returns (m, 1 + ndirs) with rows summing to one: column j of B^k is
    chosen by agent j (Sec. III), row j here holds [b_jj, b_{i_1 j}, ...]
    for the neighbors i_d = shift_d(j).  Dirichlet(1,..,1) via normalized
    Exp(1) draws, mirroring `privacy.sample_B` on the dense support.
    """
    ndirs = len(_directions(n_data, n_pod))
    e = jax.random.exponential(key, (m, 1 + ndirs), dtype=jnp.float32)
    return e / e.sum(axis=1, keepdims=True)


def _perm_matrices(n_data: int, n_pod: int) -> list[np.ndarray]:
    """Static permutation matrix per direction: P[i, j] = 1 iff i receives
    from j, with agent id = pod * n_data + data (GSPMD device order)."""
    m = n_data * n_pod
    mats = []
    for axis, _size, shift in _directions(n_data, n_pod):
        Pm = np.zeros((m, m), dtype=np.float32)
        for j in range(m):
            pj, dj = divmod(j, n_data)
            if axis == "data":
                i = pj * n_data + (dj + shift) % n_data
            else:
                i = ((pj + shift) % n_pod) * n_data + dj
            Pm[i, j] = 1.0
        mats.append(Pm)
    return mats


def perm_stack(n_data: int, n_pod: int) -> jax.Array:
    """The `_perm_matrices` list stacked to one (ndirs, m, m) float32
    array — the direction-shift operand `kernels.ring_gossip_update` /
    `ring_obfuscate_gossip` consume (each 0/1 matmul reproduces the
    corresponding `ppermute` bit-exactly for finite v)."""
    return jnp.asarray(np.stack(_perm_matrices(n_data, n_pod)))


def dense_coupling(b: jax.Array, n_data: int, n_pod: int,
                   W: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Materialize the (W, B^k) pair the ring path applies implicitly.

    W is the doubly-stochastic torus Metropolis matrix (or, for a
    time-varying topology, the step's realized W_k passed in — its support
    must lie inside the torus adjacency); B^k is the random
    column-stochastic matrix realized from the `sample_b_draws` rows
    (pre-masked by `mask_b_draws` in the time-varying case, so its support
    follows the realization automatically).
    """
    m = n_data * n_pod
    mats = _perm_matrices(n_data, n_pod)
    eye = np.eye(m, dtype=np.float32)
    if W is None:
        wts = torus_weights(n_data, n_pod)
        W = jnp.asarray(wts["w_self"] * eye
                        + wts["w_edge"] * sum(mats, np.zeros_like(eye)))
    B = jnp.asarray(eye) * b[None, :, 0]
    for di, Pm in enumerate(mats):
        B = B + jnp.asarray(Pm) * b[None, :, 1 + di]
    return W, B


def directional_keep(support: jax.Array, n_data: int, n_pod: int
                     ) -> jax.Array:
    """Per-direction edge survival: keep[j, d] = support[shift_d(j), j].

    ``support`` is the realized (m, m) 0/1 support from
    `core.mixing.MixingProcess.realize` (diagonal entries are never
    gathered — a direction's target differs from its source).  Because the
    dense mask is symmetric, keep[j, d] == keep[i, d_opp] for the edge's
    other endpoint: sender and receiver agree on every link's fate, which
    is what keeps the ring exchange consistent with the dense realization.
    """
    mats = _perm_matrices(n_data, n_pod)
    return jnp.stack(
        [jnp.einsum("ij,ij->j", jnp.asarray(Pm), support) for Pm in mats],
        axis=1)


def directional_weights(W: jax.Array, n_data: int, n_pod: int) -> dict:
    """Split a realized dense W_k (torus support) into the per-agent tables
    the ring path consumes: ``w_self`` (m,) = diag(W_k) and ``w_dir``
    (m, ndirs) with w_dir[j, d] = W_k[shift_d(j), j] — the weight agent j's
    outgoing v_ij carries toward its direction-d neighbor."""
    mats = _perm_matrices(n_data, n_pod)
    w_dir = jnp.stack(
        [jnp.einsum("ij,ij->j", jnp.asarray(Pm), W) for Pm in mats], axis=1)
    return {"w_self": jnp.diagonal(W), "w_dir": w_dir}


def rows_from_dense(B: jax.Array, n_data: int, n_pod: int) -> jax.Array:
    """Inverse of `dense_coupling`'s B reconstruction: extract the per-agent
    (m, 1 + ndirs) rows [b_jj, b_{i_1 j}, ...] from a dense column-
    stochastic B on the torus support.  ``dense_coupling(rows_from_dense
    (B))[1] == B`` exactly (each entry is copied, never recombined), which
    is what lets the privacy audit drive the ring path with the SAME B^k
    realization as the dense/eager/fused paths and pin all four
    observation streams bit-for-bit."""
    mats = _perm_matrices(n_data, n_pod)
    cols = [jnp.diagonal(B)] + [
        jnp.einsum("ij,ij->j", jnp.asarray(Pm), B) for Pm in mats]
    return jnp.stack(cols, axis=1)


def mask_b_draws(b: jax.Array, keep_dir: jax.Array) -> jax.Array:
    """Re-normalize `sample_b_draws` rows onto the realized neighbor set:
    dropped directions get weight zero and the row (self + survivors) is
    re-scaled to sum to one — the Dirichlet aggregation property keeps the
    law the same as drawing on the realized support directly, and column
    stochasticity of the implied B^k is preserved."""
    scale = jnp.concatenate(
        [jnp.ones((b.shape[0], 1), b.dtype), keep_dir.astype(b.dtype)],
        axis=1)
    e = b * scale
    return e / e.sum(axis=1, keepdims=True)


def torus_gossip_pdsgd(mesh, params: Pytree, u: Pytree, b: jax.Array, *,
                       agent_axes: tuple[str, ...] = ("pod", "data"),
                       n_data: int | None = None,
                       n_pod: int | None = None,
                       leaf_specs: Pytree | None = None,
                       W: jax.Array | None = None,
                       capture: bool = False,
                       finite_guard: bool = False,
                       schedule: str = "pipelined",
                       fused: bool = False) -> Pytree:
    """x' = W x - B^k u via neighbor-only exchanges on the mesh torus.

    params/u: pytrees with leading agent axis (m, ...); b: (m, 1+ndirs)
    rows from `sample_b_draws`.  When ``mesh`` hosts exactly one agent per
    ("pod","data") coordinate the update runs under `shard_map` with one
    `lax.ppermute` ring shift per direction; otherwise (single host, or a
    mesh that does not carry the agent axis) it falls back to the dense
    einsum with the equivalent `dense_coupling` matrices.  ``n_data`` /
    ``n_pod`` override the torus shape when no mesh carries it (the
    single-host fallback on a non-trivial torus).

    ``leaf_specs`` (a pytree of PartitionSpec congruent with params) keeps
    the NON-agent dims of each leaf sharded inside the shard_map — without
    it every leaf is resharded to P(agent_axes) and model-parallel params
    would be all-gathered to full per-agent replicas.  The gossip body is
    elementwise + ppermute over the agent axes only, so any trailing-dim
    sharding passes straight through.  Each spec's first entry must cover
    exactly ``agent_axes``.

    ``W`` selects the time-varying path: the step's realized dense W_k
    (support inside the torus adjacency, e.g. from
    `core.mixing.MixingProcess.realize`) replaces the static Metropolis
    scalars — split into per-agent `directional_weights` tables and
    sharded like ``b``, so each sender still only touches its own row.
    Pass ``b`` already masked by `mask_b_draws` so the descent term rides
    the same realized links; a dropped edge then contributes an exactly
    zero v_ij (the permute still runs — the collective keeps a static
    shape under jit — but nothing of x_j or u_j crosses the dead link).

    ``capture=True`` wire-taps the exchange for the privacy audit:
    returns ``(out, V)`` with V (m, m, D) holding exactly the per-edge
    messages v_ij this path transmits — on the shard_map path the
    sender-side v of each ppermute (tapped BEFORE the collective, i.e.
    what crosses the link), scattered into the dense layout of
    `privacy.observe.wire_messages`; on the dense fallback the same
    tensor from the equivalent `dense_coupling` matrices.  D is the
    flattened trailing size per agent, so capture requires the leaves
    un-sharded in their non-agent dims (``leaf_specs=None``).

    ``finite_guard=True`` zeroes every RECEIVED per-link contribution
    that is not finite before accumulating — the wire-level defense a
    real multi-controller deployment needs against a crashed or
    byzantine peer emitting NaN/Inf (`launch.steps.make_train_step`
    enables it whenever faults are injected).  ``where(isfinite(v), v,
    0)`` is bitwise identity on finite inputs, so the guard never
    perturbs a healthy exchange; on the dense fallback the same per-link
    semantics route through `faults.inject.guarded_gossip_mix` (clip
    disabled), whose explicit link-sum ordering is allclose- but not
    bit-comparable to the einsum.

    ``schedule`` picks the shard_map loop order.  ``"staged"`` is the
    historic compute-all-then-shift body: direction d's v is computed,
    tapped, permuted and accumulated before direction d+1 starts.
    ``"pipelined"`` (default) issues direction d's `ppermute` first and
    computes direction d+1's v WHILE that collective's DMA is in flight,
    accumulating d when the shift lands — a software pipeline over the
    link.  The two schedules build the same dataflow graph (v_{d+1}
    never depends on the shifted d), the per-direction accumulation
    order is unchanged, and the tap still reads the exact staged buffer
    before its collective, so results and captured wire streams are
    bit-identical; tests pin this.

    ``fused=True`` routes the SINGLE-HOST fallback through the Pallas
    ring kernel (`kernels.ring_gossip_update`): per-direction tables +
    0/1 `perm_stack` shifts with double-buffered VMEM v staging, instead
    of the dense `gossip_mix` einsums.  Bit-identical to the jitted
    staged-ring oracle (`kernels.ref.ring_gossip_ref`) and allclose to
    the dense fallback (different contraction order); the capture tap
    returns the kernel's own staged buffers scattered to the dense
    layout.  Ignored on the shard_map path (the ppermute pipeline IS the
    fused schedule there); refused with ``finite_guard`` — fault
    scenarios keep the dense guarded path.
    """
    if schedule not in ("staged", "pipelined"):
        raise ValueError(f"unknown schedule {schedule!r}; "
                         "expected 'staged' or 'pipelined'")
    if fused and finite_guard:
        raise ValueError("fused=True does not compose with finite_guard; "
                         "fault scenarios use the dense guarded path")
    if capture and leaf_specs is not None:
        raise ValueError(
            "capture=True flattens each agent's leaves to (m, D) and so "
            "requires replicated non-agent dims (leaf_specs=None); audit "
            "workloads replicate per agent")
    m = jax.tree.leaves(params)[0].shape[0]
    axes = tuple(a for a in agent_axes
                 if mesh is not None and a in getattr(mesh, "shape", {}))
    if n_pod is None:
        n_pod = mesh.shape.get("pod", 1) if (axes and "pod" in axes) else 1
    if n_data is None:
        n_data = (mesh.shape.get("data", 1) if (axes and "data" in axes)
                  else m // n_pod)
    if n_pod * n_data != m:
        raise ValueError(
            f"torus {n_pod}x{n_data} does not hold m={m} agents")

    dirs = _directions(n_data, n_pod)
    if b.shape[-1] != 1 + len(dirs):
        raise ValueError(
            f"b has {b.shape[-1]} coefficients but the {n_pod}x{n_data} "
            f"torus has {len(dirs)} neighbor directions")

    mesh_matches = (axes
                    and (mesh.shape.get("pod", 1) if "pod" in axes else 1) == n_pod
                    and (mesh.shape.get("data", 1) if "data" in axes else 1) == n_data)
    if not mesh_matches and fused:
        # Single-host fused fallback: the ring kernel applies the same
        # per-direction tables the shard_map path shards, with v staged
        # in VMEM instead of crossing a mesh link.
        from ..kernels import ring_gossip_update
        from ..kernels.ops import _flatten_concat, _pad_cols, _unflatten
        if leaf_specs is not None:
            raise ValueError("fused=True flattens each agent's leaves to "
                             "(m, D) and needs replicated non-agent dims "
                             "(leaf_specs=None)")
        if W is None:
            wts = torus_weights(n_data, n_pod)
            w_tab = jnp.broadcast_to(
                jnp.asarray([wts["w_self"]]
                            + [wts["w_edge"]] * len(dirs),
                            jnp.float32)[None],
                (m, 1 + len(dirs)))
        else:
            tabs = directional_weights(W, n_data, n_pod)
            w_tab = jnp.concatenate(
                [tabs["w_self"][:, None], tabs["w_dir"]], axis=1)
        perms = perm_stack(n_data, n_pod)
        x_flat, sizes, leaves = _flatten_concat(params)
        u_flat, _, _ = _flatten_concat(u)
        x_flat, pad = _pad_cols(x_flat, 512)
        u_flat, _ = _pad_cols(u_flat, 512)
        res = ring_gossip_update(w_tab, b, perms, x_flat, u_flat,
                                 capture=capture)
        out_flat = res[0] if capture else res
        if pad:
            out_flat = out_flat[:, :-pad]
        out = _unflatten(out_flat, sizes, leaves, params)
        if not capture:
            return out
        v_dir = res[1]  # (ndirs, m, D_padded), sender-major staged stream
        ncols = sum(sizes)
        V = sum(perms[di][:, :, None] * v_dir[di][None, :, :ncols]
                for di in range(len(dirs)))
        return out, V

    if not mesh_matches:
        # Dense single-host fallback: same math, explicit matrices.
        from ..core.pdsgd import gossip_mix
        Wd, B = dense_coupling(b, n_data, n_pod, W=W)
        if finite_guard:
            from ..faults.inject import guarded_gossip_mix
            out = guarded_gossip_mix(
                Wd, B, params, u, jnp.zeros((m,), jnp.float32),
                mode="nan", scale=1.0, clip=float("inf"))
        else:
            mixed = gossip_mix(Wd, params)
            desc = gossip_mix(B, u)
            out = jax.tree.map(lambda a, c: a - c, mixed, desc)
        if not capture:
            return out
        from ..privacy import observe as O
        V = O.wire_messages(Wd, B, O.flatten_agents(params),
                            O.flatten_agents(u))
        return out, V

    agent_spec = axes[0] if len(axes) == 1 else axes
    if leaf_specs is None:
        leaf_spec = jax.tree.map(lambda _: P(agent_spec), params)
    else:
        leaf_spec = leaf_specs

    if W is None:
        # Static torus: scalar Metropolis weights, shared by every agent —
        # the original (bit-anchored) path.
        wts = torus_weights(n_data, n_pod)
        w_tab = jnp.broadcast_to(
            jnp.asarray([wts["w_self"]]
                        + [wts["w_edge"]] * len(dirs), jnp.float32)[None],
            (m, 1 + len(dirs)))
    else:
        # Time-varying: per-agent weight tables from the realized W_k,
        # sharded like b so a sender only reads its own row.
        tabs = directional_weights(W, n_data, n_pod)
        w_tab = jnp.concatenate([tabs["w_self"][:, None], tabs["w_dir"]],
                                axis=1)

    if capture:
        # THE flatten convention (leaf order, ravel, f32) — shared with
        # every other path's capture so the streams stay comparable;
        # applied per shard, where each leaf is (1, ...).
        from ..privacy.observe import flatten_agents as _flat_local

    def body(b_loc, w_loc, x_loc, u_loc):
        # One agent per shard: every leaf is (1, ...), b_loc/w_loc are
        # (1, 1+ndirs) — column 0 is the self term, 1+d the directions.
        # The per-link message math itself lives in `transport.link_message`
        # (the seam every transport shares); this body keeps its historic
        # direction-order accumulation, which existing tests bit-anchor.
        from .transport import link_message

        def coeff(tab, col, leaf):
            return tab[:, col].reshape((-1,) + (1,) * (leaf.ndim - 1))

        out = jax.tree.map(
            lambda x, uu: link_message(coeff(w_loc, 0, x),
                                       coeff(b_loc, 0, x), x, uu),
            x_loc, u_loc)

        def mk_v(di):
            # The sender computes the mixed v_ij; only v crosses the link.
            return jax.tree.map(
                lambda x, uu: link_message(coeff(w_loc, 1 + di, x),
                                           coeff(b_loc, 1 + di, x), x, uu),
                x_loc, u_loc)

        taps = []
        if schedule == "pipelined":
            v = mk_v(0)
        for di, (axis, size, shift) in enumerate(dirs):
            perm = [(d, (d + shift) % size) for d in range(size)]
            if schedule == "staged":
                v = mk_v(di)
            if capture:
                # Tap at the SENDER, before the collective: this is the
                # exact buffer the ppermute puts on the wire — identical
                # under both schedules.
                taps.append(_flat_local(v))
            shifted = jax.tree.map(
                lambda leaf: jax.lax.ppermute(leaf, axis, perm), v)
            if schedule == "pipelined" and di + 1 < len(dirs):
                # Software pipeline: stage direction d+1's v while
                # direction d's ppermute DMA is in flight.  v_{d+1} does
                # not depend on the shifted d, so the values (and the
                # accumulation order below) are unchanged — only the
                # program order exposes the overlap to the scheduler.
                v = mk_v(di + 1)
            if finite_guard:
                # Receive-side guard: a non-finite incoming contribution
                # is dropped as if the link were down (exact zero).
                shifted = jax.tree.map(
                    lambda leaf: jnp.where(jnp.isfinite(leaf), leaf,
                                           jnp.zeros_like(leaf)), shifted)
            out = jax.tree.map(lambda a, c: a + c, out, shifted)
        if capture:
            return out, jnp.stack(taps, axis=1)  # (1, ndirs, D)
        return out

    out_specs = (leaf_spec, P(agent_spec)) if capture else leaf_spec
    result = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(agent_spec), P(agent_spec), leaf_spec, leaf_spec),
        out_specs=out_specs,
        check_vma=False,
    )(b, w_tab, params, u)
    if not capture:
        return result
    out, v_dir = result  # v_dir: (m, ndirs, D) — sender-major taps
    # Scatter to the dense v_ij layout: V[i, j] = v_dir[j, d] where
    # i = shift_d(j) (P_d[i, j] == 1), matching `observe.wire_messages`.
    mats = _perm_matrices(n_data, n_pod)
    V = sum(jnp.asarray(Pm)[:, :, None] * v_dir[None, :, di, :]
            for di, Pm in enumerate(mats))
    return out, V

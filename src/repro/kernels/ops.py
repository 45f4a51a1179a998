"""Public jit'd wrappers for the Pallas kernels.

``interpret=None`` everywhere defers to `runtime.default_interpret`: on this
CPU-only container kernels execute through the Pallas interpreter for
correctness validation; on TPU hardware the same calls run compiled.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .. import trace as tr
from .flash_attention import flash_attention
from .gossip import (gossip_update, guarded_gossip_update,
                     masked_gossip_update, masked_gossip_update_krng,
                     ring_gossip_update, ring_obfuscate_gossip,
                     ring_obfuscate_gossip_krng)
from .obfuscate import obfuscate_update, obfuscate_update_krng, obfuscated
from .runtime import (default_interpret, default_kernel_rng,
                      default_use_pallas, resolve_kernel_rng)
from .ssm_scan import ssd_intra_chunk

Pytree = Any

__all__ = ["flash_attention", "gossip_update", "masked_gossip_update",
           "masked_gossip_update_krng", "guarded_gossip_update",
           "obfuscate_update",
           "obfuscate_update_krng", "ssd_intra_chunk", "obfuscate_tree",
           "gossip_tree", "fused_pdsgd_tree", "sharded_pdsgd_tree",
           "ring_gossip_update", "ring_obfuscate_gossip",
           "ring_obfuscate_gossip_krng", "ring_pdsgd_tree",
           "default_interpret", "default_use_pallas", "default_kernel_rng"]


def _flatten_concat(tree: Pytree):
    leaves = jax.tree.leaves(tree)
    flat = [l.reshape(l.shape[0], -1) for l in leaves]
    sizes = [f.shape[1] for f in flat]
    return jnp.concatenate(flat, axis=1), sizes, leaves


def _unflatten(buf: jax.Array, sizes, leaves, treedef_tree):
    parts = []
    off = 0
    for s, l in zip(sizes, leaves):
        parts.append(buf[:, off:off + s].reshape(l.shape).astype(l.dtype))
        off += s
    return jax.tree.unflatten(jax.tree.structure(treedef_tree), parts)


def _pad_cols(x: jax.Array, multiple: int):
    pad = (-x.shape[1]) % multiple
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x, pad


def obfuscate_tree(key: jax.Array, x_tree: Pytree, g_tree: Pytree,
                   lam_bar, w_self, b_self,
                   interpret: bool | None = None) -> Pytree:
    """Apply the fused obfuscation kernel leaf-wise across a parameter
    pytree with leading agent dim (m, ...)."""
    x_flat, sizes, leaves = _flatten_concat(x_tree)
    g_flat, _, _ = _flatten_concat(g_tree)
    x_flat, pad = _pad_cols(x_flat, 256)
    g_flat, _ = _pad_cols(g_flat, 256)
    bits = jax.random.bits(key, x_flat.shape, dtype=jnp.uint32)
    out = obfuscate_update(x_flat, g_flat, bits, lam_bar, w_self, b_self,
                           interpret=interpret)
    if pad:
        out = out[:, :-pad]
    return _unflatten(out, sizes, leaves, x_tree)


def gossip_tree(W: jax.Array, B: jax.Array, x_tree: Pytree, u_tree: Pytree,
                interpret: bool | None = None) -> Pytree:
    """x' = W X - B U across a parameter pytree with leading agent dim."""
    x_flat, sizes, leaves = _flatten_concat(x_tree)
    u_flat, _, _ = _flatten_concat(u_tree)
    x_flat, pad = _pad_cols(x_flat, 512)
    u_flat, _ = _pad_cols(u_flat, 512)
    out = gossip_update(W, B, x_flat, u_flat, interpret=interpret)
    if pad:
        out = out[:, :-pad]
    return _unflatten(out, sizes, leaves, x_tree)


def fused_pdsgd_tree(W: jax.Array, B: jax.Array, x_tree: Pytree,
                     g_tree: Pytree, bits_tree: Pytree, lam_bar,
                     mask: jax.Array | None = None,
                     interpret: bool | None = None,
                     observe: bool = False,
                     corrupt: jax.Array | None = None,
                     corrupt_mode: str = "nan",
                     corrupt_scale: float = 1e4,
                     guard_clip: float = 1e3,
                     kernel_rng: bool | None = None,
                     seed: jax.Array | None = None,
                     mask_seed: jax.Array | None = None,
                     mask_keep_prob=None,
                     mask_adj: jax.Array | None = None) -> Pytree:
    """Full Eq. (4) update through both fused kernels in one flattened pass:

        u = Lambda(bits) ∘ g          (obfuscate kernel, w_self=0, b_self=-1)
        x' = W X - B U                (gossip kernel)

    One flatten/concat + one pad for the whole pytree; the intermediate u
    never round-trips through per-leaf shapes.  ``bits_tree`` carries the
    uint32 draws per leaf (same shapes as g_tree) so the realized Lambda is
    bit-identical to the eager `privacy.obfuscated_gradient` path — the
    randomness contract tests rely on this.

    ``mask`` (from `core.mixing.MixingProcess.realize`) selects the
    time-varying path: the gossip stage becomes `masked_gossip_update`,
    which re-derives the doubly-stochastic W_k from the realized edge mask
    in VMEM — ``W`` is ignored and W_k never staged from HBM.

    ``observe=True`` returns ``(out_tree, {"x": (m, D), "u": (m, D)})`` —
    the kernel's OWN flattened state and obfuscated-gradient buffers
    (padding stripped), which the privacy-audit wire-tap layer turns into
    the v_ij observation tensor.  With the in-kernel draw it also holds
    ``"bits"``, the (m, D) uint32 draw the kernel made, in the same
    column order.  Emitting the kernel's u (not an eager
    re-derivation) is what makes the capture an audit of what this path
    actually realized; the buffers already exist, so capture adds no
    kernel work.

    ``kernel_rng`` (None defers to `runtime.default_kernel_rng`) switches
    the obfuscate stage to the in-VMEM TPU PRNG: ``bits_tree`` is ignored
    (pass None) and ``seed`` — (2,) uint32/int32 words derived from the
    step's Lambda key — drives `obfuscate_update_krng` instead.  The
    realized Lambda then comes from the TPU PRNG stream, not the
    jax.random counter stream (zero HBM traffic for the randomness); the
    krng kernel exports the bits it drew, and the parity test replays
    them through this HBM-input path to pin the two kernels bit-for-bit.

    ``mask_seed`` extends the same contract to the EDGE-MASK draw: with
    the knob on and a (2,) mask seed given, the masked gossip stage
    becomes `gossip.masked_gossip_update_krng` — the Bernoulli mask is
    drawn in-VMEM from ``mask_keep_prob`` (required) over the
    off-diagonal base adjacency ``mask_adj`` (None = complete graph) and
    the ``mask`` argument is ignored; the realized mask never stages
    from HBM.  Off-TPU (knob off) callers keep passing the
    `MixingProcess.realize` mask unchanged.  Not composable with
    ``corrupt`` (the guard path consumes the realized mask on the host
    side) or ``observe``.

    ``corrupt`` (an (m,) 0/1 vector from `faults.FaultProcess.realize`)
    selects the fault-tolerant path: the corrupt agents' TRANSMIT
    buffers are poisoned (`faults.inject.poison_transmit`) and the
    gossip stage becomes `gossip.guarded_gossip_update`, which applies
    the per-link finite-guard + ``guard_clip`` before accumulating —
    the same program whether this step's corrupt draw fired or not, so
    corruption stays a traced scenario.  Requires ``mask`` (faults
    always compose through `faults.realize_coupling`, which provides
    one); ``observe`` is refused upstream when corruption is on.
    """
    # A caller that staged HBM bits but no seed keeps the bits path even
    # where the knob defaults on (TPU) — only an explicit seed opts in.
    use_krng = resolve_kernel_rng(kernel_rng) and seed is not None
    if kernel_rng and seed is None:
        raise ValueError("kernel_rng=True needs a (2,) seed "
                         "(derive from the step's Lambda key)")
    use_mask_krng = resolve_kernel_rng(kernel_rng) and mask_seed is not None
    if mask_seed is not None and mask_keep_prob is None:
        raise ValueError("mask_seed needs mask_keep_prob (the per-edge "
                         "keep probability, 1 - dropout rate)")
    if use_mask_krng and corrupt is not None:
        raise ValueError("in-kernel mask draw does not compose with "
                         "corrupt injection; pass the realized mask")
    if corrupt is not None and mask is None:
        raise ValueError(
            "corrupt injection needs the realized edge mask; compose "
            "faults through faults.realize_coupling")
    with tr.region(tr.LAYOUT):
        x_flat, sizes, leaves = _flatten_concat(x_tree)
        g_flat, _, _ = _flatten_concat(g_tree)
        x_flat, pad = _pad_cols(x_flat, 512)
        g_flat, _ = _pad_cols(g_flat, 512)
        bits_flat = None
        if not use_krng:
            bits_flat, _, _ = _flatten_concat(bits_tree)
            bits_flat, _ = _pad_cols(bits_flat, 512)
    # w_self=0, b_self=-1 turns the self-term kernel into u = lambda ∘ g.
    with tr.region(tr.OBFUSCATE):
        if use_krng:
            u_flat, bits_flat = obfuscate_update_krng(
                x_flat, g_flat, seed, lam_bar, jnp.float32(0.0),
                jnp.float32(-1.0), interpret=interpret)
        else:
            u_flat = obfuscate_update(x_flat, g_flat, bits_flat, lam_bar,
                                      jnp.float32(0.0), jnp.float32(-1.0),
                                      interpret=interpret)
    with tr.region(tr.GOSSIP):
        if corrupt is not None:
            from ..faults.inject import poison_transmit
            xt = poison_transmit(x_flat, corrupt, corrupt_mode,
                                 corrupt_scale)
            ut = poison_transmit(u_flat, corrupt, corrupt_mode,
                                 corrupt_scale)
            out = guarded_gossip_update(mask, B, x_flat, u_flat, xt, ut,
                                        guard_clip, interpret=interpret)
        elif use_mask_krng:
            m = x_flat.shape[0]
            adj = mask_adj
            if adj is None:
                adj = 1.0 - jnp.eye(m, dtype=jnp.float32)
            out, _ = masked_gossip_update_krng(mask_seed, mask_keep_prob,
                                               adj, B, x_flat, u_flat,
                                               interpret=interpret)
        elif mask is not None:
            out = masked_gossip_update(mask, B, x_flat, u_flat,
                                       interpret=interpret)
        else:
            out = gossip_update(W, B, x_flat, u_flat, interpret=interpret)
    with tr.region(tr.LAYOUT):
        if pad:
            out = out[:, :-pad]
        out_tree = _unflatten(out, sizes, leaves, x_tree)
        if not observe:
            return out_tree
        ncols = sum(sizes)
        flats = {"x": x_flat[:, :ncols].astype(jnp.float32),
                 "u": u_flat[:, :ncols].astype(jnp.float32)}
        if use_krng:
            flats["bits"] = bits_flat[:, :ncols]
    return out_tree, flats


def ring_pdsgd_tree(w_tab: jax.Array, b_tab: jax.Array, perms: jax.Array,
                    x_tree: Pytree, g_tree: Pytree, bits_tree: Pytree,
                    lam_bar,
                    interpret: bool | None = None,
                    observe: bool = False,
                    kernel_rng: bool | None = None,
                    seed: jax.Array | None = None) -> Pytree:
    """Eq. (4) through the ring-scheduled fused kernel, one flattened pass.

    The ring counterpart of `fused_pdsgd_tree`: instead of dense (m, m)
    W/B matmuls, the update is driven by per-direction tables
    (``w_tab``/``b_tab``: (m, 1+ndirs); ``perms``: (ndirs, m, m) 0/1
    shifts from `dist.collectives.perm_stack`) and
    `gossip.ring_obfuscate_gossip` computes Λ-draw + obfuscate + staged
    ring in a single pallas_call — each direction's v tiles are built in
    the double-buffered VMEM slot while the previous direction's shift is
    consumed.  Link dropout arrives as zeroed table entries (see
    `dist.collectives.mask_b_draws` / `directional_keep`), keeping this
    the same traced program every step.

    ``observe=True`` returns ``(out_tree, {"x", "u", "v"})`` where ``v``
    is the kernel's (ndirs, m, D) staged wire stream — the exact buffers
    a torus link would carry, so the privacy-audit tap records what this
    path actually transmitted, not an eager re-derivation.

    ``kernel_rng``/``seed`` mirror the `fused_pdsgd_tree` contract: an
    explicit (2,) seed with the knob on switches the Λ-draw to the
    in-VMEM TPU PRNG (`ring_obfuscate_gossip_krng`) and ``bits_tree`` is
    ignored.
    """
    use_krng = resolve_kernel_rng(kernel_rng) and seed is not None
    if kernel_rng and seed is None:
        raise ValueError("kernel_rng=True needs a (2,) seed "
                         "(derive from the step's Lambda key)")
    with tr.region(tr.LAYOUT):
        x_flat, sizes, leaves = _flatten_concat(x_tree)
        g_flat, _, _ = _flatten_concat(g_tree)
        x_flat, pad = _pad_cols(x_flat, 512)
        g_flat, _ = _pad_cols(g_flat, 512)
        if not use_krng:
            bits_flat, _, _ = _flatten_concat(bits_tree)
            bits_flat, _ = _pad_cols(bits_flat, 512)
    # one kernel draws Lambda, obfuscates and runs the ring: it is the gossip
    with tr.region(tr.GOSSIP):
        if use_krng:
            res = ring_obfuscate_gossip_krng(w_tab, b_tab, perms, x_flat,
                                             g_flat, seed, lam_bar,
                                             capture=observe,
                                             interpret=interpret)
            out = res[0]
            flats = {"v": res[2], "u": res[3]} if observe else None
        else:
            res = ring_obfuscate_gossip(w_tab, b_tab, perms, x_flat, g_flat,
                                        bits_flat, lam_bar, capture=observe,
                                        interpret=interpret)
            if observe:
                out, v, u = res
                flats = {"v": v, "u": u}
            else:
                out = res
                flats = None
    with tr.region(tr.LAYOUT):
        if pad:
            out = out[:, :-pad]
        out_tree = _unflatten(out, sizes, leaves, x_tree)
        if not observe:
            return out_tree
        ncols = sum(sizes)
        flats = {"x": x_flat[:, :ncols].astype(jnp.float32),
                 "u": flats["u"][:, :ncols].astype(jnp.float32),
                 "v": flats["v"][:, :, :ncols].astype(jnp.float32)}
    return out_tree, flats


def _leaf_pdsgd(W, B, x, g, bits, lam_bar, mask, interpret,
                corrupt, corrupt_mode, corrupt_scale, guard_clip):
    """One leaf of `sharded_pdsgd_tree`: same two kernels as the fused
    concat path, on this leaf's own (m, n) flattening.  The obfuscate
    kernel is elementwise and the gossip kernels treat every column
    independently (the (m, m) @ (m, bn) matmul contracts only the agent
    dim), so per-leaf results are bit-identical to the same columns of
    the concatenated buffer — the property tests pin this."""
    m = x.shape[0]
    with tr.region(tr.LAYOUT):
        xf, pad = _pad_cols(x.reshape(m, -1), 512)
        gf, _ = _pad_cols(g.reshape(m, -1), 512)
        bf, _ = _pad_cols(bits.reshape(m, -1), 512)
    with tr.region(tr.OBFUSCATE):
        u = obfuscate_update(xf, gf, bf, lam_bar, jnp.float32(0.0),
                             jnp.float32(-1.0), interpret=interpret)
    with tr.region(tr.GOSSIP):
        if corrupt is not None:
            from ..faults.inject import poison_transmit
            xt = poison_transmit(xf, corrupt, corrupt_mode, corrupt_scale)
            ut = poison_transmit(u, corrupt, corrupt_mode, corrupt_scale)
            out = guarded_gossip_update(mask, B, xf, u, xt, ut, guard_clip,
                                        interpret=interpret)
        elif mask is not None:
            out = masked_gossip_update(mask, B, xf, u, interpret=interpret)
        else:
            out = gossip_update(W, B, xf, u, interpret=interpret)
    with tr.region(tr.LAYOUT):
        if pad:
            out = out[:, :-pad]
        return out.reshape(x.shape).astype(x.dtype)


def sharded_pdsgd_tree(W: jax.Array, B: jax.Array, x_tree: Pytree,
                       g_tree: Pytree, bits_tree: Pytree, lam_bar,
                       mask: jax.Array | None = None,
                       interpret: bool | None = None,
                       corrupt: jax.Array | None = None,
                       corrupt_mode: str = "nan",
                       corrupt_scale: float = 1e4,
                       guard_clip: float = 1e3,
                       mesh=None, leaf_specs: Pytree | None = None) -> Pytree:
    """Leaf-wise Eq. (4) update — the sharded-pytree counterpart of
    `fused_pdsgd_tree`.

    The concat path flattens the whole pytree into one (m, ΣD) buffer,
    which forces every leaf onto one replicated layout and defeats GSPMD
    (an FSDP/tensor-sharded leaf would be all-gathered just to be
    re-split).  Here each leaf keeps its own shape end to end:

    * ``mesh=None`` — per-leaf Pallas kernel pairs, bit-identical to the
      concat path (obfuscate is elementwise; the gossip matmuls contract
      only the agent dim, so columns never interact).  This is the
      reference the property tests compare against.
    * ``mesh`` + ``leaf_specs`` (a PartitionSpec per leaf, agent dim
      included) — the obfuscate kernel's math as XLA's elementwise
      fusion on each leaf in its own sharding (zero communication), and
      the gossip contraction as an einsum, so GSPMD emits the agent-axis
      collective itself and every non-agent dim keeps its sharding.
      ``corrupt`` is refused here — the fault paths are dense-only
      today.
    """
    if mesh is None:
        return jax.tree.map(
            lambda x, g, b: _leaf_pdsgd(W, B, x, g, b, lam_bar, mask,
                                        interpret, corrupt, corrupt_mode,
                                        corrupt_scale, guard_clip),
            x_tree, g_tree, bits_tree)
    if corrupt is not None:
        raise NotImplementedError(
            "fault injection on the sharded leafwise path is not "
            "supported; use the dense paths for fault scenarios")
    if leaf_specs is None:
        raise ValueError("mesh given but leaf_specs is None; resolve "
                         "specs via dist.sharding.logical_spec")

    # the kernel's Lambda o g as an XLA fusion on each leaf as it is
    # sharded: the kernel needs each shard flattened to one (1, N) row, a
    # relayout whose executable code grew with the leaf (29 MB a layer at
    # granite widths, and the step compiled 18x slower)
    with tr.region(tr.OBFUSCATE):
        u_tree = jax.tree.map(
            lambda x, g, b: obfuscated(g, b, lam_bar).astype(x.dtype),
            x_tree, g_tree, bits_tree)
    if mask is not None:
        from ..core.mixing import metropolis_from_mask
        with tr.region(tr.STEP_MIX):
            W = metropolis_from_mask(mask)
    mix = lambda M, t: jax.tree.map(
        lambda l: jnp.einsum("ij,j...->i...", M, l.astype(jnp.float32),
                             preferred_element_type=jnp.float32
                             ).astype(l.dtype), t)
    with tr.region(tr.GOSSIP):
        mixed = mix(W, x_tree)
        desc = mix(B, u_tree)
        return jax.tree.map(jnp.subtract, mixed, desc)

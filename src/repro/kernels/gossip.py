"""Blocked gossip kernels: x' = W @ X - B @ U over the agent dimension.

X/U are (m, n) agent-stacked flattened parameters; W/B are tiny (m, m)
mixing matrices that live in VMEM for the whole kernel.  The grid tiles n;
each program contracts the m agents of one (m, bn) column block and
subtracts, fusing what two einsums would write twice.  W and B are never
rounded below float32: a bf16 W's rows no longer sum to 1.

The contraction runs on the VPU: for each source agent j, x[j] and u[j]
are widened to float32 once, and ``w[:, j] * x[j]`` and ``b[:, j] * u[j]``
are accumulated in float32, 2 m^2 multiply-adds a column -- a float32
dot's arithmetic term for term.  The calls' names end in ``_vpu``
(``_gossip_update_vpu``), which the compiled step's custom calls keep.
It replaced two ``HIGHEST`` (m, m) @ (m, bn) MXU dots, six bf16 passes
each over K = m of the array's 128 rows: on a v5e the granite update's
gossip took 29.0 ms a step with them against 8.5 ms for the same bytes
with no contraction at all.  The dots cost about the same a column at any
m up to 128, the VPU's 2 m^2 multiply-adds grow with m: compiled for a
v5e, the VPU form's bundles put it ahead up to about 8 agents and behind
the dots from about 16 on, more agents than any launcher here runs on
these kernels.

`masked_gossip_update` is the time-varying variant for
`core.mixing.MixingProcess`: it takes the step's realized EDGE MASK
instead of a pre-built W_k and performs mask -> Metropolis re-weight ->
W_k @ X - B @ U inside one pallas_call.  W_k never exists in HBM — the
(m, m) mask is the only per-step mixing input staged, and the re-weighting
(two tiny reductions + a divide on an (m, m) VMEM tile) is free next to
the contraction.  The formula mirrors `core.mixing.metropolis_from_mask`
exactly; keep the two in sync.

`ring_gossip_update` / `ring_obfuscate_gossip` are the RING-SCHEDULED
variants of the same Eq. (4) update, organized the way the torus gossip
actually moves data (`dist.collectives.torus_gossip_pdsgd`): per-agent
direction tables (w_tab/b_tab columns: self, then one per torus
direction) instead of dense (m, m) matrices, a per-direction staged
v_d = w_d ∘ X − b_d ∘ U buffer, and a 0/1 permutation matmul standing in
for the `ppermute` shift.  The staging buffer is double-buffered in VMEM
scratch: direction d+1's v tiles are computed while direction d's shift
is consumed — on TPU hardware the pattern the Mosaic scheduler overlaps
with the inter-core DMA, in interpret mode simply one fused program
instead of the seam's many eager dispatches.  The fused variant also
folds the Λ-draw (`obfuscate._obfuscate_math`'s b·u math) into the same
pass, so x, g and the raw bits are read once and only x' (plus optional
capture buffers) is written.  Dropout/fault realizations arrive through
the tables themselves (`collectives.directional_weights` /
`mask_b_draws` zero the dropped directions), so a dropped link
contributes an exactly-zero v_d — no separate mask input.  The pure-jnp
oracles (`ref.ring_gossip_ref` / `ref.ring_obfuscate_gossip_ref`) are
the bit-parity ground truth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blocks import COMPILER_PARAMS, LANE_BLOCK, column_block, vmem_rows
from .obfuscate import tile_seed
from .runtime import resolve_interpret

# The ring kernels' column block (they stage (2, m, bn) in VMEM scratch of
# their own); the dense kernels take theirs from `gossip_block`.
DEFAULT_BLOCK_N = 512
# f32 matmuls at full precision: on the TPU's MXU the default rounds f32
# operands to bf16, which would round every staged v_j the ring kernels'
# 0/1 shift matmul moves.  The six passes are not free (the dense kernels
# left them for the VPU); the shift matmuls still pay them.
_EXACT = jax.lax.Precision.HIGHEST
# The dense kernels contract a block one slice of columns at a time, so that
# their float32 values stay in vector registers rather than in (m, bn) VMEM
# temporaries: each (m, C) float32 value spans at most this many elements,
# sixteen (8, 128) vregs.  In the kernel compiled for a v5e, a wider slice
# pays each loop iteration's fixed latency over more columns until its
# values spill: at 2 to 8 agents 2048 columns take 23-26% fewer bundles a
# column than 512, at 16 agents 1024 and at 32 agents 512 take the fewest.
_VPU_ELEMENTS = 16 * 8 * 128


def _vpu_columns(m: int) -> int:
    """The columns of one slice of the dense kernels' contraction: a
    multiple of `LANE_BLOCK`, whose (m, C) float32 values, rows padded
    to the sublane tile, hold at most `_VPU_ELEMENTS`."""
    c = _VPU_ELEMENTS // vmem_rows(m, 4) // LANE_BLOCK * LANE_BLOCK
    return max(LANE_BLOCK, c)


def gossip_block(m: int, width: int, dtype, guarded: bool = False,
                 interpret: bool = False) -> int:
    """Column block of the dense gossip kernels over (m, width) buffers
    of ``dtype``.  The plain and masked kernels stream X, U and x' and
    hold no float32 (m, bn) temporaries (they work through
    `_vpu_columns` at a time).  The guarded kernel also streams the
    transmit buffers and holds (m, m, bn) per-link tensors, m (m, bn)
    temporaries each (the two products, their difference, the guard's
    select), so its block narrows as m grows."""
    s = jnp.dtype(dtype).itemsize
    if guarded:
        return column_block(m, width, (s,) * 5, 4 * m + 5, interpret)
    return column_block(m, width, (s,) * 3, 0, interpret)


def _block_n(block_n, X, interpret, guarded=False):
    """The column block of a dense gossip call.  The last block may
    overhang n: no column of x' reads another, and Pallas drops the
    writes past the edge."""
    m, n = X.shape
    return min(block_n or gossip_block(m, n, X.dtype, guarded, interpret), n)


def _mix(w, b, x_ref, u_ref, o_ref):
    """o = w @ x - b @ u over the agent rows of one (m, bn) column block,
    in float32 with the (m, m) float32 ``w`` and ``b`` as they are."""
    m, bn = x_ref.shape
    width = _vpu_columns(m)

    def columns(cols):
        # the dots' sums, term by term and in their order: one per
        # operand, then their difference.  Product first: XLA:CPU then
        # contracts each step into the FMA its own dot uses, so the
        # interpreted kernel agrees bit for bit with the einsum paths
        # (for m <= 8; above, XLA:CPU's dot sums in another order).
        x = x_ref[:, cols].astype(jnp.float32)
        u = u_ref[:, cols].astype(jnp.float32)
        mixed, desc = w[:, 0:1] * x[0:1], b[:, 0:1] * u[0:1]
        for j in range(1, m):
            mixed = w[:, j:j + 1] * x[j:j + 1] + mixed
            desc = b[:, j:j + 1] * u[j:j + 1] + desc
        o_ref[:, cols] = (mixed - desc).astype(o_ref.dtype)

    def step(c, carry):
        columns(pl.ds(pl.multiple_of(c * width, width), width))
        return carry

    full = bn // width
    if full:
        jax.lax.fori_loop(0, full, step, 0)
    if bn % width:  # a narrow block ends in part of a slice
        columns(slice(full * width, bn))


def _gossip_kernel(w_ref, b_ref, x_ref, u_ref, o_ref):
    _mix(w_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
         x_ref, u_ref, o_ref)


def gossip_update(W: jax.Array, B: jax.Array, X: jax.Array, U: jax.Array,
                  block_n: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    # interpret resolves in this un-jitted wrapper: top-level calls pick
    # up env flips by retracing; calls inside an outer jit bind it at
    # that outer trace
    return _gossip_update(W, B, X, U, block_n=block_n,
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _gossip_update(W, B, X, U, block_n, interpret):
    m, n = X.shape
    bn = _block_n(block_n, X, interpret)
    return pl.pallas_call(
        _gossip_kernel,
        name="_gossip_update_vpu",
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, n), X.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(W, B, X, U)


def _metropolis_weights(mask):
    """Metropolis re-weighting in VMEM (== core.mixing.metropolis_from_mask):
    w_ij = mask_ij / (1 + max(deg_i, deg_j)), w_ii = 1 - sum_j w_ij."""
    m = mask.shape[0]
    deg = mask.sum(axis=1)
    denom = 1.0 + jnp.maximum(deg[:, None], deg[None, :])
    w = mask / denom
    # diag via 2D iota: jnp.diag/eye don't lower on the TPU vector units.
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    eye = (rows == cols).astype(jnp.float32)
    return w + eye * (1.0 - w.sum(axis=1, keepdims=True))


def _mask_from_bits(bits, keep_prob, adj):
    """Realized symmetric off-diagonal edge mask from raw uint32 draws —
    the in-kernel counterpart of `core.mixing.symmetric_edge_mask`: one
    U[0,1) per UNDIRECTED edge (strict upper triangle, mirrored), gated
    by the off-diagonal base adjacency ``adj``.  Pure jnp so the mask
    math is unit-testable off-TPU with synthetic bits."""
    # uint32 -> U[0,1): top 23 bits into the mantissa of 1.xxx
    f = (bits >> 9) | jnp.uint32(0x3F800000)
    u01 = jax.lax.bitcast_convert_type(f, jnp.float32) - 1.0
    m = bits.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    keep = ((rows < cols) & (u01 < keep_prob)).astype(jnp.float32)
    return (keep + keep.T) * adj


def _masked_gossip_kernel(mask_ref, b_ref, x_ref, u_ref, o_ref):
    w = _metropolis_weights(mask_ref[...].astype(jnp.float32))
    _mix(w, b_ref[...].astype(jnp.float32), x_ref, u_ref, o_ref)


def masked_gossip_update(mask: jax.Array, B: jax.Array, X: jax.Array,
                         U: jax.Array, block_n: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """x' = metropolis(mask) @ X - B @ U, the mask -> re-weight -> gossip
    fusion for time-varying topologies.  ``mask`` is the (m, m) symmetric
    0/1 off-diagonal realized edge mask from `MixingProcess.realize`; the
    doubly-stochastic W_k is recomputed per program from the VMEM-resident
    mask and never staged from HBM."""
    return _masked_gossip_update(mask, B, X, U, block_n=block_n,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _masked_gossip_update(mask, B, X, U, block_n, interpret):
    m, n = X.shape
    bn = _block_n(block_n, X, interpret)
    return pl.pallas_call(
        _masked_gossip_kernel,
        name="_masked_gossip_update_vpu",
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, n), X.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(mask, B, X, U)


# ---------------------------------------------------------------------------
# In-kernel TPU randomness (runtime.default_kernel_rng path)
# ---------------------------------------------------------------------------

def _masked_gossip_krng_kernel(seed_ref, prob_ref, adj_ref, b_ref, x_ref,
                               u_ref, o_ref, mask_ref):
    """`_masked_gossip_kernel` with the edge-mask DRAW moved in-VMEM: the
    per-core TPU PRNG is seeded with (seed0, seed1) alone — deliberately
    NO program_id, unlike the obfuscate krng kernel — so every column
    tile re-draws the IDENTICAL (m, m) mask and the whole grid gossips
    over one consistent realized graph.  The realized mask is also
    written out (every tile stores the same block) so replay parity can
    pin this kernel against the HBM-mask path bit-for-bit, and so
    `MixingProcess` consumers still see the support they need."""
    from jax.experimental.pallas import tpu as pltpu
    pltpu.prng_seed(seed_ref[0], seed_ref[1])
    m = adj_ref.shape[0]
    bits = pltpu.bitcast(pltpu.prng_random_bits((m, m)), jnp.uint32)
    mask = _mask_from_bits(bits, prob_ref[0],
                           adj_ref[...].astype(jnp.float32))
    mask_ref[...] = mask
    _mix(_metropolis_weights(mask), b_ref[...].astype(jnp.float32),
         x_ref, u_ref, o_ref)


def masked_gossip_update_krng(seed: jax.Array, keep_prob, adj: jax.Array,
                              B: jax.Array, X: jax.Array, U: jax.Array,
                              block_n: int | None = None,
                              interpret: bool | None = None):
    """TPU-only masked gossip with the Bernoulli edge-mask draw in-VMEM.

    ``seed``: (2,) uint32/int32 PRNG words (derive from the step's mixing
    key); ``keep_prob``: scalar per-edge keep probability (1 - dropout
    rate); ``adj``: (m, m) off-diagonal 0/1 base adjacency gating which
    edges can exist (`MixingProcess.base_mask`; pass all-ones-off-diag
    for an unconstrained ER redraw).  Returns ``(out, mask)`` — feed
    ``mask`` back through `masked_gossip_update` to cross-validate the
    two paths bit-for-bit.  The mask comes from the TPU PRNG stream, NOT
    the jax.random counter stream, so it differs draw-for-draw from
    `core.mixing.symmetric_edge_mask` under the same seed — parity is by
    replaying the exported mask, exactly the Lambda-bits contract of
    `obfuscate_update_krng`.  Raises at lowering on non-TPU backends
    (no Mosaic PRNG rule on CPU, even under ``interpret=True``)."""
    return _masked_gossip_update_krng(seed, keep_prob, adj, B, X, U,
                                      block_n=block_n,
                                      interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _masked_gossip_update_krng(seed, keep_prob, adj, B, X, U, block_n,
                               interpret):
    m, n = X.shape
    bn = _block_n(block_n, X, interpret)
    seed = jnp.asarray(seed, jnp.int32)
    assert seed.shape == (2,), seed.shape
    prob = jnp.asarray(keep_prob, jnp.float32).reshape(1)
    return pl.pallas_call(
        _masked_gossip_krng_kernel,
        name="_masked_gossip_update_krng_vpu",
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), X.dtype),
            jax.ShapeDtypeStruct((m, m), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(seed, prob, adj, B, X, U)


def _guarded_gossip_kernel(mask_ref, b_ref, x_ref, u_ref, xt_ref, ut_ref,
                           o_ref, *, clip):
    """masked_gossip with per-link finite guards: the matmul form cannot
    survive a NaN/Inf transmit (one poisoned operand contaminates the
    whole dot-product row), so the off-diagonal accumulation is unrolled
    to the explicit per-link v_ij = w_ij xt_j - b_ij ut_j tensor, each
    link guarded BEFORE the sum.  (m, m, bn) f32 lives in VMEM, so
    `gossip_block(guarded=True)` narrows bn as m grows.  The diagonal
    terms never cross a wire and use the clean x/u buffers."""
    mask = mask_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    m = mask.shape[0]
    deg = mask.sum(axis=1)
    denom = 1.0 + jnp.maximum(deg[:, None], deg[None, :])
    w = mask / denom  # off-diagonal by construction (mask has zero diag)
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    eye = (rows == cols).astype(jnp.float32)
    w_diag = 1.0 - w.sum(axis=1)
    b_diag = (b * eye).sum(axis=1)
    b_off = b * (1.0 - eye)
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    self_term = w_diag[:, None] * x - b_diag[:, None] * u
    xt = xt_ref[...].astype(jnp.float32)
    ut = ut_ref[...].astype(jnp.float32)
    v = (w[:, :, None] * xt[None, :, :]
         - b_off[:, :, None] * ut[None, :, :])
    if clip is not None:
        # clip propagates NaN; the isfinite where must pick the zero branch.
        v = jnp.where(jnp.isfinite(v), jnp.clip(v, -clip, clip),
                      jnp.zeros_like(v))
    o_ref[...] = (self_term + v.sum(axis=1)).astype(o_ref.dtype)


def guarded_gossip_update(mask: jax.Array, B: jax.Array, X: jax.Array,
                          U: jax.Array, XT: jax.Array, UT: jax.Array,
                          clip: float | None,
                          block_n: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """Fault-tolerant masked gossip: Metropolis re-weighting from the
    realized edge mask (as `masked_gossip_update`) with every
    off-diagonal link contribution passed through the finite-guard
    ``where(isfinite(v), clip(v, ±clip), 0)`` before accumulation
    (``clip=None`` disables the guard — the raw chaos scenario the
    nan-sentinel layer is tested against).

    ``X``/``U`` are the agents' own (clean) buffers, consumed only by
    the diagonal terms; ``XT``/``UT`` are the TRANSMIT buffers (after
    `faults.inject.poison_transmit`), consumed by the off-diagonal
    per-link terms — a corrupt sender poisons what it puts on the wire,
    never its own state.  Mirrors `faults.inject.guarded_gossip_mix`;
    keep the two in sync."""
    return _guarded_gossip_update(
        mask, B, X, U, XT, UT,
        clip=None if clip is None else float(clip), block_n=block_n,
        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("clip", "block_n", "interpret"))
def _guarded_gossip_update(mask, B, X, U, XT, UT, clip, block_n, interpret):
    m, n = X.shape
    bn = _block_n(block_n, X, interpret, guarded=True)
    return pl.pallas_call(
        functools.partial(_guarded_gossip_kernel, clip=clip),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, m), lambda i: (0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, n), X.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(mask, B, X, U, XT, UT)


# ---------------------------------------------------------------------------
# Ring-scheduled fused gossip (the ppermute-pipeline counterpart)
# ---------------------------------------------------------------------------

def _ring_accumulate(w, b, perm, x, u, o_ref, v_ref, stage_ref, *, ndirs,
                     capture):
    """Shared ring body: self term, then per-direction staged v_d shifted
    by the 0/1 permutation and accumulated IN DIRECTION ORDER (the
    historic ring anchor — self first, then directions 0..ndirs-1).

    ``stage_ref`` is the (2, m, bn) double-buffered VMEM staging:
    direction d is consumed from slot d%2 while direction d+1 is computed
    into the other slot — the structure a TPU schedule overlaps with the
    shift's DMA.  With ``capture`` the exact staged buffer is also
    written to ``v_ref[d]`` (the wiretap tap point)."""
    acc = w[:, 0:1] * x - b[:, 0:1] * u
    stage_ref[0] = w[:, 1:2] * x - b[:, 1:2] * u
    for d in range(ndirs):
        cur, nxt = d % 2, (d + 1) % 2
        if d + 1 < ndirs:
            # stage direction d+1 while direction d's shift is in flight
            stage_ref[nxt] = (w[:, d + 2:d + 3] * x
                             - b[:, d + 2:d + 3] * u)
        v = stage_ref[cur]
        if capture:
            v_ref[d] = v
        # 0/1 permutation matmul == the ppermute shift, bit-exact for
        # finite v (each output row selects exactly one staged row)
        acc = acc + jax.lax.dot_general(
            perm[d], v, (((1,), (0,)), ((), ())), precision=_EXACT,
            preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _ring_gossip_kernel(w_ref, b_ref, perm_ref, x_ref, u_ref, o_ref,
                        *refs, ndirs, capture):
    v_ref = refs[0] if capture else None
    stage_ref = refs[-1]
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    _ring_accumulate(w_ref[...], b_ref[...], perm_ref[...], x, u,
                     o_ref, v_ref, stage_ref, ndirs=ndirs, capture=capture)


def _ring_obfuscate_kernel(w_ref, b_ref, perm_ref, x_ref, g_ref, bits_ref,
                           scal_ref, o_ref, *refs, ndirs, capture):
    """Λ-draw fused in: u = (2 lam_bar U(bits)) ∘ g is realized in VMEM
    (same mantissa math as `obfuscate._obfuscate_math`) and never touches
    HBM unless captured for the audit record."""
    stage_ref = refs[-1]
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    f = (bits_ref[...] >> 9) | jnp.uint32(0x3F800000)
    u01 = jax.lax.bitcast_convert_type(f, jnp.float32) - 1.0
    lam = (2.0 * scal_ref[0]) * u01
    u = lam * g
    if capture:
        v_ref, u_ref = refs[0], refs[1]
        u_ref[...] = u
    else:
        v_ref = None
    _ring_accumulate(w_ref[...], b_ref[...], perm_ref[...], x, u,
                     o_ref, v_ref, stage_ref, ndirs=ndirs, capture=capture)


def _ring_tables(w_tab, b_tab, perms):
    w_tab = jnp.asarray(w_tab, jnp.float32)
    b_tab = jnp.asarray(b_tab, jnp.float32)
    perms = jnp.asarray(perms, jnp.float32)
    ndirs = perms.shape[0]
    if w_tab.shape != b_tab.shape or w_tab.shape[1] != 1 + ndirs:
        raise ValueError(
            f"direction tables must be (m, 1+ndirs): w {w_tab.shape}, "
            f"b {b_tab.shape}, perms {perms.shape}")
    return w_tab, b_tab, perms, ndirs


def ring_gossip_update(w_tab: jax.Array, b_tab: jax.Array,
                       perms: jax.Array, X: jax.Array, U: jax.Array,
                       capture: bool = False,
                       block_n: int = DEFAULT_BLOCK_N,
                       interpret: bool | None = None):
    """Ring-scheduled x' = W X - B U from direction tables.

    ``w_tab``/``b_tab``: (m, 1+ndirs) per-agent coefficients (column 0 =
    self, column 1+d = this agent's weight toward direction d's
    neighbor), as produced by `dist.collectives.directional_weights` and
    `sample_b_draws`/`mask_b_draws`; ``perms``: (ndirs, m, m) stacked 0/1
    receiver<-sender permutations (`dist.collectives.perm_stack`).
    Returns ``out`` or ``(out, v)`` with ``capture=True``, where
    ``v[d]`` is direction d's staged wire buffer — sender-major, i.e.
    ``v[d][j]`` is what agent j put on the wire for direction d, exactly
    what `torus_gossip_pdsgd(capture=True)` taps."""
    w_tab, b_tab, perms, _ = _ring_tables(w_tab, b_tab, perms)
    return _ring_gossip_update(w_tab, b_tab, perms, X, U,
                               capture=bool(capture), block_n=block_n,
                               interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("capture", "block_n", "interpret"))
def _ring_gossip_update(w_tab, b_tab, perms, X, U, capture, block_n,
                        interpret):
    m, n = X.shape
    nd = perms.shape[0]
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    tab_spec = pl.BlockSpec((m, 1 + nd), lambda i: (0, 0))
    out_specs = [pl.BlockSpec((m, bn), lambda i: (0, i))]
    out_shape = [jax.ShapeDtypeStruct((m, n), X.dtype)]
    if capture:
        out_specs.append(pl.BlockSpec((nd, m, bn), lambda i: (0, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((nd, m, n), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_ring_gossip_kernel, ndirs=nd, capture=capture),
        grid=(n // bn,),
        in_specs=[
            tab_spec,
            tab_spec,
            pl.BlockSpec((nd, m, m), lambda i: (0, 0, 0)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((m, bn), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, m, bn), jnp.float32)],
        interpret=interpret,
    )(w_tab, b_tab, perms, X, U)
    return tuple(out) if capture else out[0]


def ring_obfuscate_gossip(w_tab: jax.Array, b_tab: jax.Array,
                          perms: jax.Array, X: jax.Array, G: jax.Array,
                          bits: jax.Array, lam_bar,
                          capture: bool = False,
                          block_n: int = DEFAULT_BLOCK_N,
                          interpret: bool | None = None):
    """The fully fused ring step: Λ-draw + obfuscate + staged ring gossip
    in one pallas_call.

    ``bits``: (m, n) uint32 counter draws (the same stream the eager and
    fused-concat paths consume, so the realized Λ matches them);
    ``lam_bar``: the step's Λ half-range.  Returns ``out`` or, with
    ``capture=True``, ``(out, v, u)`` where ``v`` is the (ndirs, m, n)
    staged wire stream and ``u`` the kernel's own obfuscated-gradient
    buffer — emitted from the kernel (not re-derived) so the audit
    records what this path actually realized.  Dropped links arrive as
    zeroed table entries and produce exactly-zero v rows."""
    w_tab, b_tab, perms, _ = _ring_tables(w_tab, b_tab, perms)
    return _ring_obfuscate_gossip(w_tab, b_tab, perms, X, G, bits,
                                  lam_bar, capture=bool(capture),
                                  block_n=block_n,
                                  interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("capture", "block_n", "interpret"))
def _ring_obfuscate_gossip(w_tab, b_tab, perms, X, G, bits, lam_bar,
                           capture, block_n, interpret):
    m, n = X.shape
    nd = perms.shape[0]
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    scal = jnp.asarray(lam_bar, jnp.float32).reshape(1)
    tab_spec = pl.BlockSpec((m, 1 + nd), lambda i: (0, 0))
    data_spec = pl.BlockSpec((m, bn), lambda i: (0, i))
    out_specs = [data_spec]
    out_shape = [jax.ShapeDtypeStruct((m, n), X.dtype)]
    if capture:
        out_specs += [pl.BlockSpec((nd, m, bn), lambda i: (0, 0, i)),
                      data_spec]
        out_shape += [jax.ShapeDtypeStruct((nd, m, n), jnp.float32),
                      jax.ShapeDtypeStruct((m, n), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_ring_obfuscate_kernel, ndirs=nd,
                          capture=capture),
        grid=(n // bn,),
        in_specs=[
            tab_spec,
            tab_spec,
            pl.BlockSpec((nd, m, m), lambda i: (0, 0, 0)),
            data_spec,
            data_spec,
            data_spec,
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, m, bn), jnp.float32)],
        interpret=interpret,
    )(w_tab, b_tab, perms, X, G, bits, scal)
    return tuple(out) if capture else out[0]


def _ring_obfuscate_krng_kernel(w_ref, b_ref, perm_ref, x_ref, g_ref,
                                seed_ref, scal_ref, o_ref, bits_ref,
                                *refs, ndirs, capture):
    """`_ring_obfuscate_kernel` with the Λ bits drawn in-VMEM by the TPU
    PRNG — re-seeded per column tile from `obfuscate.tile_seed` (the
    tile index folded into the random word) so the
    stream is grid-order independent, exported via ``bits_ref`` for
    replay parity through the HBM-bits kernel (the
    `obfuscate_update_krng` contract)."""
    stage_ref = refs[-1]
    pltpu.prng_seed(*tile_seed(seed_ref, 0, pl.program_id(0)))
    bits = pltpu.bitcast(pltpu.prng_random_bits(o_ref.shape), jnp.uint32)
    bits_ref[...] = bits
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    f = (bits >> 9) | jnp.uint32(0x3F800000)
    u01 = jax.lax.bitcast_convert_type(f, jnp.float32) - 1.0
    lam = (2.0 * scal_ref[0]) * u01
    u = lam * g
    if capture:
        v_ref, u_ref = refs[0], refs[1]
        u_ref[...] = u
    else:
        v_ref = None
    _ring_accumulate(w_ref[...], b_ref[...], perm_ref[...], x, u,
                     o_ref, v_ref, stage_ref, ndirs=ndirs, capture=capture)


def ring_obfuscate_gossip_krng(w_tab: jax.Array, b_tab: jax.Array,
                               perms: jax.Array, X: jax.Array,
                               G: jax.Array, seed: jax.Array, lam_bar,
                               capture: bool = False,
                               block_n: int = DEFAULT_BLOCK_N,
                               interpret: bool | None = None):
    """TPU-only fused ring step with in-VMEM Λ randomness.

    ``seed``: (2,) uint32/int32 PRNG words: the step index, then random
    bits from the step's Λ key (`core.pdsgd.krng_seed`).  Returns
    ``(out, bits)`` — or ``(out, bits, v, u)`` with
    ``capture=True`` — where ``bits`` is the uint32 draw the kernel
    used; feed it back through `ring_obfuscate_gossip` to pin the two
    randomness paths bit-for-bit.  Raises at lowering on non-TPU
    backends (no Mosaic PRNG rule on CPU, even under ``interpret=True``)
    — the `runtime.default_kernel_rng` knob keeps this path off
    everywhere it cannot run."""
    w_tab, b_tab, perms, _ = _ring_tables(w_tab, b_tab, perms)
    return _ring_obfuscate_gossip_krng(
        w_tab, b_tab, perms, X, G, seed, lam_bar, capture=bool(capture),
        block_n=block_n, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("capture", "block_n", "interpret"))
def _ring_obfuscate_gossip_krng(w_tab, b_tab, perms, X, G, seed, lam_bar,
                                capture, block_n, interpret):
    m, n = X.shape
    nd = perms.shape[0]
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    seed = jnp.asarray(seed, jnp.int32)
    assert seed.shape == (2,), seed.shape
    scal = jnp.asarray(lam_bar, jnp.float32).reshape(1)
    tab_spec = pl.BlockSpec((m, 1 + nd), lambda i: (0, 0))
    data_spec = pl.BlockSpec((m, bn), lambda i: (0, i))
    out_specs = [data_spec, data_spec]
    out_shape = [jax.ShapeDtypeStruct((m, n), X.dtype),
                 jax.ShapeDtypeStruct((m, n), jnp.uint32)]
    if capture:
        out_specs += [pl.BlockSpec((nd, m, bn), lambda i: (0, 0, i)),
                      data_spec]
        out_shape += [jax.ShapeDtypeStruct((nd, m, n), jnp.float32),
                      jax.ShapeDtypeStruct((m, n), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_ring_obfuscate_krng_kernel, ndirs=nd,
                          capture=capture),
        grid=(n // bn,),
        in_specs=[
            tab_spec,
            tab_spec,
            pl.BlockSpec((nd, m, m), lambda i: (0, 0, 0)),
            data_spec,
            data_spec,
            pl.BlockSpec((2,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, m, bn), jnp.float32)],
        interpret=interpret,
    )(w_tab, b_tab, perms, X, G, seed, scal)
    return tuple(out)

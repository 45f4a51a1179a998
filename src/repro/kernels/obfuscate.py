"""Fused gradient-obfuscation kernel — the paper's privacy hot loop.

Computes the self-term of Eq. (3) in one VMEM pass per tile:

    v = w_self * x - b_self * (lambda ∘ g),   lambda = 2*lam_bar*U(bits)

Without fusion the update reads/writes d-sized arrays four times
(materialize lambda, materialize u = lambda*g, mix, subtract); fused it is
one read of (x, g, bits) + one write of v — a ~3x HBM-traffic cut on an
op that runs on every parameter, every step (d up to 34B here vs the
paper's 1.7M).  A tile is all m rows by a column block that
`blocks.column_block` sizes from VMEM (`obfuscate_block`).

On a real TPU the `bits` input disappears: `obfuscate_update_krng` seeds
the per-core PRNG (`pltpu.prng_seed`, re-seeded per grid tile from
`tile_seed` so tiles stay order-independent) and draws the bits in-VMEM with
`pltpu.prng_random_bits` — zero HBM traffic for lambda, behind the
`runtime.default_kernel_rng` knob.  The variant also WRITES the bits it
drew as a second output, so the parity test can replay them through the
HBM-input kernel and assert the two paths agree bit-for-bit.  The CPU
interpreter has no PRNG primitive (no Mosaic lowering, even under
``interpret=True``), so the portable kernel takes counter-based bits from
jax.random outside — correctness-identical, and validated against
ref.obfuscate_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blocks import COMPILER_PARAMS, column_block
from .runtime import resolve_interpret

# float32 (m, bc) temporaries of the body: the uniform, lambda, x and g
# widened, the result before its cast.
_TEMPS = 5


def obfuscate_block(m: int, width: int, x_dtype, g_dtype,
                    interpret: bool = False) -> int:
    """Column block of the obfuscate kernels over (m, width) buffers: x,
    g and v stream in their dtypes and the bits as uint32, in or out."""
    xs, gs = jnp.dtype(x_dtype).itemsize, jnp.dtype(g_dtype).itemsize
    return column_block(m, width, (xs, gs, 4, xs), _TEMPS, interpret)


def _grid(x, g, block, interpret):
    """(br, bc) and the grid for an (R, C) call: ``block=None`` is one row
    of tiles with the rule's column block.  The last column block may
    overhang C: the kernel is elementwise, and Pallas drops the writes
    past the edge."""
    R, C = x.shape
    if block is None:
        block = (R, obfuscate_block(R, C, x.dtype, g.dtype, interpret))
    br, bc = min(block[0], R), min(block[1], C)
    assert R % br == 0, (x.shape, block)
    return br, bc, (R // br, pl.cdiv(C, bc))


def _obfuscate_math(x, g, bits, lam_bar, w_self, b_self, out_dtype):
    """Shared tile math: v = w_self*x - b_self*(lambda(bits) ∘ g)."""
    # uint32 -> U[0,1): stuff the top 23 bits into the mantissa of 1.xxx
    f = (bits >> 9) | jnp.uint32(0x3F800000)
    u01 = jax.lax.bitcast_convert_type(f, jnp.float32) - 1.0
    lam = (2.0 * lam_bar) * u01
    g = g.astype(jnp.float32)
    x = x.astype(jnp.float32)
    return (w_self * x - b_self * (lam * g)).astype(out_dtype)


def obfuscated(g, bits, lam_bar):
    """lambda(bits) ∘ g in float32: `_obfuscate_math`'s u, for XLA to fuse
    where no kernel runs (the leafwise update on a mesh)."""
    f = (bits >> 9) | jnp.uint32(0x3F800000)
    u01 = jax.lax.bitcast_convert_type(f, jnp.float32) - 1.0
    return ((2.0 * lam_bar) * u01) * g.astype(jnp.float32)


def _obfuscate_kernel(x_ref, g_ref, bits_ref, scal_ref, o_ref):
    """scal_ref: (3,) = [lam_bar, w_self, b_self] in SMEM-like VMEM."""
    o_ref[...] = _obfuscate_math(x_ref[...], g_ref[...], bits_ref[...],
                                 scal_ref[0], scal_ref[1], scal_ref[2],
                                 o_ref.dtype)


def obfuscate_update(x: jax.Array, g: jax.Array, bits: jax.Array,
                     lam_bar, w_self, b_self,
                     block: tuple[int, int] | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """x, g: (R, C) same shape; bits: (R, C) uint32.  Returns v same shape.

    R is a multiple of the row block; ``block=None`` takes (R,
    `obfuscate_block`), and the last column block may overhang C (ops.py
    flattens pytrees and pads them to whole vregs).  ``interpret=None``
    defers to `runtime.default_interpret` (compiled on TPU, interpreter
    elsewhere); resolved in this un-jitted wrapper, so TOP-LEVEL calls pick
    up env-var flips by retracing.  Calls inside an outer jit (e.g. a
    training step) bind the knob once at that outer trace — rebuild the
    step to change it.
    """
    return _obfuscate_update(x, g, bits, lam_bar, w_self, b_self,
                             block=block,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _obfuscate_update(x, g, bits, lam_bar, w_self, b_self,
                      block, interpret):
    R, C = x.shape
    br, bc, grid = _grid(x, g, block, interpret)
    scal = jnp.stack([jnp.asarray(lam_bar, jnp.float32),
                      jnp.asarray(w_self, jnp.float32),
                      jnp.asarray(b_self, jnp.float32)])
    return pl.pallas_call(
        _obfuscate_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((3,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(x, g, bits, scal)


# ---------------------------------------------------------------------------
# In-kernel TPU randomness (runtime.default_kernel_rng path)
# ---------------------------------------------------------------------------

# Odd 32-bit multipliers (golden-ratio and murmur3 constants, as int32):
# multiplying by an odd number is a bijection mod 2**32, so distinct tile
# coordinates always give distinct seed words.
_TILE_MIX_I = -1640531527  # 0x9E3779B9
_TILE_MIX_J = -2048144789  # 0x85EBCA6B


def tile_seed(seed_ref, i, j):
    """The two PRNG seed words for grid tile (i, j): the call's (seed0,
    seed1) with ``i`` folded into the first word and ``j`` into the
    second.  Mosaic seeds the TPU PRNG with at most two words, so the
    coordinates cannot be passed as extra words; folding them keeps each
    tile's stream a function of (seed, i, j) alone, never of the order in
    which the grid runs.

    Callers put the step index in seed0 and random bits in seed1
    (`core.pdsgd.krng_seed`).  With one row of tiles, as on every fused
    path (i = 0), word 0 is then the step itself: no two steps of a run
    (below 2**32 steps) and no two tiles of a step share a stream, by
    construction rather than by chance."""
    return seed_ref[0] ^ (i * _TILE_MIX_I), seed_ref[1] ^ (j * _TILE_MIX_J)


def _obfuscate_krng_kernel(x_ref, g_ref, seed_ref, scal_ref, o_ref, bits_ref):
    """Same math as `_obfuscate_kernel`, but the uint32 draws come from the
    per-core TPU PRNG instead of an HBM input.  The PRNG is re-seeded at
    every tile from `tile_seed`, so the stream a tile sees depends only on
    the step seed and its grid coordinates, never on grid iteration
    order.  The bits are also written out so the HBM-input kernel can
    replay them (parity test) and so the eager Lambda-audit path can
    reconstruct lambda."""
    from jax.experimental.pallas import tpu as pltpu
    pltpu.prng_seed(*tile_seed(seed_ref, pl.program_id(0), pl.program_id(1)))
    bits = pltpu.bitcast(pltpu.prng_random_bits(o_ref.shape), jnp.uint32)
    bits_ref[...] = bits
    o_ref[...] = _obfuscate_math(x_ref[...], g_ref[...], bits,
                                 scal_ref[0], scal_ref[1], scal_ref[2],
                                 o_ref.dtype)


def obfuscate_update_krng(x: jax.Array, g: jax.Array, seed: jax.Array,
                          lam_bar, w_self, b_self,
                          block: tuple[int, int] | None = None,
                          interpret: bool | None = None):
    """TPU-only obfuscation with in-VMEM randomness.

    ``seed``: (2,) uint32/int32 PRNG seed words: the step index, then
    random bits from the step's Lambda key (`core.pdsgd.krng_seed`; see
    `tile_seed`).  Returns
    ``(v, bits)`` where ``bits`` is the (R, C) uint32 draw the kernel used
    — feed it back through `obfuscate_update` to cross-validate the two
    randomness paths bit-for-bit.  Raises at lowering on non-TPU backends
    (`pltpu.prng_seed` has no CPU/interpret rule); `runtime.
    default_kernel_rng` keeps this path off everywhere it cannot run.
    """
    return _obfuscate_update_krng(x, g, seed, lam_bar, w_self, b_self,
                                  block=block,
                                  interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _obfuscate_update_krng(x, g, seed, lam_bar, w_self, b_self,
                           block, interpret):
    R, C = x.shape
    br, bc, grid = _grid(x, g, block, interpret)
    scal = jnp.stack([jnp.asarray(lam_bar, jnp.float32),
                      jnp.asarray(w_self, jnp.float32),
                      jnp.asarray(b_self, jnp.float32)])
    seed = jnp.asarray(seed, jnp.int32)
    assert seed.shape == (2,), seed.shape
    return pl.pallas_call(
        _obfuscate_krng_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((2,), lambda i, j: (0,)),
            pl.BlockSpec((3,), lambda i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), x.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.uint32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(x, g, seed, scal)

#!/usr/bin/env python
"""Time the dense gossip kernel's contraction on a TPU, over agent counts.

For each agent count m, x' = W X - B U over (m, n) bf16 buffers of
m * n = ``--elements`` elements (default: the granite one-chip cell's
4 x 211,393,536), in four forms:

* ``vpu``: `kernels.gossip.gossip_update` as it ships, at the block
  `gossip_block` gives it and at the block of the ``mxu`` form, so that
  the gain of the contraction and that of the wider block read apart;
* ``mxu``: the two ``HIGHEST`` (m, m) @ (m, bn) dots the kernel ran before
  it contracted on the VPU, at the block that form took (five float32
  (m, bn) temporaries by `kernels.blocks`' count);
* ``floor``: the same bytes with no contraction (x' = x - u), at both
  blocks.

Off a TPU the kernels run in interpret mode, which checks the script and
times nothing of interest.  Prints one JSON line per (m, form, block):
``ms`` holds three means, each of ``--calls`` back-to-back calls after a
warm-up, on the host clock around ``block_until_ready``.

    python scripts/gossip_forms.py [--agents 2 4 8 16 32] [--calls 10]
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import COMPILER_PARAMS, column_block
from repro.kernels.gossip import gossip_update

GRANITE = 211_393_536
INTERPRET = jax.default_backend() != "tpu"
# float32 (m, bn) temporaries of the mxu form: x, u widened, the two
# products, the result
MXU_TEMPS = 5


def _mxu_kernel(w_ref, b_ref, x_ref, u_ref, o_ref):
    exact = jax.lax.Precision.HIGHEST
    mixed = jnp.dot(w_ref[...], x_ref[...].astype(jnp.float32),
                    precision=exact, preferred_element_type=jnp.float32)
    desc = jnp.dot(b_ref[...], u_ref[...].astype(jnp.float32),
                   precision=exact, preferred_element_type=jnp.float32)
    o_ref[...] = (mixed - desc).astype(o_ref.dtype)


def _floor_kernel(x_ref, u_ref, o_ref):
    o_ref[...] = (x_ref[...].astype(jnp.float32)
                  - u_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn",))
def _mxu(W, B, X, U, bn):
    m, n = X.shape
    mat = pl.BlockSpec((m, m), lambda i: (0, 0))
    spec = pl.BlockSpec((m, bn), lambda i: (0, i))
    return pl.pallas_call(
        _mxu_kernel, grid=(pl.cdiv(n, bn),), in_specs=[mat, mat, spec, spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct((m, n), X.dtype),
        compiler_params=COMPILER_PARAMS, interpret=INTERPRET)(W, B, X, U)


@functools.partial(jax.jit, static_argnames=("bn",))
def _floor(W, B, X, U, bn):
    del W, B
    m, n = X.shape
    spec = pl.BlockSpec((m, bn), lambda i: (0, i))
    return pl.pallas_call(
        _floor_kernel, grid=(pl.cdiv(n, bn),), in_specs=[spec, spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct((m, n), X.dtype),
        compiler_params=COMPILER_PARAMS, interpret=INTERPRET)(X, U)


def _vpu(W, B, X, U, bn):
    return gossip_update(W, B, X, U, block_n=bn, interpret=INTERPRET)


def _ms(fn, args, calls):
    jax.block_until_ready(fn(*args))
    means = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        means.append((time.perf_counter() - t) / calls * 1e3)
    return means


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--agents", type=int, nargs="+",
                    default=[2, 3, 4, 5, 6, 8, 12, 16, 24, 32])
    ap.add_argument("--elements", type=int, default=4 * GRANITE)
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    for m in args.agents:
        n = args.elements // m // 512 * 512
        kx, ku, kw, kb = jax.random.split(jax.random.key(m), 4)
        X = jax.random.normal(kx, (m, n), jnp.bfloat16)
        U = jax.random.normal(ku, (m, n), jnp.bfloat16)
        W = jax.random.dirichlet(kw, jnp.ones(m), (m,))
        B = jax.random.dirichlet(kb, jnp.ones(m), (m,))
        rule = min(column_block(m, n, (2, 2, 2), 0, INTERPRET), n)
        mxu = min(column_block(m, n, (2, 2, 2), MXU_TEMPS, INTERPRET), n)
        runs = [("vpu", _vpu, rule), ("vpu", _vpu, mxu), ("mxu", _mxu, mxu),
                ("floor", _floor, mxu), ("floor", _floor, rule)]
        for form, fn, bn in dict.fromkeys(runs):
            ms = _ms(functools.partial(fn, bn=bn), (W, B, X, U), args.calls)
            print(json.dumps({"m": m, "n": n, "form": form, "block": bn,
                              "ms": ms, "device": dev.device_kind}),
                  flush=True)
        del X, U


if __name__ == "__main__":
    main()

"""Operations and bytes the measured work needs, computed from shapes.

These are the yardstick's own counts: a later change to the program that
fuses, renames or replaces a kernel is judged against the same numbers.
"""
from __future__ import annotations

import math


def params_per_agent(abstract) -> int:
    """D: the elements of one agent's parameter pytree (leaves of shape
    ``(...)``, no agent axis)."""
    import jax
    return sum(math.prod(a.shape) for a in jax.tree.leaves(abstract))


def update_min_bytes(m: int, D: int, itemsize: int, chips: int = 1):
    """Least HBM traffic of one Eq. (4) update x' = W x - B (Lambda o g)
    over m agents of D parameters, on each of ``chips`` chips that share
    it: read x and g once, write x' once, 3 * m * D elements, each chip
    its share.  Lambda is drawn in-kernel and W, B are m x m, so neither
    counts."""
    return 3 * m * D * itemsize / chips


def update_min_seconds(m: int, D: int, itemsize: int,
                       hbm_bytes_per_s: float, chips: int = 1) -> float:
    """`update_min_bytes` at one chip's HBM bandwidth: the update is bound
    by memory traffic (2 flops per element of x and g against 6 bytes in
    bf16), not by arithmetic, and the chips that share it work side by
    side."""
    return update_min_bytes(m, D, itemsize, chips) / hbm_bytes_per_s

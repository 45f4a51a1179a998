"""Column blocks of the update kernels, sized from VMEM.

The obfuscate and gossip kernels stream (m, bc) column blocks of the
agent-stacked (m, D) buffers through VMEM, one grid step per block.  On a
TPU v5e each grid step costs a fixed 0.3 µs whatever the block holds, so
a narrow block leaves a kernel bound by grid steps, not by HBM: at m = 4 a
(4, 256) block moves 2 KB an operand.  The block is therefore as wide as
VMEM allows: each kernel says what one of its columns holds in VMEM, and
`column_block` takes the widest 512 * 2**k columns that fit `VMEM_BUDGET`.

The count is the pessimistic one: rows are padded to the sublane tile of
their dtype (8 rows of 32 bits, 16 of bf16: a 4-row bf16 block counts as
16 rows), every streamed block counts twice (the pipeline double-buffers
it), and each float32 temporary of the body that spans the block counts as
an (m, bc) array of its own (the dense gossip works through column slices
of at most sixteen vregs a value, which are left out).  The kernels ask
Mosaic for `VMEM_BUDGET` of scoped VMEM, so a block the rule admits
compiles.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

# Column blocks are multiples of this: four 128-lane vregs.
LANE_BLOCK = 512
# What one kernel's blocks and temporaries may take, by the count above,
# and the scoped VMEM the kernels compile with (a v5e core has 128 MiB).
# At m = 4 it admits 65536 columns for the obfuscate kernel and 131072 for
# the dense gossip: alone on a v5e, the obfuscate kernel runs at 570 GB/s
# from 32768 columns on, and the gossip, then two MXU dots, gained 1.7%
# from 32768 to 65536 columns and 0.5% more at 131072.
VMEM_BUDGET = 28 << 20
# The interpreter runs a kernel body as XLA:CPU operations, and XLA:CPU
# computes the gossip's (m, m) @ (m, bc) dot with another kernel, which
# rounds differently, past about 2**15 output elements.  Interpreted blocks
# stay under that, so every block and layout agrees bit for bit there.
INTERPRET_ELEMENTS = 1 << 15

COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)


def vmem_rows(rows: int, itemsize: int) -> int:
    """``rows`` padded to the sublane tile of a dtype of ``itemsize``
    bytes: 8 rows of 32-bit words, 16 of bf16, 32 of int8."""
    tile = 8 * max(1, 4 // itemsize)
    return -(-rows // tile) * tile


def column_block(m: int, width: int, streamed: tuple[int, ...],
                 f32_temps: int, interpret: bool = False) -> int:
    """The column block for a kernel over an (m, width) buffer.

    ``streamed`` holds the itemsize of every (m, bc) block the kernel
    reads or writes; ``f32_temps`` counts the (m, bc) float32 temporaries
    its body holds.  Returns the widest ``LANE_BLOCK * 2**k`` whose
    double-buffered blocks and temporaries fit `VMEM_BUDGET` (at least
    one `LANE_BLOCK`; under ``interpret``, also at most
    `INTERPRET_ELEMENTS` elements), capped at ``width`` rounded up to
    `LANE_BLOCK`, so a narrow buffer is one block."""
    per_col = (2 * sum(vmem_rows(m, s) * s for s in streamed)
               + f32_temps * vmem_rows(m, 4) * 4)
    bc = LANE_BLOCK
    while (2 * bc * per_col <= VMEM_BUDGET
           and not (interpret and 2 * bc * m > INTERPRET_ELEMENTS)):
        bc *= 2
    return min(bc, -(-width // LANE_BLOCK) * LANE_BLOCK)

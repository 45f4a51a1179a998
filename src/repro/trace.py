"""Named regions of the training step and host spans of the data layer.

One mechanism for each kind of work, and the names they carry:

* `region` is a `jax.named_scope`, for device work.  The name lands in the
  ``op_name`` metadata of every compiled instruction the region emits
  (``jit(scanned)/while/body/.../step.update/layout/concatenate``) and
  changes no instruction: the optimized program with its metadata removed
  is the same with regions or without.  Backward work carries JAX's own
  ``transpose(jvp(...))`` wrapper inside `STEP_MODEL`, so forward and
  backward need no region of their own; a rematerialized forward lands
  under the wrapper too.
* `span` is a `jax.profiler.TraceAnnotation`, for host work.  Its keyword
  arguments are stats of the span in a profiler trace, on the device's
  clock; with no trace active it costs about a microsecond.

`bench/regions.py` reads both back from a trace: the compiled step's
instruction names map to regions through their metadata.
"""
from __future__ import annotations

import jax

__all__ = ["region", "span", "STEP_REGIONS", "UPDATE_REGIONS",
           "MODEL_REGIONS"]

# The training step (`core.pdsgd.make_decentralized_step`).  A step region
# opened inside another one owns its work (B^k is drawn inside the update).
STEP_MODEL = "step.model"     # the vmapped value_and_grad: forward, backward
STEP_MIX = "step.mix"         # W_k, support and mask, the B^k draw, faults
STEP_UPDATE = "step.update"   # every update path, with the children below
STEP_REPORT = "step.report"   # loss mean, consensus error, sentinels, means
STEP_REGIONS = (STEP_MODEL, STEP_MIX, STEP_UPDATE, STEP_REPORT)

# Children of `STEP_UPDATE` (`core.pdsgd`, `kernels.ops`).
LAYOUT = "layout"             # flatten/concat, padding, slicing, unflatten
OBFUSCATE = "obfuscate"       # u = Lambda ∘ g (kernel or jnp)
GOSSIP = "gossip"             # x' = W x - B u (kernel, einsum or collective)
UPDATE_REGIONS = (LAYOUT, OBFUSCATE, GOSSIP)

# Children of `STEP_MODEL` (`models.transformer`, `models.moe`).
EMBED = "embed"
ATTN = "attn"
MLP = "mlp"                   # the FFN half of a layer, MoE included
MOE_ROUTER = "moe.router"
MOE_EXPERTS = "moe.experts"
HEAD = "head"                 # final norm, unembedding, cross-entropy
MODEL_REGIONS = (EMBED, ATTN, MLP, MOE_ROUTER, MOE_EXPERTS, HEAD)

# Host spans of the data layer (`data.prefetch`); each carries the chunk's
# first step as the stat ``step``.
DATA_PRODUCE = "repro.data.produce"   # the worker synthesizes a chunk
DATA_PLACE = "repro.data.place"       # the worker puts it on the device
DATA_WAIT = "repro.data.wait"         # the consumer waits for it


def region(name: str):
    """Name the device work traced inside the ``with`` block."""
    return jax.named_scope(name)


def span(name: str, **stats):
    """Name the host work run inside the ``with`` block."""
    return jax.profiler.TraceAnnotation(name, **stats)

"""Device time of a region and of the collectives inside it.

The readers of the sharded cells (``update_ms``, ``update_collective_ms``,
``model_collective_ms``, ``update_all_roofline``) take
their numbers from here.  Like `bench.regions`, everything counts own
device time (`bench.trace.self_times`) of the operations inside the step
module's runs in the traced window, per training step and chip, and
attributes an operation to a region through its instruction's ``op_name``
(`bench.regions.instruction_regions`, in the readers' context).

A collective is an operation whose instruction the compiler named after a
collective opcode: all-gather, all-reduce, reduce-scatter,
collective-permute and all-to-all, synchronous or as an async
``-start``/``-done`` pair (``all-gather-start.3``), or, as the TPU compiler
wraps an async collective with its operand's copy,
``async-collective-start``/``-done``.  GSPMD emits them with the op_name of
the operation they serve (``.../gossip/ij,j...->i.../dot_general``), or
without one, and then they take the region of their operands or users: a
gossip einsum's all-gather lands in ``step.update/gossip``.
"""
from __future__ import annotations

import re

COLLECTIVE = re.compile(r"^((all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?|"
                        r"async-collective-(start|done))(\.\d+)?$")


def is_collective(name: str) -> bool:
    return COLLECTIVE.match(name) is not None


def own_ns(ctx) -> dict:
    """{instruction: own ns} of the step module's operations in the traced
    window, summed over the cell's chips; once per context."""
    if "own_ns" not in ctx:
        from bench import regions as G
        from bench import trace as T
        reduced, (lo, hi) = ctx["trace"], ctx["trace_window"]
        out = {}
        for plane in ctx["planes"]:
            ops = G.inside(reduced.device_ops[plane], G.module_intervals(
                reduced.modules.get(plane, []), ctx["module"]), lo, hi)
            for name, ns in T.self_times(ops):
                out[name] = out.get(name, 0) + ns
        ctx["own_ns"] = out
    return ctx["own_ns"]


def _under(region, path: str) -> bool:
    return region is not None and (region + "/").startswith(path + "/")


def region_ms(ctx, path: str, collectives_only: bool = False):
    """ms per step and chip of the region ``path`` and the regions under
    it (only its collectives with ``collectives_only``); None where the
    window has no step, no device plane, or no such time."""
    if ctx["trace"] is None or not ctx["planes"] or not ctx["steps"]:
        return None
    regions = ctx["regions"]
    ns = sum(v for name, v in own_ns(ctx).items()
             if _under(regions.get(name), path)
             and (not collectives_only or is_collective(name)))
    return 1e-6 * ns / (ctx["steps"] * len(ctx["planes"])) or None

"""update_kernel_ms (ms per step): summed device time of the Pallas
update kernels (`kernels.obfuscate`, `kernels.gossip`) in the traced
window, per training step and chip.  Kernels are matched by name; when
none matches the metric is absent (the run prints the compiled step's
custom calls on an earlier line)."""
from bench import trace as T

PATTERNS = ("obfuscate", "gossip", "pdsgd")


def read(ctx):
    if ctx["trace"] is None or not ctx["steps"] or not ctx["planes"]:
        return None
    lo, hi = ctx["trace_window"]
    ns = sum(e - s for p in ctx["planes"] for _, s, e in
             T.matching(T.clip(ctx["trace"].device_ops[p], lo, hi),
                        PATTERNS))
    if ns == 0:
        return None
    return ns / 1e6 / ctx["steps"] / len(ctx["planes"])

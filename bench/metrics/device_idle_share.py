"""device_idle_share (%): 1 - (union of the device's operation intervals)
/ (traced window), averaged over the chips the cell uses.  Source: the
profiler's device trace; the window is the harness's ``bench.window``
host span."""
from bench import trace as T


def read(ctx):
    if ctx["trace"] is None or not ctx["planes"]:
        return None
    lo, hi = ctx["trace_window"]
    busy = [T.busy_ns(ctx["trace"].device_ops[p], lo, hi)
            for p in ctx["planes"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))

"""update_roofline (%): the least time of one Eq. (4) update on this chip
over the measured time of the update kernels (`update_kernel_ms`, per
chip).  The update is bound by HBM bandwidth: it reads x and g once and
writes x' once, 3 * m * D elements of the stored dtype, of which each of
the cell's chips holds its share (`bench.counts.update_min_bytes`), at
one chip's HBM bytes/s (`bench.peaks`).  It counts the work, not the
implementation, so a change that fuses or replaces the kernels is judged
against the same count."""


def read(ctx):
    ms = ctx["read"]("update_kernel_ms")
    if ms is None or ctx["peaks"] is None:
        return None
    least = ctx["counts"].update_min_seconds(
        ctx["agents"], ctx["params_per_agent"], ctx["itemsize"],
        ctx["peaks"]["hbm_bytes_per_s"], ctx["chips"])
    return 100.0 * least / (ms / 1e3)

"""update_all_roofline (%): the least time of one Eq. (4) update on this
chip over the measured time of the whole update (`update_ms`, per chip).
The least time is `update_roofline`'s: 3 * m * D elements of the stored
dtype, each chip its share (`bench.counts.update_min_seconds`), at one
chip's HBM bytes/s (`bench.peaks`).  Where the update is not two kernels
(on a mesh the leafwise path's obfuscation is an XLA fusion that draws
Lambda, its gossip an einsum with collectives), this is the whole update against its
roofline: layout copies, bits, kernels and collectives all count."""


def read(ctx):
    ms = ctx["read"]("update_ms")
    if ms is None or ctx["peaks"] is None:
        return None
    least = ctx["counts"].update_min_seconds(
        ctx["agents"], ctx["params_per_agent"], ctx["itemsize"],
        ctx["peaks"]["hbm_bytes_per_s"], ctx["chips"])
    return 100.0 * least / (ms / 1e3)

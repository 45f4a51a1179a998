"""data_wait_ms (ms): mean, per dispatch of the window, of the harness's
host span around taking the next chunk from `data.prefetch_chunks` (the
time the step loop waited for the data layer)."""


def read(ctx):
    waits = [e - s for name, s, e in ctx["spans"] if name == "next_chunk"]
    return 1e3 * sum(waits) / len(waits) if waits else None

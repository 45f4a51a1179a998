"""data_produce_ms (ms per chunk): the prefetcher's work on each chunk the
window took, synthesis (``repro.data.produce``) and placement on the
device (``repro.data.place``), from the program's host spans in the
traced window (`bench.regions.data_produce_ms`)."""
from bench import regions as G


def read(ctx):
    if ctx["trace"] is None:
        return None
    return G.data_produce_ms(ctx["trace"].program_spans,
                             *ctx["trace_window"])

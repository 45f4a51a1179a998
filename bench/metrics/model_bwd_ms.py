"""model_bwd_ms (ms per step): own device time of the model's backward
pass (the program's ``step.model`` region under JAX's ``transpose``, the
rematerialized forward included), per training step and chip, inside the
step module's runs in the traced window (`bench.regions`)."""
from bench import regions as G


def read(ctx):
    return G.reader_ms(ctx, "model_bwd_ms")

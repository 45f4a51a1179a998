"""model_fwd_ms (ms per step): own device time of the model's forward pass
(the program's ``step.model`` region outside JAX's ``transpose``), per
training step and chip, inside the step module's runs in the traced
window (`bench.regions`)."""
from bench import regions as G


def read(ctx):
    return G.reader_ms(ctx, "model_fwd_ms")

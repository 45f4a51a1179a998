"""model_collective_ms (ms per step): own device time of the collective
operations (all-gather, all-reduce, reduce-scatter, collective-permute,
all-to-all, with their async start and done) in the program's
``step.model`` region, forward and backward, per training step and chip,
inside the step module's runs in the traced window (`bench.collectives`):
the fsdp all-gathers of the weights and the reductions of their
gradients.  Absent where the model runs no collective, as on one chip."""
from bench import collectives as C


def read(ctx):
    from repro import trace as rt
    return C.region_ms(ctx, rt.STEP_MODEL, collectives_only=True)

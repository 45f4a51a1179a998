"""update_collective_ms (ms per step): own device time of the collective
operations (all-gather, all-reduce, reduce-scatter, collective-permute,
all-to-all, with their async start and done) in the program's
``step.update/gossip`` region, per training step and chip, inside the
step module's runs in the traced window (`bench.collectives`).  Absent
where the gossip runs no collective, as on one chip."""
from bench import collectives as C


def read(ctx):
    from repro import trace as rt
    return C.region_ms(ctx, rt.STEP_UPDATE + "/" + rt.GOSSIP,
                       collectives_only=True)

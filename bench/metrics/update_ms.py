"""update_ms (ms per step): own device time of the whole update (the
program's ``step.update`` region and every region under it: layout,
obfuscate with the Lambda draw fused into it, gossip with its collectives), per
training step and chip, inside the step module's runs in the traced
window (`bench.collectives`)."""
from bench import collectives as C


def read(ctx):
    from repro import trace as rt
    return C.region_ms(ctx, rt.STEP_UPDATE)

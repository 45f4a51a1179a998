"""update_layout_ms (ms per step): own device time of the update's layout
copies (the program's ``step.update/layout`` region: flatten, pad, slice,
split, and the relayout copies the compiler adds for them), per training
step and chip, inside the step module's runs in the traced window
(`bench.regions`).  Absent where the update path has no such region."""
from bench import regions as G


def read(ctx):
    return G.reader_ms(ctx, "update_layout_ms")

"""step_mfu (%): the configuration's analytic model FLOPs per token
(``flops_per_token`` of its reference; recomputed operations do not
count) times the tokens trained in the window, over the window's wall
time, the cell's chips and the chip's peak bf16 FLOP/s
(`bench.peaks`)."""


def read(ctx):
    if ctx["peaks"] is None or not ctx["tokens"]:
        return None
    done = ctx["flops_per_token"] * ctx["tokens"]
    return 100.0 * done / (ctx["window_s"] * ctx["chips"]
                           * ctx["peaks"]["flops_bf16"])

"""The whole bilevel step's share of the chip's peak, in %: the operations
the traced window's cycles require (`flops.py`) over the window's length
times the chips' bf16 peak (`peaks.json`)."""


def read(ctx):
    t = ctx.trace
    if t.window_ns <= 0 or not ctx.window_flops:
        return None
    return 100.0 * ctx.window_flops / (t.window_ns / 1e9 * ctx.chips
                                       * ctx.peak['bf16_flops_per_s'])

"""Device time of one call of the trainer's inner step program, in ms: the
mean duration of its `jit_inner_step` executions in the traced window."""


def read(ctx):
    d = ctx.trace.module_durations('jit_inner_step')
    return sum(d) / len(d) / 1e6 if d else None

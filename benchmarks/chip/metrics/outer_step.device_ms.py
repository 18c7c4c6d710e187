"""Device time of one call of the trainer's hypergradient step program, in
ms: the mean duration of its `jit_outer_step` executions in the traced
window (sketch build, IHVP apply, mixed VJP and outer update, in one
program)."""


def read(ctx):
    d = ctx.trace.module_durations('jit_outer_step')
    return sum(d) / len(d) / 1e6 if d else None

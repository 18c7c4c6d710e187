"""Share of the traced window, in %, in which the device is idle while the
trainer's main thread waits for input: idle time overlapping a `data.wait`
span (the prefetch queue) or a `train.outer_batch` span (the outer batch's
build), averaged over the devices like `device.idle_share` (`scopes.py`)."""
import scopes


def read(ctx):
    t = ctx.trace
    ns = scopes.input_idle_ns(t)
    if ns is None or t.window_ns <= 0:
        return None
    return 100.0 * ns / t.window_ns

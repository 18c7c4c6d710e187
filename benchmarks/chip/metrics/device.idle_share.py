"""Share of the traced window, in %, in which no operation ran on the device:
100 x (1 - union of the device's operation intervals / window), averaged
over the devices in the trace."""


def read(ctx):
    t = ctx.trace
    if not t.devices or t.window_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)

"""Host time the prefetch thread takes to make one batch, in ms: the mean
duration of the `data.produce` spans that began in the traced window
(`scopes.py`)."""
import scopes


def read(ctx):
    d = scopes.span_durations_ns(ctx.trace, 'data.produce')
    return sum(d) / len(d) / 1e6 if d else None

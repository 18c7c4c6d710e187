"""Device time of the mixed second-order term, in ms per outer step: the
self time of the operations under the `mixed_vjp` scope
(`core/implicit.py`) in the traced window, over the `jit_outer_step`
executions there (`scopes.py`)."""
import scopes


def read(ctx):
    return scopes.read_scope(ctx, 'mixed_vjp')

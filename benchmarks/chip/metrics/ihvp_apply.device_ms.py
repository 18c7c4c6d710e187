"""Device time of the IHVP apply, in ms per outer step: the self time of
the operations under the `ihvp_apply` scope (`core/implicit.py`, around the
solver's apply) in the traced window, over the `jit_outer_step` executions
there (`scopes.py`)."""
import scopes


def read(ctx):
    return scopes.read_scope(ctx, 'ihvp_apply')

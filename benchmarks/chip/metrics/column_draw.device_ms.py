"""Device time of the hypergradient step's column draw, in ms per outer
step: the self time of the operations under the `column_draw` scope
(`NystromIHVP.prepare`, `sample_indices`) in the traced window, over the
`jit_outer_step` executions there (`scopes.py`)."""
import scopes


def read(ctx):
    return scopes.read_scope(ctx, 'column_draw')

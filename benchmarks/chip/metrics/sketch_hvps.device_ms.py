"""Device time of the sketch's k HVPs, in ms per outer step: the self time
of the operations under the `sketch_hvps` scope (`NystromIHVP.prepare`,
`extract_columns`) in the traced window, over the `jit_outer_step`
executions there (`scopes.py`)."""
import scopes


def read(ctx):
    return scopes.read_scope(ctx, 'sketch_hvps')

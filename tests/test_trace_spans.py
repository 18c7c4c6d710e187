"""The trainer's profiler spans and named scopes (docs/tracing.md).

- The hypergradient step's four device scopes (``column_draw``,
  ``sketch_hvps``, ``ihvp_apply``, ``mixed_vjp``) reach the lowered
  program's locations, on both of ``implicit_root``'s reverse-mode paths.
- ``Prefetcher`` records one ``data.produce`` span per item it makes and one
  ``data.wait`` span per item handed over.
- ``train_lm`` records a ``train`` step annotation per step and a
  ``train.outer_batch`` span per outer step.

Each test starts the profiler itself, into its own ``tmp_path``.
"""
import glob

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import NystromIHVP, SketchPolicy, implicit_root
from repro.data.loader import Prefetcher

SCOPES = ('column_draw', 'sketch_hvps', 'ihvp_apply', 'mixed_vjp')


def _span_counts(trace_dir, names) -> dict:
    """How many host events of each name the run's profile holds."""
    path, = glob.glob(f'{trace_dir}/**/*.xplane.pb', recursive=True)
    counts = dict.fromkeys(names, 0)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in counts:
                    counts[ev.name] += 1
    return counts


def _quadratic(P=8, H=3):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    A = jax.random.normal(k1, (P, P))
    A = A @ A.T / P + jnp.eye(P)
    B = jax.random.normal(k2, (P, H))
    t = jax.random.normal(k3, (P,))

    def inner(prm, hp, batch):
        th = prm['theta']
        return 0.5 * th @ A @ th - th @ (B @ hp['phi'])

    def outer(prm, hp, batch):
        return 0.5 * jnp.sum((prm['theta'] - t) ** 2)

    def smap(hp, batch):
        return {'theta': jnp.linalg.solve(A, B @ hp['phi'])}

    return inner, outer, smap, {'phi': jnp.ones((H,))}


@pytest.mark.parametrize('forward_mode', [True, False])
def test_hypergradient_scopes_reach_the_lowered_program(forward_mode):
    """The trainer's outer step in small: a sketch refreshed under the
    policy's ``lax.cond``, then the hypergradient through the sketch."""
    inner, outer, smap, phi0 = _quadratic()
    solver = NystromIHVP(k=4, rho=1e-3)
    policy = SketchPolicy(solver=solver, inner_loss=inner, refresh_every=2)
    solve = implicit_root(smap, inner, solver, forward_mode=forward_mode)
    theta0 = smap(phi0, None)
    state0 = policy.init_state(theta0, phi0, None, jax.random.PRNGKey(1))

    def hypergradient(phi, state, key):
        state, _ = policy.refresh(state, smap(phi, None), phi, None, key)
        return jax.grad(
            lambda p: outer(solve(p, None, state=state.sketch), p, None))(phi)

    text = jax.jit(hypergradient).lower(
        phi0, state0, jax.random.PRNGKey(2)).as_text(debug_info=True)
    for scope in SCOPES:
        assert f'/{scope}/' in text or f'({scope})' in text, scope


def test_prefetcher_spans(tmp_path):
    n = 5
    with jax.profiler.trace(str(tmp_path)):
        items = list(Prefetcher(iter(range(n)), depth=2))
    assert items == list(range(n))
    # one span more than items each: the draw that finds the iterator
    # spent, and the wait that receives the end of the stream
    assert _span_counts(tmp_path, ('data.produce', 'data.wait')) == {
        'data.produce': n + 1, 'data.wait': n + 1}


def test_train_lm_step_and_outer_batch_spans(tmp_path):
    from repro.configs import get_config
    from repro.launch.train import build_parser, train_lm
    steps = 3
    args = build_parser().parse_args([
        '--steps', str(steps), '--batch', '2', '--seq', '16', '--outer-every', '2',
        '--k', '2', '--log-every', '0'])
    with jax.profiler.trace(str(tmp_path)):
        run = train_lm(get_config('yi_9b').reduced(), args)
    assert len(run.losses) == steps and len(run.outer) == 1
    assert _span_counts(tmp_path, ('train', 'train.outer_batch')) == {
        'train': steps, 'train.outer_batch': 1}

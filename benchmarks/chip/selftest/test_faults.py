"""A run's check against the reference, driven off the chip at a size a test
run holds, with the timed path broken underneath: each fault a training cell
can have has to turn `correct` false, and the sound run has to stay true.

The faults are planted in the inner step and in the outer (hypergradient)
step. The device check is skipped (`require_tpu=False`); everything after it runs:
the sizing call, the measured call of `train_lm` with its seeded resume, the
reference and the comparison, under the cell's own limits. The trainer runs
its float32 matmul path here: at these widths its bfloat16 path is further
from the reference than at the cell's (a CPU-size effect), and the faults
must show against a sound run that passes.
"""
import json

import jax.numpy as jnp
import pytest

import run_cell
import tiny

WORKLOAD = 'yi-9b.reweight.fresh'
SEED = 2**31 + 77


def _run(capsys, cell):
    assert run_cell.main(['--workload', WORKLOAD, '--seed', str(SEED), '--seconds', '1'],
                         require_tpu=False, cell=cell) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _unchanged_step(monkeypatch):
    """The inner step returns its parameters and optimizer state unchanged."""
    import repro.launch.train as train
    make = train.make_optimizer

    def frozen(cfg):
        opt = make(cfg)
        return type(opt)(opt.init, lambda g, s, p, step: (
            __import__('jax').tree.map(jnp.zeros_like, p), s))
    monkeypatch.setattr(train, 'make_optimizer', frozen)


def _half_batch(monkeypatch):
    """The inner loss is the mean over the first half of the rows."""
    import jax
    import repro.launch.train as train
    build = train.build_losses

    def halved(cfg):
        inner, outer = build(cfg)
        return (lambda p, h, b: inner(p, h, jax.tree.map(lambda a: a[:a.shape[0] // 2], b)),
                outer)
    monkeypatch.setattr(train, 'build_losses', halved)


def _altered_answer(monkeypatch):
    """The step's answer altered where it is produced: the update AdamW
    returns for the largest leaf is doubled."""
    import jax
    import repro.launch.train as train
    make = train.make_optimizer

    def altered(cfg):
        opt = make(cfg)

        def update(g, s, p, step):
            upd, s = opt.update(g, s, p, step)
            leaves, treedef = jax.tree.flatten(upd)
            i = max(range(len(leaves)), key=lambda j: leaves[j].size)
            leaves[i] = 2 * leaves[i]
            return treedef.unflatten(leaves), s
        return type(opt)(opt.init, update)
    monkeypatch.setattr(train, 'make_optimizer', altered)


def _outer_adam(monkeypatch, alter):
    """The outer optimizer (Adam on the domain logits) with `alter` applied
    to (hypergradient, update) before the logits take the update."""
    import repro.launch.train as train
    make = train.adam

    def altered(*a, **kw):
        opt = make(*a, **kw)

        def update(g, s, p, step):
            g = alter['grad'](g) if 'grad' in alter else g
            upd, s2 = opt.update(g, s, p, step)
            return alter['update'](upd, s, s2) if 'update' in alter else (upd, s2)
        return type(opt)(opt.init, update)
    monkeypatch.setattr(train, 'adam', altered)


def _outer_unchanged(monkeypatch):
    """The outer step returns the domain logits and Adam's state unchanged."""
    import jax
    _outer_adam(monkeypatch, {'update': lambda upd, s, s2: (
        jax.tree.map(jnp.zeros_like, upd), s)})


def _reuse_unchanged(monkeypatch):
    """The first outer step that reuses the sketch returns the domain logits
    and Adam's state unchanged (the first outer step, from zero moments, is
    sound)."""
    import jax

    def skip_after_first(upd, s, s2):
        first = jnp.all(s.mu['domain_logits'] == 0)
        pick = lambda a, b: jax.tree.map(lambda x, y: jnp.where(first, x, y), a, b)  # noqa: E731
        return pick(upd, jax.tree.map(jnp.zeros_like, upd)), pick(s2, s)
    _outer_adam(monkeypatch, {'update': skip_after_first})


def _outer_answer(monkeypatch):
    """The hypergradient altered where it is produced: its largest entry
    negated before the outer optimizer takes it."""
    def negate(g):
        x = g['domain_logits']
        i = jnp.argmax(jnp.abs(x))
        return {'domain_logits': x.at[i].set(-x[i])}
    _outer_adam(monkeypatch, {'grad': negate})


def _mixed_half_batch(monkeypatch):
    """The hypergradient's mixed term (the VJP through the inner loss's
    gradient) is taken over the first half of the inner batch."""
    import jax
    import repro.launch.train as train
    root = train.implicit_root

    def halved(fn, inner_loss, solver, **kw):
        return root(fn, lambda p, h, b: inner_loss(
            p, h, jax.tree.map(lambda a: a[:a.shape[0] // 2], b)), solver, **kw)
    monkeypatch.setattr(train, 'implicit_root', halved)


@pytest.fixture(scope='module')
def cell():
    c = tiny.tiny_cell(WORKLOAD)
    c.config['program']['model_config']['compute_dtype'] = 'float32'
    return c


def test_sound_run_is_correct(capsys, cell):
    assert _run(capsys, cell)['correct'] is True


@pytest.mark.parametrize('fault', [_unchanged_step, _half_batch, _altered_answer,
                                   _outer_unchanged, _outer_answer, _mixed_half_batch])
def test_fault_turns_correct_false(capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    out = _run(capsys, cell)
    assert out['correct'] is False, out['checks']


def test_reuse_step_unchanged_turns_correct_false(capsys, monkeypatch):
    amortized = tiny.tiny_cell('yi-9b.reweight.amortized')
    amortized.config['program']['model_config']['compute_dtype'] = 'float32'
    _reuse_unchanged(monkeypatch)
    assert run_cell.main(['--workload', amortized.name, '--seed', str(SEED), '--seconds', '1'],
                         require_tpu=False, cell=amortized) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out['correct'] is False, out['checks']
    assert out['checks']['dphi2']['value'] > out['checks']['dphi2']['limit']

"""The readers of the program's own spans and scopes (`scopes.py`): on
synthetic events, and on a trace recorded on the chip: a `--trace 1` window
of `yi-9b.reweight.amortized` (TPU v5 lite) cut to one outer step that
builds the sketch, the ten inner steps after it and the outer step that
reuses it. It keeps the device's operations with their name stacks and the
program's own host spans, no Python-tracer events; the numbers below were
read from it once and are fixed."""
from pathlib import Path

import pytest

import harness
import scopes
import traces

HOST, DEV = '/host:CPU', '/device:TPU:0'
OUTER = 'jit(outer_step)'
FIXTURE = (Path(__file__).resolve().parents[1] / 'testdata'
           / 'yi-9b.reweight.amortized.scoped.events.json.gz')


def _op(start, dur, stack, name='%x = f32[] add(a, b)'):
    return (DEV, traces.OP_LINE, name, start, dur, stack)


def _module(name, start, dur):
    return (DEV, traces.MODULE_LINE, name, start, dur, '')


def test_scope_of_reads_through_transformations():
    assert scopes.scope_of(f'{OUTER}/transpose(jvp(mixed_vjp))/jvp(transpose(jvp()))/mul') \
        == 'mixed_vjp'
    assert scopes.scope_of(f'{OUTER}/cond/branch_1_fun/column_draw/jit(_shuffle)/sort') \
        == 'column_draw'
    assert scopes.scope_of(f'{OUTER}/transpose(jvp(ihvp_apply))/jit(solve)/dot') == 'ihvp_apply'
    # the innermost scope wins; a scope's name inside another word does not count
    assert scopes.scope_of(f'{OUTER}/mixed_vjp/sketch_hvps/add') == 'sketch_hvps'
    assert scopes.scope_of(f'{OUTER}/my_column_draw_x/add') is None
    assert scopes.scope_of('jit(inner_step)/add') is None
    assert scopes.scope_of('') is None


def test_self_time_goes_to_the_innermost_scope_per_outer_step():
    events = [
        _module('jit_outer_step(1)', 0, 100),
        # a conditional holds the sketch build: its own time is unscoped
        _op(0, 60, f'{OUTER}/cond', '%conditional = f32[] conditional(p)'),
        _op(5, 30, f'{OUTER}/cond/branch_1_fun/column_draw/sort'),
        _op(35, 20, f'{OUTER}/cond/branch_1_fun/sketch_hvps/vmap(jvp(jvp()))/dot'),
        # a fusion under one scope that holds an op under another
        _op(60, 20, f'{OUTER}/transpose(jvp(ihvp_apply))/dot'),
        _op(65, 5, f'{OUTER}/transpose(jvp(mixed_vjp))/mul'),
        _op(80, 10, f'{OUTER}/outer_adam/add'),
        # a second outer step, reusing the sketch
        _module('jit_outer_step(1)', 200, 40),
        _op(200, 12, f'{OUTER}/transpose(jvp(ihvp_apply))/dot'),
        _op(212, 18, f'{OUTER}/transpose(jvp(mixed_vjp))/mul'),
    ]
    got = scopes.scope_ms(events, 0, 300)
    ns = {'column_draw': 30, 'sketch_hvps': 20, 'ihvp_apply': 15 + 12, 'mixed_vjp': 5 + 18}
    assert got == pytest.approx({k: v / 2 / 1e6 for k, v in ns.items()})


def test_scope_time_is_cut_to_the_window():
    events = [_module('jit_outer_step(1)', 10, 100),
              _op(10, 100, f'{OUTER}/cond/branch_1_fun/column_draw/sort')]
    assert scopes.scope_ms(events, 0, 60) == pytest.approx({'column_draw': 50e-6})
    # no outer step began in the window: nothing to divide by
    assert scopes.scope_ms(events, 20, 60) == {}


def test_a_program_without_scopes_reads_none():
    events = [_module('jit_outer_step(1)', 0, 100), _op(0, 100, f'{OUTER}/sort')]
    assert scopes.scope_ms(events, 0, 100) == {}


def _trace(host_spans, ops):
    events = [(HOST, 'bench', traces.WINDOW, 0, 1000),
              (DEV, traces.MODULE_LINE, 'jit_inner_step(1)', 0, 1000)]
    events += [(DEV, traces.OP_LINE, '%x = f32[] add(a, b)', a, b - a) for a, b in ops]
    events += [(HOST, 'python', name, a, b - a) for name, a, b in host_spans]
    return traces.Trace(events)


def _read(name, trace):
    return harness.read_metric(name, harness.Context(trace, 0.0, {}, chips=1))


def test_input_idle_share_counts_idle_time_under_input_spans():
    # busy [0, 400) and [600, 1000): an idle gap of 200 ns, half of it under
    # a data.wait span that starts while the device is still busy
    trace = _trace([('data.wait', 300, 500), ('$queue.py:180 get', 300, 500)],
                   [(0, 400), (600, 1000)])
    assert scopes.input_idle_ns(trace) == 100
    assert _read('loop.input_idle_share', trace) == pytest.approx(10.0)
    assert _read('device.idle_share', trace) == pytest.approx(20.0)


def test_input_idle_share_unions_overlapping_spans():
    trace = _trace([('data.wait', 400, 500), ('train.outer_batch', 450, 700)],
                   [(0, 400), (600, 1000)])
    assert scopes.input_idle_ns(trace) == 200


def test_host_readers_without_spans_read_none():
    trace = _trace([('$synthetic.py:192 batch', 400, 600)], [(0, 400), (600, 1000)])
    assert _read('loop.input_idle_share', trace) is None
    assert _read('data.produce_host_ms', trace) is None


def test_data_produce_mean_of_spans_starting_in_the_window():
    trace = _trace([('data.produce', -50, 20), ('data.produce', 100, 130),
                    ('data.produce', 500, 550), ('data.produce', 990, 1100)],
                   [(0, 1000)])
    # the span that began before the window is left out
    assert _read('data.produce_host_ms', trace) == pytest.approx((30 + 50 + 110) / 3 / 1e6)


@pytest.fixture(scope='module')
def recorded():
    return traces.read_events(str(FIXTURE))


def test_recorded_scopes_per_outer_step(recorded, monkeypatch):
    device = [e for e in recorded if e[0].startswith(traces.DEVICE_PREFIX)]
    trace = traces.Trace(recorded)
    assert trace.window_ns == 4_036_550_572
    assert trace.module_durations('jit_outer_step') == [3_494_763_229, 93_635_642]
    assert len(trace.module_durations('jit_inner_step')) == 10
    want = {'column_draw': 1650.176134, 'sketch_hvps': 38.406111,
            'ihvp_apply': 49.6480375, 'mixed_vjp': 10.7878445}
    assert scopes.scope_ms(device, trace.t0, trace.t1) == pytest.approx(want, abs=1e-6)
    # the readers, as the harness calls them, over this run's scoped events
    monkeypatch.setattr(scopes, 'run_events', lambda: device)
    for scope, ms in want.items():
        assert _read(f'{scope}.device_ms', trace) == pytest.approx(ms, abs=1e-6)
    # the four scopes hold all but 2.5% of the two outer steps' device time
    assert sum(want.values()) * 2 / ((3_494_763_229 + 93_635_642) / 1e6) == pytest.approx(
        0.9748, abs=1e-4)


def test_recorded_input_spans(recorded):
    trace = traces.Trace(recorded)
    assert scopes.input_idle_ns(trace) == 222_523_937
    assert _read('loop.input_idle_share', trace) == pytest.approx(
        100 * 222_523_937 / 4_036_550_572, abs=1e-9)
    assert _read('data.produce_host_ms', trace) == pytest.approx(629.885586 / 11, abs=1e-6)

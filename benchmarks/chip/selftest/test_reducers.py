"""The reduction from a profiler trace to the per-layer metrics, on a trace
recorded on the chip: the first 0.7 s of a `--trace 1` window of
`yi-9b.reweight.fresh` (TPU v5 lite), cut with `traces.clip_events`. It holds
ten inner steps and the start of an outer step; the numbers below were read
from it once and are fixed."""
from pathlib import Path

import pytest

import harness
import traces

FIXTURE = Path(__file__).resolve().parents[1] / 'testdata' / 'yi-9b.reweight.fresh.events.json.gz'
PEAK = {'bf16_flops_per_s': 197e12}


@pytest.fixture(scope='module')
def trace():
    return traces.Trace(traces.read_events(str(FIXTURE)))


def _read(name, trace, window_flops=0.0):
    return harness.read_metric(name, harness.Context(trace, window_flops, PEAK, chips=1))


def test_window_and_busy(trace):
    assert trace.devices == ['/device:TPU:0']
    assert trace.window_ns == 700_000_000
    assert trace.busy_ns == 316_253_835


def test_step_device_times(trace):
    assert len(trace.module_durations('jit_inner_step')) == 10
    assert _read('inner_step.device_ms', trace) == pytest.approx(19.421775, abs=1e-6)
    assert _read('outer_step.device_ms', trace) == pytest.approx(3494.965067, abs=1e-6)


def test_idle_share(trace):
    assert _read('device.idle_share', trace) == pytest.approx(
        100 * (1 - 316_253_835 / 700_000_000), abs=1e-9)


def test_mfu(trace):
    # 1.379e13 FLOPs in 0.7 s on one 197 TFLOP/s chip is 10%
    assert _read('train.mfu', trace, window_flops=0.1 * 0.7 * 197e12) == pytest.approx(10.0)


def test_breakdown(trace):
    b = trace.breakdown()
    assert b['device_ops'][0] == ['jit_outer_step/sort sort', 0.108265805]
    assert b['device_ops'][1] == ['jit_inner_step/subtract_add_fusion.1 fusion', 0.01767455]
    assert b['idle_gaps'][0] == ['$<unknown> acquire', 0.145157833]
    assert b['idle_gaps'][1] == ['$synthetic.py:192 batch', 0.051130622]
    assert len(b['device_ops']) == len(b['idle_gaps']) == 10


def test_self_time_removes_nested_ops():
    host, dev = '/host:CPU', '/device:TPU:0'
    events = [(host, 'bench', traces.WINDOW, 0, 100),
              (dev, traces.MODULE_LINE, 'jit_outer_step(1)', 0, 90),
              (dev, traces.OP_LINE, '%conditional = f32[] conditional(x)', 0, 80),
              (dev, traces.OP_LINE, '%sort.1 = (u32[4]{0:T(1024)}, s32[4]) sort(a, b)', 10, 50)]
    t = traces.Trace(events)
    assert t.busy_ns == 80
    assert dict(t.breakdown()['device_ops']) == {
        'jit_outer_step/conditional conditional': 30e-9, 'jit_outer_step/sort.1 sort': 50e-9}

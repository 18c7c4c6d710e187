"""The control: the reference in float8 matmuls put in the program's place
fails the cell's limits, at a size a test run holds (the readings at the
cells' own sizes, on the chip, are in PERF.md). The float32 reference against
itself passes them."""
import jax
import pytest

import harness
import tiny


@pytest.mark.parametrize('workload', ['yi-9b.reweight.fresh', 'yi-9b.reweight.amortized'])
def test_control_fails_limits(workload):
    from repro.models import build_model
    cell = tiny.tiny_cell(workload)
    cfg = harness.program_config(cell.config)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    seed = 2**31 + 5
    ref = harness.reference_record(cell, seed, shapes)
    assert harness.judge(harness.compare(ref, ref), cell.limits)
    ctl = harness.reference_record(cell, seed, shapes, prec='fp8')
    numbers = harness.compare(ctl, ref)
    assert not harness.judge(numbers, cell.limits), numbers

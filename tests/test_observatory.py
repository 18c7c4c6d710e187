"""Observatory contract tests: error monotonicity, HVP bills, schema, filters.

One toy sweep (module-scoped: logreg at D=8, every registered solver, a
k-ladder at fixed damping, oracle damped identically) backs the accuracy
contracts; the parsing/filter tests are pure. The monotone-error contract
is the scientific core: more sketch columns / more iterations must not make
the hypergradient *worse* against the exact-IHVP oracle — if it does, a
solver regression slipped into the apply path.
"""
import json

import pytest

from benchmarks.check_bench_schema import check_file
from benchmarks.common import bench_row, write_bench
from repro.bench import (build_population, parse_grid, parse_problem_spec,
                         parse_vary, run_sweep, solver_grid_points)
from repro.bench.observatory import measure_cell

SPEC = 'logreg_wd:D=8:n=60'
RHO = 1e-2
KS = (2, 4, 8)


@pytest.fixture(scope='module')
def cells():
    # errors averaged over 8 column draws: over 2 the order of the k = 2
    # and k = 4 rungs is the draw's luck, not the solver's
    return run_sweep((SPEC,), ('nystrom', 'cg', 'neumann', 'exact'),
                     {'k': KS, 'rho': (RHO,)}, tasks=8, oracle_rho=RHO,
                     reps=1, seed=0)


def _errs(cells, solver):
    by_k = {c.grid['k']: c.hypergrad_error for c in cells
            if c.solver == solver}
    return [by_k[k] for k in KS]


class TestErrorContract:
    def test_nystrom_error_nonincreasing_in_k(self, cells):
        errs = _errs(cells, 'nystrom')
        # 5% slack + absolute floor: the sketch draws different columns per
        # k, so adjacent rungs may tie — but more rank must never hurt
        for lo, hi in zip(errs[1:], errs[:-1]):
            assert lo <= hi * 1.05 + 1e-6, errs
        assert errs[-1] < errs[0], errs

    def test_cg_error_nonincreasing_in_iters(self, cells):
        errs = _errs(cells, 'cg')
        for lo, hi in zip(errs[1:], errs[:-1]):
            assert lo <= hi * 1.05 + 1e-6, errs
        assert errs[-1] < errs[0] * 1e-2, errs     # CG converges fast at p=8

    def test_full_rank_nystrom_matches_oracle(self, cells):
        # k = p = 8: the sketch spans the whole space, so the only residual
        # is roundoff (the oracle uses the same rho)
        assert _errs(cells, 'nystrom')[-1] < 1e-4

    def test_exact_solver_matches_oracle_exactly(self, cells):
        (cell,) = [c for c in cells if c.solver == 'exact']
        assert cell.hypergrad_error < 1e-6
        assert cell.err_max < 1e-6


class TestHvpBill:
    """Per-cell hvp_count is the analytic per-hypergradient bill."""

    def test_nystrom_bills_k(self, cells):
        for c in cells:
            if c.solver == 'nystrom':
                assert c.hvp_count == c.grid['k']

    def test_iterative_solvers_bill_their_iterations(self, cells):
        for c in cells:
            if c.solver in ('cg', 'neumann'):
                assert c.hvp_count == c.grid['k']

    def test_exact_bills_p(self, cells):
        (cell,) = [c for c in cells if c.solver == 'exact']
        assert cell.hvp_count == 8                  # p = D for logreg_wd


class TestPersistence:
    def test_cells_round_trip_through_schema_check(self, cells, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv('BENCH_OUT_DIR', str(tmp_path))
        rows = [bench_row(solver=c.solver, backend='tree', m=1,
                          applies_per_sec=c.applies_per_sec,
                          wall_seconds=c.wall_seconds, problem=c.problem,
                          hvp_count=c.hvp_count,
                          hypergrad_error=c.hypergrad_error, grid=c.grid,
                          err_max=c.err_max, tasks=c.tasks)
                for c in cells]
        path = write_bench('observatory_test', rows)
        assert check_file(path) == []
        doc = json.loads(open(path).read())
        assert doc['schema_version'] == 2
        assert all(r['problem'] == SPEC for r in doc['rows'])


class TestFiltersAndParsing:
    def test_solver_filter_selects_exactly_named_entries(self, cells):
        assert {c.solver for c in cells} == {'nystrom', 'cg', 'neumann',
                                            'exact'}
        only = run_sweep((SPEC,), ('cg',), {'k': (2,), 'rho': (RHO,)},
                         tasks=1, oracle_rho=RHO, reps=1)
        assert [c.solver for c in only] == ['cg']

    def test_unknown_solver_raises_before_measurement(self):
        with pytest.raises(ValueError, match="unknown solver 'sgd'"):
            run_sweep((SPEC,), ('sgd',), {'k': (2,)}, tasks=1)

    def test_unknown_problem_raises_with_registry(self):
        with pytest.raises(ValueError, match='unknown problem'):
            run_sweep(('not_a_problem',), ('cg',), {'k': (2,)}, tasks=1)

    def test_grid_points_follow_solver_spec_fields(self):
        grid = {'k': (2, 4), 'rho': (0.01, 0.1), 'alpha': (0.1,)}
        assert solver_grid_points('exact', grid) == [{'rho': 0.01},
                                                     {'rho': 0.1}]
        assert solver_grid_points('neumann', grid) == [
            {'k': 2, 'alpha': 0.1}, {'k': 4, 'alpha': 0.1}]
        assert len(solver_grid_points('nystrom', grid)) == 4
        assert solver_grid_points('cg', {}) == [{}]

    def test_parse_problem_spec(self):
        assert parse_problem_spec('reweighting:d=8:width=16') == (
            'reweighting', {'d': 8, 'width': 16})
        assert parse_problem_spec('imaml') == ('imaml', {})
        with pytest.raises(ValueError, match='bad problem spec'):
            parse_problem_spec('logreg_wd:D8')

    def test_parse_grid_and_vary(self):
        assert parse_grid('k=2:4,rho=0.01') == {'k': (2, 4), 'rho': (0.01,)}
        assert parse_vary('imbalance=10,100') == ('imbalance', (10, 100))
        with pytest.raises(ValueError, match='bad grid axis'):
            parse_grid('k')


class TestSolveIntegration:
    """solve() exposes the same oracle scoring on its solved state."""

    def test_solve_records_hypergrad_error_when_requested(self):
        from repro.core import HypergradConfig, get_problem, solve
        problem = get_problem('logreg_wd', D=8, n=60)
        cfg = HypergradConfig(solver='cg', k=8, rho=RHO)
        res = solve(problem, cfg, n_outer=1, steps_per_outer=5)
        assert res.hypergrad_error is None
        res = solve(problem, cfg, n_outer=1, steps_per_outer=5,
                    with_hypergrad_error=True, oracle_rho=RHO)
        assert res.hypergrad_error is not None
        assert 0.0 <= res.hypergrad_error < 1e-3   # CG at l=p converges

    def test_solve_rejects_error_scoring_on_meta_path(self):
        from repro.core import get_problem, solve
        problem = get_problem('imaml', image_size=8, width=8)
        with pytest.raises(ValueError, match='vmap_tasks'):
            solve(problem, None, n_outer=1, vmap_tasks=2,
                  with_hypergrad_error=True)


class TestBackendAxis:
    """--backends fans out per-backend cells, but only for solvers that
    actually build a contraction backend (Nyström); the others have no
    backend dial and must appear exactly once, tagged 'tree'."""

    @pytest.fixture(scope='class')
    def backend_cells(self):
        return run_sweep((SPEC,), ('nystrom', 'cg'),
                         {'k': (4,), 'rho': (RHO,)}, tasks=2,
                         oracle_rho=RHO, reps=1, seed=0,
                         backends=('tree', 'flat'))

    def test_backend_fanout_only_for_backend_building_solvers(
            self, backend_cells):
        nystrom = sorted(c.backend for c in backend_cells
                         if c.solver == 'nystrom')
        assert nystrom == ['flat', 'tree']
        cg = [c.backend for c in backend_cells if c.solver == 'cg']
        assert cg == ['tree']           # no dial → one cell, tree-tagged

    def test_backends_agree_on_error_and_bill(self, backend_cells):
        tree, flat = [c for c in backend_cells if c.solver == 'nystrom']
        if tree.backend != 'tree':
            tree, flat = flat, tree
        # same sketch math, different operand layout: identical analytic
        # bill, errors equal to layout roundoff
        assert tree.hvp_count == flat.hvp_count == 4
        assert flat.hypergrad_error == pytest.approx(
            tree.hypergrad_error, rel=1e-3, abs=1e-6)

    def test_measure_cell_records_requested_backend(self):
        bundle = build_population(SPEC, tasks=1)
        cell = measure_cell(bundle, 'nystrom', {'k': 2, 'rho': RHO},
                            backend='flat', reps=1)
        assert cell.backend == 'flat'
        # backend-less solver: the tag is recorded but nothing is routed
        cell = measure_cell(bundle, 'cg', {'k': 2, 'rho': RHO}, reps=1)
        assert cell.backend == 'tree'


class TestAuditFields:
    """--audit: each cell's timed program audited via repro.analysis."""

    def test_audit_off_leaves_fields_none(self):
        bundle = build_population(SPEC, tasks=1)
        cell = measure_cell(bundle, 'cg', {'k': 2, 'rho': RHO}, reps=1)
        assert cell.collective_count is None
        assert cell.accum_dtype_ok is None

    def test_audit_fills_structure_fields(self):
        bundle = build_population(SPEC, tasks=1)
        cell = measure_cell(bundle, 'nystrom', {'k': 2, 'rho': RHO},
                            reps=1, audit=True)
        # single-device, f32 throughout: no collectives, clean accumulation
        assert cell.collective_count == 0
        assert cell.accum_dtype_ok is True

    def test_audited_rows_round_trip_through_schema_check(self, tmp_path,
                                                          monkeypatch):
        monkeypatch.setenv('BENCH_OUT_DIR', str(tmp_path))
        bundle = build_population(SPEC, tasks=1)
        cell = measure_cell(bundle, 'cg', {'k': 2, 'rho': RHO}, reps=1,
                            audit=True)
        rows = [bench_row(solver=cell.solver, backend=cell.backend, m=1,
                          applies_per_sec=cell.applies_per_sec,
                          wall_seconds=cell.wall_seconds,
                          problem=cell.problem, hvp_count=cell.hvp_count,
                          collective_count=cell.collective_count,
                          accum_dtype_ok=cell.accum_dtype_ok)]
        path = write_bench('observatory_audit_test', rows)
        assert check_file(path) == []
        # the checker types the optional fields, not just presence
        doc = json.loads(open(path).read())
        doc['rows'][0]['accum_dtype_ok'] = 'yes'
        bad = tmp_path / 'BENCH_bad.json'
        bad.write_text(json.dumps(doc))
        assert any('accum_dtype_ok' in e for e in check_file(str(bad)))


class TestPopulation:
    def test_oracle_guard_refuses_large_p(self):
        with pytest.raises(ValueError, match='max_oracle_p'):
            build_population(SPEC, tasks=1, max_oracle_p=4)

    def test_vary_axis_sets_population(self):
        bundle = build_population('reweighting:d=8:width=16', tasks=1,
                                  vary=('imbalance', (10, 100)),
                                  batch_size=16, steps=3)
        assert bundle.tasks == 2
        cell = measure_cell(bundle, 'cg', {'k': 2, 'rho': RHO}, reps=1)
        assert cell.tasks == 2 and cell.hvp_count == 2

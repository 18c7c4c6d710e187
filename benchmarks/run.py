"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (harness contract). Modules:
  fig1_inverse_quality  — Fig. 1
  fig2_logreg_hpo       — Figs. 2/3 (+ ρ robustness sweep)
  tab2_distillation     — Tab. 2
  tab3_imaml            — Tab. 3
  tab4_reweighting      — Tab. 4
  tab5_speed_memory     — Tab. 5
  tab6_robustness       — Tab. 6 / Fig. 4
  bench_influence       — influence-service queries/sec vs m
  observatory           — solver × problem × accuracy-knob complexity sweep
  roofline              — EXPERIMENTS.md §Roofline source (dry-run artifacts)

FAST=1 env shrinks horizons for CI smoke. The apply/influence benches also
persist machine-readable BENCH_*.json rows (benchmarks/common.py schema;
benchmarks/check_bench_schema.py validates them in CI).
"""
import os
import sys
import time
import traceback


def _observatory(fast: bool = False) -> None:
    """The solver observatory sweep at orchestrator scale: every solver over
    the toy problem set (shrunk to a logreg 2×2 micro-sweep under FAST)."""
    from benchmarks import observatory
    argv = ['--oracle-rho', '0.01']
    if fast:
        argv += ['--problems', 'logreg_wd:D=8:n=60',
                 '--grid', 'k=2:5,rho=0.01', '--tasks', '2']
    observatory.main(argv)


def main() -> None:
    fast = bool(int(os.environ.get('FAST', '0')))
    from benchmarks import (bench_influence, fig1_inverse_quality,
                            fig2_logreg_hpo, roofline, tab2_distillation,
                            tab3_imaml, tab4_reweighting, tab5_speed_memory,
                            tab6_robustness)
    jobs = [
        ('fig1', fig1_inverse_quality.run, {}),
        ('fig2', fig2_logreg_hpo.run, {'n_outer': 4 if fast else 12}),
        ('fig3', fig2_logreg_hpo.run_rho_sweep, {'n_outer': 2 if fast else 8}),
        ('tab2', tab2_distillation.run, {'n_outer': 3 if fast else 25}),
        ('tab3', tab3_imaml.run, {'n_episodes': 10 if fast else 60,
                                  'n_eval': 5 if fast else 20}),
        ('tab4', tab4_reweighting.run,
         {'imbalances': (100,) if fast else (200, 100, 50),
          'n_outer': 5 if fast else 30}),
        ('tab5', tab5_speed_memory.run,
         {'sizes': (5,) if fast else (5, 10, 20)}),
        ('tab6', tab6_robustness.run, {'n_outer': 3 if fast else 15}),
        ('influence', bench_influence.run,
         {'m_values': (1, 4) if fast else (1, 8, 32),
          'k': 4 if fast else 16,
          'train_steps': 10 if fast else 100}),
        ('observatory', _observatory, {'fast': fast}),
        ('roofline', roofline.run, {}),
    ]
    t00 = time.time()
    failed = []
    for name, fn, kw in jobs:
        t0 = time.time()
        try:
            fn(**kw)
            print(f'# {name} done in {time.time()-t0:.1f}s', flush=True)
        except Exception as e:
            # keep running the other jobs; the exit code reports this one
            traceback.print_exc()
            print(f'{name},0.0,ERROR {type(e).__name__}: {e}', flush=True)
            failed.append(name)
    print(f'# total {time.time()-t00:.1f}s')
    if failed:
        sys.exit(f'# failed jobs: {", ".join(failed)}')


if __name__ == '__main__':
    main()

"""Run one benchmark cell once on the chip and print its result.

    python3 benchmarks/chip/run_cell.py --workload yi-9b.reweight.fresh \
        --seed 1234 --seconds 30 --trace 0

Run from the root of a checkout; `BENCHMARK.json` there names the cells.
With `--trace 0` the result's metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of a shorter
window. The last line of standard output is the result, one JSON object; the
last lines of standard error are the numbers the check compared, each beside
its limit. Without a TPU, or with fewer chips than the cell asks for, the
run exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / 'src')]
CACHE_DIR = ROOT / '.jax_cache'         # the trainer's own default, inside the checkout
TRACE_DIR = BENCH / '.trace'


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(cell, res, devices, correct, limits) -> dict:
    import harness
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices),
              'memory_peak_bytes': harness.peak_bytes(res['memory'])}
    out = {'correct': correct, 'attempted': res['attempted'], 'failed': res['failed'],
           'metrics': {k: {'value': v, 'unit': u} for k, (v, u) in res['metrics'].items()},
           'device': device}
    if 'busy_s' in res:
        device['busy_s'] = res['busy_s']
        device['window_s'] = res['trace_window_s']
        out['breakdown'] = res['breakdown']
    out['checks'] = {k: {'value': res['numbers'][k], 'limit': lim}
                     for k, lim in limits.items()}
    return out


def main(argv=None, require_tpu: bool = True, cell=None) -> int:
    args = parse(argv)
    if not (ROOT / 'src' / 'repro').is_dir():
        print(f'[bench] no system under test at {ROOT / "src" / "repro"}', file=sys.stderr)
        return 2
    import jax
    jax.config.update('jax_compilation_cache_dir', str(CACHE_DIR))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    import harness
    cell = cell or harness.load_cell(args.workload)
    devices = jax.devices()
    peaks = json.loads((BENCH / 'peaks.json').read_text())
    if require_tpu:
        if devices[0].platform != 'tpu' or len(devices) < cell.chips:
            print(f'[bench] needs {cell.chips} TPU chip(s); JAX found '
                  f'{len(devices)} {devices[0].platform} device(s)', file=sys.stderr)
            return 2
        if devices[0].device_kind not in peaks:
            print(f'[bench] no peaks for device kind {devices[0].device_kind!r} '
                  'in peaks.json', file=sys.stderr)
            return 2
        peak = peaks[devices[0].device_kind]
    else:
        peak = next(iter(peaks.values()))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace), T0, peak, TRACE_DIR)
    correct = res['failed'] == 0 and res['attempted'] > 0 and harness.judge(
        res['numbers'], cell.limits)
    print(f"[bench] memory_stats: {json.dumps(res['memory'])}", file=sys.stderr)
    print(f"[bench] window {res['window_s']:.3f} s, {res['n_cycles']} cycles of "
          f"{cell.cycle} steps (sized at {res['per_cycle_s']:.3f} s each), set-up "
          f"{res['setup_s']:.3f} s, reference {res['reference_s']:.3f} s", file=sys.stderr)
    for name, value in res['numbers'].items():
        if name not in cell.limits:
            print(f'[bench] reading {name} = {value:.6g} (not compared)', file=sys.stderr)
    print(f'[bench] correct = {correct}; the numbers compared:', file=sys.stderr)
    for name, lim in cell.limits.items():
        print(f'[bench] check {name} = {res["numbers"][name]:.6g} (limit {lim})',
              file=sys.stderr, flush=True)
    print(json.dumps(result_line(cell, res, devices, correct, cell.limits)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())

"""The readings the correctness limits are set from, on the chip, in one
process:

    python3 benchmarks/chip/readings.py --workload yi-9b.reweight.fresh \
        --seeds 11,12,13 --control-seeds 11,12,13 --fault-seeds 11,12,13 \
        --out readings.jsonl

`--files CONFIG:MIX` in place of `--workload` reads a configuration under
a mix that `BENCHMARK.json` does not pair (no limits).
For each of `--seeds` the trainer runs its warm steps from the seed (the
steps a run checks, without the window) and its numbers are compared with
the reference, as a run compares them; with `--free-sketch` also with a
reference that leaves the sketch out (u = grad g / rho), which shows how
much the sketch's k directions move the hypergradient. For each of
`--control-seeds` the reference in float8 matmuls stands in for the
program, and for each of `--fault-seeds` the reference with each planted
fault does. One JSON line per reading goes to `--out` and to standard
output.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / 'src')]


def collect(cell, seeds, control_seeds, fault_seeds, free_sketch, emit):
    import jax

    import harness
    import weights
    refs, shapes = {}, None
    for seed in seeds:
        t = time.perf_counter()
        obs = harness.Observer(cell, cell.start_step(seed), probe=True)
        lm, resume = harness.drive(cell, seed, cell.warm, obs)
        shapes = resume.param_shapes
        prog = harness.program_record(cell, seed, obs, lm, shapes)
        losses = list(lm.losses)
        del lm, obs
        gc.collect()
        refs[seed] = harness.reference_record(cell, seed, shapes)
        names = [weights.leaf_path(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]]
        emit(seed, 'program', harness.compare(prog, refs[seed]),
             diagnostics=harness.diagnostics(prog, refs[seed], names), losses=losses[:3],
             vectors={f'{key}_{side}': [x.tolist() for x in rec[key]]
                      for side, rec in (('program', prog), ('reference', refs[seed]))
                      for key in ('phi', 'hg')},
             sketch_eigenvalues=refs[seed].get('sketch_eigenvalues'),
             memory=harness.memory_stats(), seconds=time.perf_counter() - t)
        if free_sketch:
            free = harness.reference_record(cell, seed, shapes, sketch=False)
            emit(seed, 'program_vs_free_sketch', harness.compare(prog, free),
                 diagnostics=harness.diagnostics(prog, free))
            emit(seed, 'free_sketch_vs_reference', harness.compare(free, refs[seed]),
                 diagnostics=harness.diagnostics(free, refs[seed]))
    if shapes is None:
        raise SystemExit('--seeds gives the parameter shapes; name at least one')
    for seed in control_seeds:
        ref = refs.get(seed) or harness.reference_record(cell, seed, shapes)
        ctl = harness.reference_record(cell, seed, shapes, prec='fp8')
        emit(seed, 'control_fp8', harness.compare(ctl, ref),
             diagnostics=harness.diagnostics(ctl, ref))
    for seed in fault_seeds:
        ref = refs.get(seed) or harness.reference_record(cell, seed, shapes)
        for fault in ('half', 'half_outer', 'half_mixed', 'answer'):
            bad = harness.reference_record(cell, seed, shapes, fault=fault)
            emit(seed, f'fault_{fault}', harness.compare(bad, ref),
                 diagnostics=harness.diagnostics(bad, ref))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', help='a cell BENCHMARK.json names')
    ap.add_argument('--files', help='CONFIG:MIX, a pair from their files alone')
    ap.add_argument('--seeds', default='')
    ap.add_argument('--control-seeds', default='')
    ap.add_argument('--fault-seeds', default='')
    ap.add_argument('--free-sketch', action='store_true')
    ap.add_argument('--out', required=True)
    a = ap.parse_args()
    import jax
    jax.config.update('jax_compilation_cache_dir', str(ROOT / '.jax_cache'))
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    import harness
    cell = (harness.load_cell(a.workload) if a.workload
            else harness.file_cell(*a.files.split(':')))
    ints = lambda s: [int(x) for x in s.split(',') if x]  # noqa: E731
    with open(a.out, 'a') as out:
        def emit(seed, kind, numbers, **extra):
            line = json.dumps(dict(workload=cell.name, seed=seed, kind=kind,
                                   numbers=numbers, **extra))
            print(line, flush=True)
            out.write(line + '\n')
            out.flush()
        collect(cell, ints(a.seeds), ints(a.control_seeds), ints(a.fault_seeds),
                a.free_sketch, emit)


if __name__ == '__main__':
    main()

#!/usr/bin/env python
"""Cost of the trainer's profiler spans, per enter and exit.

    python tools/span_cost.py [--n 200000] [--n-on 20000]

Times `n` enters and exits of a `jax.profiler.TraceAnnotation` and of a
`StepTraceAnnotation` three ways: with no profiler active ("off"), under
`jax.profiler.trace` with its default options, the Python tracer on, as the
chip benchmark's traced runs start it ("on"), and under a trace with the
Python tracer off ("on_no_python"). Under the profiler it times fewer
(`--n-on`): the trace keeps every span and, with the Python tracer, every
call. Each figure holds the loop's own cost too, so it bounds the span's
from above. Prints one JSON object of microseconds per span;
docs/tracing.md gives the readings on a TPU v5e host.
"""
import argparse
import json
import tempfile
import time

import jax


def per_span_us(make, n: int) -> float:
    t = time.perf_counter()
    for i in range(n):
        with make(i):
            pass
    return (time.perf_counter() - t) / n * 1e6


def measure(n: int, n_on: int) -> dict:
    kinds = {'TraceAnnotation': lambda i: jax.profiler.TraceAnnotation('data.wait'),
             'StepTraceAnnotation': lambda i: jax.profiler.StepTraceAnnotation(
                 'train', step_num=i)}
    out = {}
    for name, make in kinds.items():
        per_span_us(make, n // 10)                  # warm the bindings
        out[f'{name}.off'] = per_span_us(make, n)
    no_python = jax.profiler.ProfileOptions()
    no_python.python_tracer_level = 0
    for label, options in (('on', None), ('on_no_python', no_python)):
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d, profiler_options=options):
                for name, make in kinds.items():
                    out[f'{name}.{label}'] = per_span_us(make, n_on)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--n', type=int, default=200_000)
    ap.add_argument('--n-on', type=int, default=20_000)
    args = ap.parse_args(argv)
    res = measure(args.n, args.n_on)
    res.update(device=jax.devices()[0].device_kind, n=args.n, n_on=args.n_on)
    print(json.dumps(res))
    return 0


if __name__ == '__main__':
    raise SystemExit(main())

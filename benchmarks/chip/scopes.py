"""The program's own spans and scopes in a traced run (docs/tracing.md).

Device scopes. The hypergradient step names its phases with
`jax.named_scope`: `column_draw`, `sketch_hvps`, `ihvp_apply`, `mixed_vjp`.
A device operation carries its name stack in the trace, as the `tf_op`
stat of its event's metadata (`PATH_STAT`), e.g.
`jit(outer_step)/transpose(jvp(mixed_vjp))/dot_general:`.
`traces.load_events` keeps no stats, so `load_scoped_events` reads the
run's `.xplane.pb` again: the harness empties `.trace/` before each run, so
the newest file there is this run's. `scope_ms` gives each scope's device
time per `jit_outer_step` execution in the window: every operation's self
time (less the operations nested in it, as `traces.Trace.self_times` cuts
it), given to the innermost of the four scopes on its name stack.

Host spans. `data.wait` (the trainer waits for its prefetch thread) and
`train.outer_batch` (it builds the outer batch) are where the loop holds
the device back for input; `input_idle_ns` is the device's idle time that
they overlap. `data.produce` is the prefetch thread making one batch.

Each reader returns None where the trace holds none of what it reads, as a
program without these spans and scopes gives.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict
from pathlib import Path

import traces
import xplane

SCOPES = ('column_draw', 'sketch_hvps', 'ihvp_apply', 'mixed_vjp')
OUTER = 'jit_outer_step'
INPUT_SPANS = ('data.wait', 'train.outer_batch')
TRACE_DIR = Path(__file__).resolve().parent / '.trace'
PATH_STAT = 'tf_op'


def load_scoped_events(path: str) -> list[tuple]:
    """The device planes' program executions and operations, as
    `(plane, line, name, start_ns, dur_ns, name_stack)`; an operation's name
    is its HLO name and opcode (`traces._op_name`), its name stack the
    `PATH_STAT` of its event's metadata, which `jax.profiler.ProfileData`
    does not expose (`xplane.py` reads it)."""
    out = []
    for plane in xplane.read_planes(path, lambda name: name.startswith(traces.DEVICE_PREFIX)):
        for line in (traces.MODULE_LINE, traces.OP_LINE):
            for mid, start, dur in plane.lines.get(line, ()):
                name, stats = plane.metadata.get(mid, ('', {}))
                if line == traces.OP_LINE:
                    out.append((plane.name, line, traces._op_name(name), start, dur,
                                str(stats.get(PATH_STAT, ''))))
                else:
                    out.append((plane.name, line, name, start, dur, ''))
    return out


@functools.lru_cache(maxsize=1)
def _events_of(path: str, mtime_ns: int) -> list[tuple]:
    return load_scoped_events(path)


def run_events(trace_dir: Path = TRACE_DIR) -> list[tuple] | None:
    """This run's scoped events, read once for the four readers, or None
    without a trace."""
    files = sorted(trace_dir.glob('**/*.xplane.pb'), key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    return _events_of(str(files[-1]), files[-1].stat().st_mtime_ns)


_WRAPPED = re.compile(r'(?:[\w.-]+\()*([\w.-]*)\)*')


def scope_of(stack: str) -> str | None:
    """The innermost of `SCOPES` among the name stack's components, each
    read through the transformations wrapped round it
    (`transpose(jvp(mixed_vjp))` is `mixed_vjp`). The last component is
    the operation itself (`mul:` on a TPU trace), never a scope."""
    found = None
    for part in stack.split('/'):
        m = _WRAPPED.fullmatch(part)
        if m and m.group(1) in SCOPES:
            found = m.group(1)
    return found


def scope_ms(events, t0: int, t1: int) -> dict:
    """{scope: device ms per outer step} over [t0, t1]: each scope's self
    time, summed over the device planes, over the number of `OUTER`
    executions that began in the window on them. Scopes that no operation
    carries are left out; an empty dict where no outer step ran."""
    t = traces.Trace(list(events) + [('/host:CPU', 'bench', traces.WINDOW, t0, t1 - t0, '')])
    n = len(t.module_durations(OUTER))
    if not n:
        return {}
    per = defaultdict(int)
    for op, ns in t.self_times():
        scope = scope_of(op[5])
        if scope:
            per[scope] += ns
    return {s: per[s] / n / 1e6 for s in SCOPES if s in per}


def read_scope(ctx, scope: str):
    """A reader's value: `scope`'s device ms per outer step in this run's
    traced window, or None."""
    events = run_events()
    if events is None:
        return None
    return scope_ms(events, ctx.trace.t0, ctx.trace.t1).get(scope)


def _overlap_ns(xs, ys) -> int:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def input_idle_ns(trace) -> float | None:
    """Device idle time in the window that a `data.wait` or
    `train.outer_batch` span overlaps, averaged over the device planes;
    None where the trace holds neither span."""
    spans = [(max(e[3], trace.t0), min(e[3] + e[4], trace.t1))
             for e in trace.host if e[2] in INPUT_SPANS]
    if not spans or not trace.devices:
        return None
    covered = traces._union([s for s in spans if s[1] > s[0]])
    length = sum(b - a for a, b in covered)
    return sum(length - _overlap_ns(covered, trace.busy_intervals(dev))
               for dev in trace.devices) / len(trace.devices)


def span_durations_ns(trace, name: str) -> list[int]:
    """Durations of the host spans named `name` that began in the window."""
    return [e[4] for e in trace.host if e[2] == name and trace.t0 <= e[3] < trace.t1]

"""From a profiler trace to the events the per-layer readers use.

`load_events(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData` and
keeps, as `(plane, line, name, start_ns, dur_ns)`, the device planes' lines
and the host threads' lines. `Trace` cuts them to the window the benchmark
marked with a host span named `WINDOW` and gives:

- `modules`: the device's program executions (line `XLA Modules`), e.g.
  `jit_inner_step(...)`;
- `ops`: its operations (line `XLA Ops`), each with the program it ran in;
- `busy_ns`: the union of the operation intervals in the window, averaged
  over the device planes;
- `breakdown()`: the operations that took most time, by self time (less
  the operations nested in them), and the longest idle gaps, each named by
  the host activity that overlaps it most.

The events can be saved as compact JSON (`save_events`) so that a trace
recorded on the chip checks the reduction in `selftest/`.
"""
from __future__ import annotations

import bisect
import gzip
import json
from collections import defaultdict

WINDOW = 'bench.window'
DEVICE_PREFIX = '/device:'
MODULE_LINE = 'XLA Modules'
OP_LINE = 'XLA Ops'


def load_events(path: str) -> list[tuple]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not (device or plane.name.startswith('/host:')):
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, int(ev.start_ns),
                            int(ev.duration_ns)))
    return out


def save_events(events, path: str) -> None:
    with gzip.open(path, 'wt') as f:
        json.dump(events, f)


def read_events(path: str) -> list[tuple]:
    with gzip.open(path, 'rt') as f:
        return [tuple(e) for e in json.load(f)]


def clip_events(events, t0: int, t1: int) -> list[tuple]:
    """The events that overlap [t0, t1], with the window span set to it:
    how a recorded trace is cut down to a test's size."""
    out = [e for e in events if e[2] != WINDOW and e[3] < t1 and e[3] + e[4] > t0]
    host = next(e[0] for e in events if e[2] == WINDOW)
    return out + [(host, 'bench', WINDOW, t0, t1 - t0)]


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


class Trace:
    def __init__(self, events):
        win = [e for e in events if e[2] == WINDOW and not e[0].startswith(DEVICE_PREFIX)]
        if len(win) != 1:
            raise ValueError(f'expected one {WINDOW!r} host span, found {len(win)}')
        self.t0 = win[0][3]
        self.t1 = win[0][3] + win[0][4]
        inside = [e for e in events if e[3] < self.t1 and e[3] + e[4] > self.t0]
        self.devices = sorted({e[0] for e in inside if e[0].startswith(DEVICE_PREFIX)})
        self.host = [e for e in inside
                     if not e[0].startswith(DEVICE_PREFIX) and e[2] != WINDOW]
        self.modules = [e for e in inside if e[1] == MODULE_LINE]
        self.ops = []
        for dev in self.devices:
            mods = sorted((e for e in self.modules if e[0] == dev), key=lambda e: e[3])
            starts = [m[3] for m in mods]
            for e in inside:
                if e[0] != dev or e[1] != OP_LINE:
                    continue
                i = bisect.bisect_right(starts, e[3]) - 1
                prog = mods[i][2] if i >= 0 and e[3] < mods[i][3] + mods[i][4] else '?'
                self.ops.append(e + (prog,))

    @property
    def window_ns(self) -> int:
        return self.t1 - self.t0

    def _clip(self, a, b):
        return max(a, self.t0), min(b, self.t1)

    def busy_intervals(self, device):
        spans = [self._clip(e[3], e[3] + e[4]) for e in self.ops if e[0] == device]
        return _union([s for s in spans if s[1] > s[0]])

    @property
    def busy_ns(self) -> float:
        if not self.devices:
            return 0.0
        return sum(b - a for dev in self.devices
                   for a, b in self.busy_intervals(dev)) / len(self.devices)

    def module_durations(self, prefix: str) -> list[int]:
        """Device durations of the program executions whose name starts with
        `prefix` and which began inside the window."""
        return [e[4] for e in self.modules
                if e[2].startswith(prefix) and self.t0 <= e[3] < self.t1]

    def self_times(self):
        """(op, self ns in the window): each operation's time less that of
        the operations nested in it (a conditional holds its branch's ops)."""
        out = []
        for dev in self.devices:
            stack = []
            for e in sorted((e for e in self.ops if e[0] == dev), key=lambda e: (e[3], -e[4])):
                while stack and stack[-1][0][3] + stack[-1][0][4] <= e[3]:
                    out.append(tuple(stack.pop()))
                a, b = self._clip(e[3], e[3] + e[4])
                if stack:
                    stack[-1][1] -= max(0, b - a)
                stack.append([e, max(0, b - a)])
            out.extend(tuple(x) for x in stack)
        return out

    def breakdown(self, n: int = 10) -> dict:
        per_op = defaultdict(int)
        for e, ns in self.self_times():
            per_op[f'{_short(e[5])}/{_op_name(e[2])}'] += ns
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n]
        gaps = []
        for dev in self.devices[:1]:
            edges = [self.t0] + [x for iv in self.busy_intervals(dev) for x in iv] + [self.t1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((a, b))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        return {'device_ops': [[name, ns / 1e9] for name, ns in top],
                'idle_gaps': [[self._host_activity(a, b), (b - a) / 1e9] for a, b in gaps]}

    def _host_activity(self, a, b) -> str:
        """The host event that covers most of [a, b]; among equals, the
        shortest, which names the most specific activity."""
        best, best_key = 'no host event', None
        for e in self.host:
            overlap = min(b, e[3] + e[4]) - max(a, e[3])
            if overlap <= 0:
                continue
            key = (overlap, -e[4])
            if best_key is None or key > best_key:
                best, best_key = e[2], key
        return best


def _short(module: str) -> str:
    return module.split('(')[0]


def _op_name(name: str) -> str:
    """`%sort.24 = (u32[...]{0:T(1024)}, s32[...]) sort(...)` -> `sort.24 sort`:
    the HLO name and its opcode, without the shapes."""
    head, _, rest = name.partition(' = ')
    if rest.startswith('('):                  # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += {'(': 1, ')': -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(' ')[2]
    opcode = rest.strip().split('(')[0]
    return f"{head.lstrip('%')} {opcode}".strip()

"""One run of one cell: the bilevel LM trainer's own loop,
`repro.launch.train.train_lm`, timed on the chip and checked against the
plain reference (`reference.py`).

A cell names a configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<mix>.json`); its correctness limits are `limits/<cell>.json` and
its per-layer metrics are read by `metrics/<metric>.py`. Nothing here names a
cell.

How a run drives the program:

- Seed. `train_lm` draws its weights from a fixed key and its rows from the
  global step. A run resumes it through its checkpoint path: `Resume` stands
  in for the trainer's `CheckpointManager`, hands back the seed's parameters
  (`weights.py`), zero optimizer state and zero domain logits at a start step
  drawn from the seed, so the rows move with the seed, and writes nothing.
  Every `save` the trainer makes (`--ckpt-every 1`) reaches `Observer`, which
  notes the time and reads the state the check needs.
- Window. A first call of two refresh cycles sizes the window from the
  second: n cycles that fill `--seconds`. The measured call then runs one cycle (the checked steps
  and the first outer step) and n more. The window opens at the
  save after the warm steps and closes at the save after the last outer step,
  whose results the trainer has read back to the host by then. Compilation,
  initialisation and the warm steps fall in set-up.
- Check. After the window, with the program's state freed, the reference
  follows the same steps from the same seed, through the first outer step
  (and, where the mix reuses the sketch, the first reuse), and the numbers
  in `compare` are held to the cell's limits.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import time
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

import flops as flops_lib
import reference
import tokens
import traces
import weights

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
TRACE_SECONDS = 8.0      # a traced run's window: whole cycles filling about this


def _read(path: Path):
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list

    @property
    def cycle(self) -> int:
        return self.traffic['outer_every'] * self.traffic['sketch_refresh_every']

    @property
    def warm(self) -> int:
        """Steps before the window: one whole cycle, which holds the checked
        steps and the checked outer steps."""
        return self.cycle

    @property
    def checked_outer(self) -> int:
        """Outer steps the check follows: the first, which builds a sketch,
        and where the mix reuses the sketch, the second, which reuses it."""
        return 1 if self.traffic['sketch_refresh_every'] == 1 else 2

    def start_step(self, seed: int) -> int:
        """A whole number of cycles in [min, max] of the mix's `start_step`,
        drawn from the seed: the rows move with the seed, and Adam's bias
        correction at a resume with zero moments keeps the first updates near
        the size they have in a fresh run."""
        lo = math.ceil(self.traffic['start_step']['min'] / self.cycle)
        hi = self.traffic['start_step']['max'] // self.cycle
        return self.cycle * (lo + seed % (hi - lo + 1))


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _read(root / 'BENCHMARK.json')
    cell = next((w for w in bench['workloads'] if w['name'] == workload), None)
    if cell is None:
        raise SystemExit(f'unknown workload {workload!r}')
    entry = next(c for c in bench['configs'] if c['name'] == cell['config'])
    limits = root / 'benchmarks/chip/limits' / f'{workload}.json'
    return Cell(
        name=workload, chips=cell['chips'],
        config=_read(root / entry['file']),
        traffic=_read(root / 'benchmarks/chip/traffic' / f"{cell['traffic']}.json"),
        limits=_read(limits) if limits.exists() else {},
        per_layer=bench['per_layer'])


def file_cell(config: str, traffic: str) -> Cell:
    """A configuration under a mix from their files alone, whether or not
    BENCHMARK.json names the pair (no limits, no per-layer metrics)."""
    return Cell(name=f'{config}.{traffic}', chips=1,
                config=_read(BENCH / 'configs' / f'{config}.json'),
                traffic=_read(BENCH / 'traffic' / f'{traffic}.json'), limits={}, per_layer=[])


def program_config(conf: dict):
    """The trainer's ModelConfig for a configuration file, checked key by
    key against the file's published numbers."""
    from repro.configs import get_config
    prog = conf['program']
    cfg = dataclasses.replace(get_config(prog['arch']), **prog['model_config'])
    for key, attr in prog['keys'].items():
        if getattr(cfg, attr) != conf[key]:
            raise SystemExit(f'{conf["name"]}: the program runs {attr}='
                             f'{getattr(cfg, attr)!r}, the file states {key}={conf[key]!r}')
    return cfg


def trainer_args(cell: Cell, steps: int):
    from repro.launch.train import build_parser
    t, tr = cell.config['trainer'], cell.traffic
    return build_parser().parse_args([
        '--steps', str(steps), '--batch', str(tr['batch']), '--seq', str(tr['seq']),
        '--outer-every', str(tr['outer_every']),
        '--sketch-refresh-every', str(tr['sketch_refresh_every']),
        '--solver', t['solver'], '--k', str(t['sketch_rank']), '--rho', str(t['rho']),
        '--ckpt-dir', 'seeded-resume', '--ckpt-every', '1', '--log-every', '0'])


def _find(state, attr):
    """The first node of an optimizer state with attribute `attr`."""
    if hasattr(state, attr):
        return getattr(state, attr)
    for s in (state if isinstance(state, (tuple, list)) else ()):
        found = _find(s, attr)
        if found is not None:
            return found
    return None


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def diff_norms(a, b):
    return leaf_norms(jax.tree.map(jnp.subtract, a, b))


def memory_stats() -> dict:
    """`memory_stats()` of the fullest local device."""
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(stats, key=lambda s: s.get('peak_bytes_in_use', 0))


class Resume:
    """Stands in for the trainer's CheckpointManager: the resume returns the
    seed's state, and each save goes to `on_save`. Nothing touches disk."""

    def __init__(self, cell: Cell, seed: int, on_save):
        self.cell, self.seed, self.on_save = cell, seed, on_save
        self.start = cell.start_step(seed)
        self.param_shapes = None

    def latest_step(self):
        return self.start

    def restore_latest(self, template, shardings=None):
        self.param_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), template['params'])
        tree = {key: weights.zeros_like_tree(val) for key, val in template.items()
                if key != 'params'}
        tree['params'] = weights.make_params(self.cell.config['init'],
                                             self.param_shapes, self.seed)
        return tree, {'step': self.start}

    def save(self, step, tree, extra=None):
        self.on_save(step, tree)

    def wait(self):
        pass


class Observer:
    """Reads the trainer at each save: times every step; in the measured
    call it opens and closes the window (and the profiler), and with `probe`
    it keeps what the check compares."""

    def __init__(self, cell: Cell, start: int, window=None, probe=False, trace_dir=None):
        self.cell, self.start, self.window = cell, start, window
        self.probe, self.trace_dir = probe, trace_dir
        self.times, self.seen = {}, {}
        self.t_start = self.t_end = None
        self.memory = {}
        self._annotation = None

    def __call__(self, step, tree):
        now = time.perf_counter()
        if step in self.times:          # the trainer's last save repeats a step
            return
        self.times[step] = now
        if self.window and step == self.window[1]:
            self.t_end = now
            self.memory = memory_stats()
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                jax.profiler.stop_trace()
        if self.probe:
            self._probe(step - self.start, tree)
        if self.window and step == self.window[0]:
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self._annotation = jax.profiler.TraceAnnotation(traces.WINDOW)
                self._annotation.__enter__()
            self.t_start = time.perf_counter()

    def _probe(self, k, tree):
        every = self.cell.traffic['outer_every']
        if k == 1:
            self.seen['mu1'] = np.asarray(leaf_norms(_find(tree['opt'], 'mu')))
        elif k == 3:
            self.seen['theta3'] = jax.device_get(tree['params'])
        if k % every == 0 and k // every <= self.cell.checked_outer:
            self.seen.setdefault('hmu', []).append(
                np.asarray(_find(tree['houter'], 'mu')['domain_logits']))
            self.seen.setdefault('phi', []).append(np.asarray(tree['h']['domain_logits']))


def drive(cell: Cell, seed: int, n_steps: int, observer: Observer):
    """One `train_lm` call of `n_steps` steps from the seed's start step."""
    import repro.launch.train as train_mod
    resume = Resume(cell, seed, observer)
    cfg = program_config(cell.config)
    args = trainer_args(cell, resume.start + n_steps)
    with mock.patch.object(train_mod, 'CheckpointManager', lambda directory: resume):
        run = train_mod.train_lm(cfg, args)
    return run, resume


def size_window(cell: Cell, seed: int, seconds: float, trace: bool) -> tuple[int, float]:
    """(cycles, seconds per cycle): two cycles, the second timed, since a
    program's first execution carries one-time costs."""
    start = cell.start_step(seed)
    obs = Observer(cell, start)
    c = cell.cycle
    drive(cell, seed, 2 * c, obs)
    per_cycle = obs.times[start + 2 * c] - obs.times[start + c]
    target = min(seconds, TRACE_SECONDS) if trace else seconds
    gc.collect()
    return max(1, round(target / per_cycle)), per_cycle


# ---------------------------------------------------------------- the check
def program_record(cell: Cell, seed: int, obs: Observer, run, param_shapes) -> dict:
    b1 = cell.config['trainer']['outer_optimizer']['b1']
    ib1 = cell.config['trainer']['inner_optimizer']['b1']
    theta0 = weights.make_params(cell.config['init'], param_shapes, seed)
    theta3 = jax.device_put(obs.seen['theta3'])
    dtheta3 = np.asarray(diff_norms(theta3, theta0))
    del theta0, theta3
    mus = [np.zeros_like(obs.seen['hmu'][0])] + obs.seen['hmu']
    hg = [(mu - b1 * prev) / (1 - b1) for prev, mu in zip(mus, mus[1:])]
    return {'losses': np.asarray(run.losses[:3]), 'grad1': obs.seen['mu1'] / (1 - ib1),
            'dtheta3': dtheta3, 'hg': hg, 'phi': obs.seen['phi']}


def reference_record(cell: Cell, seed: int, param_shapes, prec='f32', fault=None,
                     sketch=True) -> dict:
    """The reference's own run over the checked steps: the inner steps up to
    the last checked outer step (`Cell.checked_outer`), and those outer
    steps, each with its hypergradient and the domain logits it leaves.
    `fault`, planted: 'half' (each inner loss over the first half of its
    rows), 'half_outer' (each outer step's outer loss over the first half of
    the outer batch), 'half_mixed' (its mixed VJP over the first half of the
    inner batch) or 'answer' (each hypergradient's largest entry negated).
    `sketch=False` takes the inverse as 1/rho, leaving out the sketch's k
    directions."""
    c, tr, t = cell.config, cell.traffic, cell.config['trainer']
    every, B = tr['outer_every'], tr['batch']
    stream = tokens.Stream(tr['stream'], c['vocab_size'], tr['seq'])
    prog = reference.make_programs(c, t, prec, half=fault == 'half')
    mixed = (reference.make_programs(c, t, prec, half=True) if fault == 'half_mixed'
             else prog)['mixed']
    theta0 = weights.make_params(c['init'], param_shapes, seed)
    theta = theta0
    m = v = weights.zeros_like_tree(theta0)
    phi = jnp.zeros((t['n_domain_logits'],), jnp.float32)
    pm = pv = jnp.zeros_like(phi)
    start = cell.start_step(seed)
    losses, rec, sk = [], {'phi': [], 'hg': []}, None
    for j in range(cell.checked_outer * every):
        step = start + j
        batch = jax.device_put(stream.batch(step, B))
        theta, m, v, loss, g = prog['inner_step'](theta, m, v, phi, batch, jnp.int32(step))
        losses.append(loss)
        if j == 0:
            rec['grad1'] = np.asarray(leaf_norms(g))
        del g
        if j == 2:
            rec['dtheta3'] = np.asarray(diff_norms(theta, theta0))
            del theta0
        if (j + 1) % every:
            continue
        if sketch and ((j + 1) // every - 1) % tr['sketch_refresh_every'] == 0:
            sk = None                    # the old sketch's HBM, before the new one
            sk = prog['columns'](theta, phi, batch, jax.random.PRNGKey(step))
        ob = stream.outer_batch(step, B)
        if fault == 'half_outer':
            ob = jax.tree.map(lambda a: a[:B // 2], ob)
        vg = prog['outer_grad'](theta, jax.device_put(ob))
        hg = mixed(theta, phi, batch, reference.ihvp(sk, vg, t['rho']))
        del vg
        if fault == 'answer':
            i = jnp.argmax(jnp.abs(hg))
            hg = hg.at[i].set(-hg[i])
        phi, pm, pv = prog['outer_step'](phi, pm, pv, hg, jnp.int32(step))
        rec['hg'].append(np.asarray(hg))
        rec['phi'].append(np.asarray(phi))
    rec['losses'] = np.asarray(losses[:3])
    if sk is not None:
        rec['sketch_eigenvalues'] = np.linalg.eigvalsh(np.asarray(sk[1], np.float64)).tolist()
    return rec


def _gap(a, b) -> float:
    return float(abs(a - b) / abs(b))


def _one_minus_cos(a, b) -> float:
    return float(1 - a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers held to the limits, each a relative gap:

    - loss1: the first step's |L - L_ref| / |L_ref|, the forward pass at the
      seed's weights (later steps' losses carry the trajectories' drift:
      `diagnostics`);
    - grad1: per leaf, the gap between the first (clipped) gradient's norm
      and the reference's, over the larger of the reference leaf's norm and
      the median leaf's; the worst leaf;
    - dtheta3: the same for each leaf's change over the first three steps,
      leaving out leaves whose reference gradient is under a thousandth of
      the median leaf's;
    - hg1: 1 - cos of the angle between the first outer step's
      hypergradient and the reference's;
    - dphi1: the gap between the norm of the domain logits' change over the
      first outer step and the reference's, over the logits whose reference
      hypergradient is over a thousandth of its largest entry (a domain
      absent from the inner batch has a hypergradient of 0 up to rounding,
      and Adam's normalised step turns that rounding into a full step);
      from zero moments Adam's step is about lr x sign, so a state left
      unchanged or an update doubled reads 1;
    - dphi2: the same over the second outer step, where the check follows
      one (the first that reuses the sketch), over the logits kept at
      either step.
    """
    def by_leaf(a, b, keep):
        scale = np.maximum(b, np.median(b))
        return float(np.max((np.abs(a - b) / scale)[keep]))

    g_ref = ref['grad1']
    keep = g_ref >= 1e-3 * np.median(g_ref)
    out = {'loss1': _gap(prog['losses'][0], ref['losses'][0]),
           'grad1': by_leaf(prog['grad1'], g_ref, np.ones_like(keep)),
           'dtheta3': by_leaf(prog['dtheta3'], ref['dtheta3'], keep),
           'hg1': _one_minus_cos(prog['hg'][0], ref['hg'][0])}
    live = np.zeros(ref['hg'][0].shape, bool)
    phi_p = [np.zeros_like(live, np.float32)] + list(prog['phi'])
    phi_r = [np.zeros_like(live, np.float32)] + list(ref['phi'])
    for i in range(1, min(len(phi_p), len(phi_r))):
        hg = np.abs(ref['hg'][i - 1])
        live |= hg > 1e-3 * hg.max()
        out[f'dphi{i}'] = _gap(np.linalg.norm((phi_p[i] - phi_p[i - 1])[live]),
                               np.linalg.norm((phi_r[i] - phi_r[i - 1])[live]))
    return out


def diagnostics(prog: dict, ref: dict, leaf_names=None) -> dict:
    """Readings beside the compared numbers, for setting and explaining
    limits: each checked step's loss gap, each checked hypergradient's
    relative gap and angle, and the leaf that sets grad1 and dtheta3."""
    out = {f'loss_step{i + 1}': float(abs(x - y) / abs(y))
           for i, (x, y) in enumerate(zip(prog['losses'], ref['losses']))}
    for i, (a, b) in enumerate(zip(prog['hg'], ref['hg'])):
        out[f'hg{i + 1}_gap'] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        out[f'hg{i + 1}_one_minus_cos'] = _one_minus_cos(a, b)
    if leaf_names is not None:
        for key in ('grad1', 'dtheta3'):
            x, y = prog[key], ref[key]
            gap = np.abs(x - y) / np.maximum(y, np.median(y))
            out[f'{key}_worst_leaf'] = leaf_names[int(np.argmax(gap))]
    return out


def judge(numbers: dict, limits: dict) -> bool:
    """Every number the cell's limits name is finite and within its limit.
    A number without a limit is read but not compared (PERF.md says why)."""
    if not limits:
        return False
    return all(name in numbers and math.isfinite(numbers[name])
               and numbers[name] <= lim for name, lim in limits.items())


# ---------------------------------------------------------------- metrics
class Context:
    """What a per-layer reader sees."""

    def __init__(self, trace, window_flops, peak, chips):
        self.trace, self.window_flops, self.peak, self.chips = (
            trace, window_flops, peak, chips)


def read_metric(name: str, ctx: Context):
    spec = importlib.util.spec_from_file_location(
        f'metric_{name}', BENCH / 'metrics' / f'{name}.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def param_count(shapes) -> int:
    return int(sum(math.prod(s.shape) for s in jax.tree.leaves(shapes)))


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        peak: dict, trace_dir: Path) -> dict:
    """Everything after the device check: returns the result's fields."""
    n_cycles, per_cycle = size_window(cell, seed, seconds, trace)
    start = cell.start_step(seed)
    window = (start + cell.warm, start + cell.warm + n_cycles * cell.cycle)
    obs = Observer(cell, start, window, probe=True,
                   trace_dir=str(trace_dir) if trace else None)
    lm, resume = drive(cell, seed, window[1] - start, obs)
    window_s = obs.t_end - obs.t_start
    shapes = resume.param_shapes
    tr = cell.traffic
    steps = n_cycles * cell.cycle
    values = list(lm.losses) + [o['hypergrad_norm'] for o in lm.outer]
    result = {
        'attempted': len(values),
        'failed': sum(not math.isfinite(x) for x in values),
        'memory': obs.memory,
        'window_s': window_s,
        'n_cycles': n_cycles,
        'setup_s': obs.t_start - t0,
    }
    metrics = {}
    if not trace:
        metrics['tokens_per_s'] = (steps * tr['batch'] * tr['seq'] / window_s, 'tokens/s')
        metrics['peak_hbm_gb'] = (peak_bytes(obs.memory) / 1e9, 'GB')
        metrics['setup_s'] = (obs.t_start - t0, 's')
    else:
        files = sorted(trace_dir.glob('**/*.xplane.pb'), key=lambda p: p.stat().st_mtime)
        tr_ = traces.Trace(traces.load_events(str(files[-1])))
        k = cell.config['trainer']['sketch_rank']
        fl = flops_lib.cycle_flops(cell.config, tr, k, param_count(shapes))
        ctx = Context(tr_, fl['cycle'] * n_cycles, peak, cell.chips)
        for m in cell.per_layer:
            value = read_metric(m['name'], ctx)
            if value is not None:
                metrics[m['name']] = (value, m['unit'])
        result['busy_s'] = tr_.busy_ns / 1e9
        result['trace_window_s'] = tr_.window_ns / 1e9
        result['breakdown'] = tr_.breakdown()
    result['metrics'] = metrics
    prog = program_record(cell, seed, obs, lm, shapes)
    del lm, obs
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_record(cell, seed, shapes)
    result['numbers'] = compare(prog, ref)
    result['reference_s'] = time.perf_counter() - t_ref
    result['per_cycle_s'] = per_cycle
    return result


def peak_bytes(stats: dict) -> int:
    """The most device memory the run needed: the allocator's peak of
    buffers in use plus the peak it held reserved for programs' temporaries,
    where the backend reports the latter."""
    return int(stats.get('peak_bytes_in_use', 0) + stats.get('peak_bytes_reserved', 0))

"""Production bilevel LM trainer.

Wires every substrate together: sharded model (pjit over the host mesh or
the production mesh), deterministic domain-mixture data pipeline with
prefetch, AdamW/Adafactor, atomic+async checkpointing with resume, and the
paper's Nyström hypergradient as a first-class outer step — every
``outer_every`` inner steps, per-domain loss weights are updated from a
balanced validation batch (§5.4 at LM scale).

Fault-tolerance drill: kill the process mid-run and relaunch with the same
--ckpt-dir — it resumes from the last durable step (restores across a
*different* device count thanks to reshard-on-restore). See
tests/test_substrate.py::test_trainer_restart_resumes for the automated
version of that drill.

The LM loop itself is :func:`train_lm` (any ``ModelConfig``); ``main``
resolves ``--arch``/``--reduced`` to one and calls it. ``chip_smoke.py`` at
the repository root calls it on a depth-cut yi-9b at full width.

Usage (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch yi_9b --reduced \
      --steps 50 --outer-every 25 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import SketchPolicy, config_from_cli, implicit_root
from repro.core.tree_util import tree_norm
from repro.data.loader import Prefetcher, ShardedLoader
from repro.data.synthetic import TokenStream
from repro.distributed.ctx import activation_mesh
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import N_DOMAINS, make_optimizer
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.models.transformer import train_loss
from repro.optim import adam


def build_losses(cfg):
    def inner_loss(params, hparams, batch):
        w = jax.nn.softmax(hparams['domain_logits']) * N_DOMAINS
        return train_loss(cfg, params, batch,
                          example_weights=w[batch['domain']])

    def outer_loss(params, hparams, batch):
        return train_loss(cfg, params, batch)

    return inner_loss, outer_loss


def _run_graph(args):
    """``--problem <graph-name>``: a multi-level GRAPHS entry (trilevel
    chains) routed through ``Engine.solve`` — the whole inner-to-outer
    sweep as one jitted program. ``--solver``/``--rho``/
    ``--sketch-refresh-every`` configure every edge uniformly (per-edge
    overrides are a builder-kwarg affair); ``--steps`` counts outer steps."""
    from repro.engine import Engine, EngineConfig, get_graph
    kwargs = {'solver': args.solver}
    if args.rho is not None:
        kwargs['rho'] = args.rho
    if args.sketch_refresh_every is not None:
        kwargs['refresh_every'] = args.sketch_refresh_every
    graph = get_graph(args.problem, **kwargs)
    order = graph.chain_order()
    print(f'[train] graph={args.problem} levels={"<-".join(order)} '
          f'solver={args.solver} n_outer={args.steps}')
    result = Engine().solve(graph, EngineConfig(n_outer=args.steps))
    for i, loss in enumerate(result.losses):
        if i % max(1, args.log_every) == 0 or i == len(result.losses) - 1:
            print(f'[engine] outer {i}: top_loss={loss:.6f}')
    bills = ' '.join(f'{e}={n}' for e, n in result.edge_hvps.items())
    print(f'[train] done: graph={args.problem} hvps={result.hvp_count} '
          f'({bills}) wall_s={result.seconds:.1f}')
    return result


def _run_problem(args):
    """``--problem <name>``: resolve the registry entry and drive it through
    the typed problem API (one entry point; sketch amortization via
    ``--sketch-refresh-every`` comes along for free). An
    :class:`~repro.core.problem.InfluenceProblem` routes to ``influence()``
    instead of ``solve()``; a multi-level graph name (``repro.engine``
    GRAPHS registry) routes to ``Engine.solve`` — ``--steps`` then counts
    training (resp. outer) steps and ``--queries``/``--top-k`` size the
    query block / result."""
    from repro.core.problem import (InfluenceProblem, get_problem, influence,
                                    solve)
    from repro.engine import GRAPHS
    if args.problem in GRAPHS:
        return _run_graph(args)
    hg_cfg = config_from_cli(
        args.solver,
        flags={'k': args.k, 'rho': args.rho,
               'sketch_refresh_every': args.sketch_refresh_every},
        defaults={'k': 8, 'rho': 1e-2})
    problem = get_problem(args.problem)
    if isinstance(problem, InfluenceProblem):
        if args.serve:
            return _serve_problem(problem, hg_cfg, args)
        queries = problem.reference['queries'](args.queries)
        print(f'[train] influence problem={problem.name} '
              f'solver={args.solver} m={args.queries} top_k={args.top_k}')
        result = influence(problem, hg_cfg, queries,
                           top_k=args.top_k, train_steps=args.steps)
        for q in range(result.scores.shape[0]):
            pairs = ' '.join(
                f'{int(i)}:{float(s):+.4f}'
                for s, i in zip(result.scores[q], result.indices[q]))
            print(f'[influence] query {q}: {pairs}')
        print(f'[train] done: problem={problem.name} '
              f'hvps={result.hvp_count} wall_s={result.seconds:.1f}')
        return result
    print(f'[train] problem={problem.name} solver={args.solver} '
          f'n_outer={args.steps}')
    result = solve(problem, hg_cfg, n_outer=args.steps,
                   log_every=args.log_every)
    metrics = ' '.join(f'{k}={v:.4f}' for k, v in result.metrics.items())
    print(f'[train] done: problem={problem.name} '
          f'outer_loss={result.history["outer_loss"][-1]:.4f} '
          f'hvps={result.hvp_count} wall_s={result.seconds:.1f} {metrics}')
    return result


def _serve_problem(problem, hg_cfg, args):
    """``--problem influence --serve``: stand up the serving tier
    (``repro.serve``) instead of a one-shot ``influence()`` call. Trains
    once, calibrates the batcher's block size from a warmup sweep, then
    answers ``--queries`` queries TWICE — a cold pass (first flush builds
    the sketch into the store) and a warm pass (every flush hits the store,
    zero build HVPs) — and prints the per-pass service stats, so the
    amortization the store buys is visible from the CLI."""
    import jax as _jax

    from repro.serve import InfluenceService, SketchStore

    store = SketchStore()
    service = InfluenceService(problem, hg_cfg, store=store,
                               top_k=args.top_k, train_steps=args.steps,
                               max_delay=0.0)
    print(f'[serve] influence problem={problem.name} solver={args.solver} '
          f'queries={args.queries} top_k={args.top_k}')
    rates = service.warmup()
    print(f'[serve] calibrated block_size={service.batcher.block_size} '
          + ' '.join(f'm={m}:{r:.1f}q/s' for m, r in sorted(rates.items())))
    pool = problem.reference['queries'](args.queries)
    for phase in ('cold', 'warm'):
        if phase == 'cold':
            store.clear()                      # forget the warmup's sketch
        service.reset_metrics()                # per-pass latency/HVP stats
        hits0, misses0 = store.hits, store.misses
        tickets = []
        for q in range(args.queries):
            tickets.append(service.submit(
                _jax.tree.map(lambda x: x[q], pool)))
            service.pump()
        service.flush()
        for q, t in enumerate(tickets):
            resp = service.result(t)
            pairs = ' '.join(f'{int(i)}:{float(s):+.4f}'
                             for s, i in zip(resp.scores, resp.indices))
            print(f'[serve:{phase}] query {q} ({resp.latency_s*1e3:.1f}ms '
                  f'm={resp.batched_m} hit={resp.cache_hit}): {pairs}')
        s = service.stats()
        lookups = (store.hits - hits0) + (store.misses - misses0)
        rate = (store.hits - hits0) / lookups if lookups else 0.0
        print(f'[serve:{phase}] p50={s["latency_p50_ms"]:.1f}ms '
              f'p95={s["latency_p95_ms"]:.1f}ms '
              f'hvps={s["build_hvps"] + s["fallback_hvps"]} '
              f'hit_rate={rate:.2f}')
    return service


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='yi_9b')
    ap.add_argument('--reduced', action='store_true',
                    help='tiny same-family config (CPU smoke / CI)')
    ap.add_argument('--steps', type=int, default=200)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq', type=int, default=128)
    ap.add_argument('--outer-every', type=int, default=50,
                    help='inner steps between Nyström hypergradient updates')
    ap.add_argument('--k', type=int, default=None,
                    help='sketch rank / iterations (default 8)')
    ap.add_argument('--rho', type=float, default=None,
                    help='damping (default 1e-2)')
    ap.add_argument('--sketch-refresh-every', type=int, default=None,
                    help='outer steps between sketch rebuilds (default 1 = '
                         'fresh every outer step; N>1 reuses the sketch for '
                         'N-1 steps, saving k HVPs each)')
    ap.add_argument('--solver', default='nystrom')
    ap.add_argument('--problem', default=None,
                    help='run a registered problem (repro.core PROBLEMS '
                         'registry, e.g. reweighting | distillation | '
                         'logreg_wd | influence) through solve()/influence()'
                         ', or a multi-level graph (repro.engine GRAPHS '
                         'registry: distill_hpo | reweight_maml) through '
                         'Engine.solve, instead of the LM pipeline; --steps '
                         'then counts OUTER (resp. training) steps')
    ap.add_argument('--queries', type=int, default=8,
                    help='influence problems: query-block width m')
    ap.add_argument('--top-k', type=int, default=10,
                    help='influence problems: top-k examples per query')
    ap.add_argument('--serve', action='store_true',
                    help='influence problems: stand up the serving tier '
                         '(sketch store + query batcher, repro.serve) and '
                         'answer --queries queries cold then warm, printing '
                         'latency/cache stats, instead of one influence() '
                         'call')
    ap.add_argument('--ckpt-dir', default=None)
    ap.add_argument('--ckpt-every', type=int, default=100)
    ap.add_argument('--production-mesh', action='store_true')
    ap.add_argument('--log-every', type=int, default=10)
    return ap


@dataclasses.dataclass
class LMRun:
    """What one :func:`train_lm` run observed, on the host.

    ``losses``: the inner loss of every step this run took (a resumed run
    starts at its checkpoint). ``outer``: one dict per outer step — ``step``,
    ``val`` (pre-update outer loss), ``noisy_weight`` (softmax mass on the
    noisy domains) and ``hypergrad_norm``. ``compile_s``: seconds spent
    compiling each jitted step, kept apart from the step times.
    ``peak_bytes_in_use``: the device allocator's peak, where the backend
    reports one (None elsewhere).
    """
    losses: list
    outer: list
    hparams: Any
    compile_s: dict
    peak_bytes_in_use: int | None


def train_lm(cfg: ModelConfig, args) -> LMRun:
    """The bilevel LM loop on ``cfg``: ``args.steps`` inner steps, an outer
    Nyström hypergradient step every ``args.outer_every`` of them, with
    checkpoint/resume under ``args.ckpt_dir``. ``args`` is the namespace
    :func:`build_parser` parses; ``--arch``/``--reduced`` are read by
    :func:`main` only, which resolves them to ``cfg``."""
    model = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    print(f'[train] arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M '
          f'mesh={dict(mesh.shape)} devices={len(jax.devices())}')
    inner_loss, outer_loss = build_losses(cfg)
    optimizer = make_optimizer(cfg)
    outer_opt = adam(1e-2)
    # registry-driven flag forwarding: explicitly-passed flags the solver
    # does not consume are rejected loudly by build(), never silently dropped
    hg_cfg = config_from_cli(
        args.solver,
        flags={'k': args.k, 'rho': args.rho,
               'sketch_refresh_every': args.sketch_refresh_every},
        defaults={'k': 8, 'rho': 1e-2},
        column_chunk=4)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def inner_step(params, opt_state, hparams, step, batch):
        loss, grads = jax.value_and_grad(inner_loss)(params, hparams, batch)
        params, opt_state = optimizer.apply(grads, opt_state, params, step)
        return params, opt_state, step + 1, loss

    solver = hg_cfg.build()
    # sketch lifecycle: amortizable solvers (Nyström/exact) carry one sketch
    # across outer steps, rebuilt every sketch_refresh_every of them by the
    # policy's lax.cond inside the jitted step; iterative solvers prepare
    # fresh inside the backward pass (nothing to amortize).
    if getattr(type(solver), 'amortizable', False):
        policy = SketchPolicy(solver=solver, inner_loss=inner_loss,
                              refresh_every=hg_cfg.sketch_refresh_every)
    elif hg_cfg.sketch_refresh_every > 1:
        raise TypeError(
            f'--sketch-refresh-every={hg_cfg.sketch_refresh_every} needs an '
            f'amortizable solver; {type(solver).__name__} prepares a '
            'trace-local state with nothing to reuse across outer steps')
    else:
        policy = None

    # the sketch state is replaced every outer step: donating it lets the
    # refreshed sketch reuse its HBM instead of holding two at once
    @functools.partial(jax.jit, donate_argnums=(7,))
    def outer_step(params, hparams, outer_state, step, inner_b, outer_b, key,
                   sketch_state):
        # the warm-started params are the implicit solution; grad through the
        # implicit_root map assembles Eq. 3 in the custom_vjp backward pass
        solve = implicit_root(lambda phi, b: params, inner_loss, solver)
        if policy is not None:
            sketch_state, _ = policy.refresh(
                sketch_state, params, hparams, inner_b, key)

            def outer_obj(phi):
                theta = solve(phi, inner_b, state=sketch_state.sketch)
                return outer_loss(theta, phi, outer_b)
        else:
            def outer_obj(phi):
                return outer_loss(solve(phi, inner_b, rng=key), phi, outer_b)

        val, hg = jax.value_and_grad(outer_obj)(hparams)  # val: pre-update g
        hparams, outer_state = outer_opt.apply(hg, outer_state, hparams, step)
        return hparams, outer_state, val, tree_norm(hg), sketch_state

    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    opt_state = optimizer.init(params)
    hparams = {'domain_logits': jnp.zeros((N_DOMAINS,), jnp.float32)}
    outer_state = outer_opt.init(hparams)
    step = jnp.int32(0)

    # ---------------- checkpoint/resume (fault tolerance) ----------------
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if ckpt and ckpt.latest_step() is not None:
        tree = {'params': params, 'opt': opt_state, 'h': hparams,
                'houter': outer_state}
        tree, manifest = ckpt.restore_latest(tree)
        params, opt_state = tree['params'], tree['opt']
        hparams, outer_state = tree['h'], tree['houter']
        start_step = manifest['step']
        print(f'[train] resumed from step {start_step}')
        step = jnp.int32(start_step)

    # ---------------- data pipeline (deterministic, step-indexed) --------
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq)
    loader = Prefetcher(ShardedLoader(
        lambda s: stream.batch(s, args.batch), start_step=start_step), depth=2)

    # both step programs are compiled before the loop, so compile time is
    # reported on its own and never lands inside a step time
    batch = next(loader)
    compile_s = {}

    def compile_step(name, fn, *a):
        t = time.perf_counter()
        with activation_mesh(mesh):        # the model's sharding hints
            out = fn.lower(*a).compile()
        compile_s[name] = time.perf_counter() - t
        print(f'[train] compiled {name} in {compile_s[name]:.2f}s',
              flush=True)
        return out

    inner_step = compile_step('inner_step', inner_step, params,
                              opt_state, hparams, step, batch)
    sketch_state = None
    if any((i + 1) % args.outer_every == 0
           for i in range(start_step, args.steps)):
        # a key only for its shape: init_state's rng is eval_shape-only and
        # lowering reads no values
        shape_key = jax.random.fold_in(rng, 1)
        if policy is not None:
            # structural zeros at max staleness: the first outer step's
            # lax.cond rebuilds it; costs no HVPs here
            sketch_state = policy.init_state(
                params, hparams, batch, shape_key)
        outer_step = compile_step(
            'outer_step', outer_step, params, hparams, outer_state,
            step, batch, batch, shape_key, sketch_state)

    # ---------------- loop ----------------
    losses, outer = [], []
    t_win, n_win = time.perf_counter(), 0
    for i in range(start_step, args.steps):
        if i > start_step:
            batch = next(loader)
        params, opt_state, step, loss = inner_step(
            params, opt_state, hparams, step, batch)
        losses.append(loss)
        n_win += 1
        if args.log_every and (i + 1) % args.log_every == 0:
            loss_f = float(loss)
            now = time.perf_counter()
            print(f'[train] step {i+1} loss={loss_f:.4f} '
                  f'step_s={(now - t_win) / n_win:.4f}', flush=True)
            t_win, n_win = now, 0
        if (i + 1) % args.outer_every == 0:
            t0 = time.perf_counter()
            outer_b = stream.batch(10_000_000 + i, args.batch,
                                   clean_only=True)
            okey = jax.random.PRNGKey(i)
            hparams, outer_state, val, hg_norm, sketch_state = outer_step(
                params, hparams, outer_state, jnp.int32(i), batch,
                outer_b, okey, sketch_state)
            w = jax.nn.softmax(hparams['domain_logits'])
            rec = {'step': i + 1, 'val': float(val),
                   'noisy_weight': float(
                       w[jnp.array(stream.noisy_domains)].sum()),
                   'hypergrad_norm': float(hg_norm)}
            outer.append(rec)
            outer_s = time.perf_counter() - t0
            uniform = len(stream.noisy_domains) / w.shape[0]
            print(f'[outer] step {i+1} val(pre-update)={rec["val"]:.4f} '
                  f'noisy-domain weight={rec["noisy_weight"]:.3f} '
                  f'(uniform={uniform:.3f}) '
                  f'hypergrad_norm={rec["hypergrad_norm"]:.4e} '
                  f'outer_s={outer_s:.4f}', flush=True)
            t_win += outer_s          # inner step times exclude it
        if ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, {'params': params, 'opt': opt_state,
                              'h': hparams, 'houter': outer_state})
    if ckpt:
        ckpt.save(args.steps, {'params': params, 'opt': opt_state,
                               'h': hparams, 'houter': outer_state})
        ckpt.wait()
    losses = [float(l) for l in losses]
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get('peak_bytes_in_use')
    final = f'{losses[-1]:.4f}' if losses else 'n/a'
    print(f'[train] done: {args.steps} steps, final loss {final}, '
          f'peak_bytes_in_use={peak}', flush=True)
    return LMRun(losses=losses, outer=outer, hparams=hparams,
                 compile_s=compile_s, peak_bytes_in_use=peak)


def main(argv=None):
    use_compile_cache()
    args = build_parser().parse_args(argv)
    if args.problem is not None:
        return _run_problem(args)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    return train_lm(cfg, args)


if __name__ == '__main__':
    main()

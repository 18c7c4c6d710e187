"""Smoke run of the main path on one TPU chip (four with ``--chips 4``).

    python chip_smoke.py              # one chip: trainer + Pallas kernels
    python chip_smoke.py --chips 4    # four chips: flat_sharded only

One chip, two phases, one process:

1. **Trainer.** The bilevel LM trainer (``repro.launch.train.train_lm``, the
   loop behind ``python -m repro.launch.train``) on yi-9b at its published
   widths, cut in depth to one layer (one whole scan period) and in
   vocabulary to an eighth, random weights from a fixed seed: 8 inner steps
   and, every 4, an outer step that builds a fresh Nyström sketch and
   applies it. Every loss must be finite and every hypergradient finite and
   nonzero.
2. **Kernels.** The compiled Pallas Nyström kernels (``PallasBackend``)
   and ``FlatBackend``'s XLA contractions, both through
   ``NystromIHVP.prepare``/``apply_matrix`` on a seeded bf16 sketch with the
   trainer's p and k, for m = 1 and m = 32 queries: each against the
   float64 answer for its own sketch, computed on the host, and against
   each other.

``--chips 4`` runs only the sharded path, on the trainer's p: the
``flat_sharded`` prepare and block apply over a 4-device mesh against the
unsharded ``flat`` apply on one device, with ``FLAT_SHARDED_CONTRACT``
enforced on the compiled apply.

No phase's failure is caught: any failure exits nonzero. The last line of
standard output is one JSON object naming the device; it is printed only
when every phase passed. Without a TPU the script exits nonzero at once.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / 'src'))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.auditor import audit  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import FLAT_SHARDED_CONTRACT, NystromIHVP  # noqa: E402
from repro.core.backend import (FlatBackend, FlatShardedBackend,  # noqa: E402
                                PallasBackend)
from repro.core.tree_util import PyTreeIndexer  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.train import build_parser, train_lm  # noqa: E402
from repro.models import build_model  # noqa: E402

# sketch rank, cut from the 8 the trainer defaults to. The outer step holds
# the tree sketch (C and its whitened form B, 2·k·p f32 = 1.91 GB per unit
# of k at this p) beside the params and AdamW state (2.87 GB), and reserves
# its temporaries when it loads: at k = 4 a v5e refused to load it (6.91 GB
# asked, 5.95 GB free)
K = 3
RHO = 1e-2     # the trainer's damping
SEED = 0
# Tolerances on ‖u − u_ref‖/‖u_ref‖, set from v5e readings at k = 3 over
# three seeds (PERF.md, PR 13). Each apply is checked against exact_apply:
# the float64 answer for its own bf16 sketch, so only the apply's own
# arithmetic shows.
# PALLAS_TOL: the kernels sum each (k, block_p) slab on the MXU and add the
# slabs in f32. Readings 5e-7 … 2.6e-6 at both sizes; a flat apply with one
# bf16 pass for HIGHEST reads 7e-4 … 9e-4 on the m = 32 block. 1e-5 keeps
# ~4× over the readings and tells the two precisions apart.
PALLAS_TOL = 1e-5
# REL_TOL: flat against float64, and every comparison with flat. XLA's
# block GEMM contracts all of p on the MXU: flat's m = 32 apply reads
# 1.0e-4 … 1.3e-4 off float64 at p = 7.47e6, the same under 'high' as under
# 'highest', and flat_sharded vs flat (refine=0, m = 4) read 3.8e-4 at the
# trainer's p. That is as large as the one-bf16-pass error, so this bound
# catches a structural fault (a wrong slab, mask, layout or psum is off by
# order 1), not a lost precision. flat's vector apply (m = 1) reduces to
# 6e-8.
REL_TOL = 1e-3
# query-block width of the four-chip check: its reference, the unsharded
# flat apply, holds the (p, m) f32 block and its answer on one chip
SHARDED_M = 4


def yi9b_cut():
    """yi-9b at published widths, depth and vocabulary cut to fit one v5e."""
    full = get_config('yi_9b')
    cfg = dataclasses.replace(full, n_layers=1, vocab_size=8000)
    print(f'[smoke] yi-9b cut: n_layers {full.n_layers} -> {cfg.n_layers} '
          f'(one whole scan period of {full.block_period} layer)')
    print(f'[smoke] yi-9b cut: vocab_size {full.vocab_size} -> '
          f'{cfg.vocab_size} (an eighth; TokenStream draws its ids from '
          f'the first {min(cfg.vocab_size, 512)})')
    print(f'[smoke] yi-9b widths as published: d_model={cfg.d_model} '
          f'heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim} '
          f'd_ff={cfg.d_ff}; weights random (seed {SEED})')
    print(f'[smoke] sketch rank k={K}, cut from 8: at k=4 a v5e refused to '
          'load the outer step (6.91 GB of temporaries asked, 5.95 GB free)')
    return cfg


def param_count(cfg) -> int:
    """p: the number of scalars in the model's parameter tree."""
    tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(SEED))
    return sum(x.size for x in jax.tree.leaves(tree))


def trainer_phase(cfg) -> None:
    """8 inner steps and 2 outer steps of the bilevel LM trainer."""
    args = build_parser().parse_args(
        ['--steps', '8', '--outer-every', '4', '--k', str(K),
         '--log-every', '1'])
    run = train_lm(cfg, args)
    if len(run.losses) != 8 or not all(map(math.isfinite, run.losses)):
        raise RuntimeError(f'inner losses not 8 finite values: {run.losses}')
    norms = [o['hypergrad_norm'] for o in run.outer]
    if len(norms) != 2 or not all(math.isfinite(n) and n > 0 for n in norms):
        raise RuntimeError(f'hypergradient norms not 2 finite nonzero '
                           f'values: {norms}')
    print(f'[smoke] trainer ok: losses={run.losses} hypergrad_norms={norms} '
          f'compile_s={run.compile_s} '
          f'peak_bytes_in_use={run.peak_bytes_in_use}', flush=True)


def ihvp_programs(p: int, k: int, backend, refine: int = 1):
    """(solver, make_G, prepare, query, apply) for the seeded curvature
    H = GᵀG; all but make_G jitted.

    G (k, p) bf16 is drawn with entries of variance ρ/p, so H's k nonzero
    eigenvalues are ≈ ρ and a rank-k Nyström sketch of it is exact.
    ``prepare(G, key)`` runs ``NystromIHVP.prepare`` on H's HVP;
    ``query(G, Z)`` is the block V = GᵀZ (p, m), which lies in H's range —
    where (H + ρI)⁻¹ differs most from 1/ρ, so the Woodbury correction is
    about half the answer; ``apply(sketch, V)`` runs ``apply_matrix`` and
    returns U (p, m).
    """
    solver = NystromIHVP(k=k, rho=RHO, backend=backend, refine=refine)
    indexer = PyTreeIndexer({'w': jax.ShapeDtypeStruct((p,), jnp.float32)})

    def make_G(key):
        return (jax.random.normal(key, (k, p)) * (RHO / p) ** 0.5).astype(
            jnp.bfloat16)

    def hvp_of(G):
        def hvp(v):
            Gv = jnp.einsum('kp,p->k', G, v['w'],
                            preferred_element_type=jnp.float32)
            return {'w': jnp.einsum('kp,k->p', G, Gv,
                                    preferred_element_type=jnp.float32)}
        return hvp

    def query(G, Z):
        return {'w': jnp.einsum('kp,km->pm', G, Z,
                                preferred_element_type=jnp.float32)}

    prepare = jax.jit(lambda G, key: solver.prepare(hvp_of(G), indexer, key))
    apply = jax.jit(lambda s, V: solver.apply_matrix(s, V)['w'])
    return solver, make_G, prepare, jax.jit(query), apply


def run_ihvp(p: int, k: int, m: int, backend, refine: int = 1):
    """One seeded prepare + apply_matrix of m queries on one device under
    HIGHEST matmul precision; returns (U, sketch, V) on the device."""
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    with jax.default_matmul_precision('highest'):
        _, make_G, prepare, query, apply = ihvp_programs(p, k, backend,
                                                         refine)
        G = jax.jit(make_G)(keys[0])
        V = query(G, jax.random.normal(keys[1], (k, m)))
        sketch = prepare(G, keys[2])
        return apply(sketch, V), sketch, V


def exact_apply(B, V) -> np.ndarray:
    """(BᵀB + ρI)⁻¹ V in float64 on the host.

    B (k, p) is a prepared sketch's whitened factor, H_k = BᵀB, exactly as
    stored (bf16); V (p, m) the query block. Through Woodbury the answer is
    (V − Bᵀ(BBᵀ + ρI)⁻¹BV)/ρ, a k×k solve, so it is exact to float64
    rounding: the answer every apply of that sketch should reach, whatever
    the sketch's own storage error.
    """
    B = np.asarray(B, np.float64)
    V = np.asarray(V, np.float64)
    W = np.linalg.solve(B @ B.T + RHO * np.eye(len(B)), B @ V)
    return (V - B.T @ W) / RHO


def rel_err(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def kernel_phase(p: int, k: int, m: int) -> None:
    """Pallas and flat through prepare/apply_matrix, each against the
    float64 answer for its own sketch, and against each other."""
    bf16 = jnp.bfloat16
    U, err = {}, {}
    for name, backend in (
            ('flat', FlatBackend(sketch_dtype=bf16)),
            ('pallas', PallasBackend(sketch_dtype=bf16, interpret=False))):
        u, sketch, V = run_ihvp(p, k, m, backend)
        U[name] = np.asarray(u)
        if not np.all(np.isfinite(U[name])):
            raise RuntimeError(f'{name} apply at p={p} m={m} is not finite')
        err[name] = rel_err(U[name], exact_apply(jax.device_get(sketch.B),
                                                 jax.device_get(V['w'])))
        del u, sketch, V
    vs_flat = rel_err(U['pallas'], U['flat'])
    print(f'[smoke] kernels p={p} k={k} m={m}: |u - u_f64|/|u_f64| '
          f'pallas={err["pallas"]:.3e} (tolerance {PALLAS_TOL:.0e}) '
          f'flat={err["flat"]:.3e}; |u_pallas - u_flat|/|u_flat|='
          f'{vs_flat:.3e} (tolerance {REL_TOL:.0e})', flush=True)
    if not err['pallas'] <= PALLAS_TOL:
        raise RuntimeError(f'pallas at p={p} m={m} is {err["pallas"]:.3e} '
                           f'off float64, over {PALLAS_TOL:.0e}')
    if not (err['flat'] <= REL_TOL and vs_flat <= REL_TOL):
        raise RuntimeError(f'flat at p={p} m={m} is {err["flat"]:.3e} off '
                           f'float64 and {vs_flat:.3e} off pallas, over '
                           f'{REL_TOL:.0e}')


def sharded_phase(p: int, k: int, m: int) -> None:
    """flat_sharded on a 4-device mesh against flat on one device, both
    the two-pass apply (refine=0) that ``FLAT_SHARDED_CONTRACT`` covers."""
    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ('model',))
    sharded = FlatShardedBackend(mesh=mesh, specs={'w': P('model')},
                                 sketch_dtype=jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    with jax.default_matmul_precision('highest'):
        solver, make_G, prepare, query, apply = ihvp_programs(
            p, k, sharded, refine=0)
        G = jax.jit(make_G, out_shardings=NamedSharding(
            mesh, P(None, 'model')))(keys[0])
        V = jax.jit(query, out_shardings={'w': NamedSharding(
            mesh, P('model', None))})(G, jax.random.normal(keys[1], (k, m)))
        sketch = prepare(G, keys[2])
        for name, x in (('G', G), ('sketch C', sketch.C.buf),
                        ('sketch B', sketch.B.buf), ('V', V['w'])):
            whole = [s.device for s in x.addressable_shards
                     if s.data.size == x.size]
            if whole or len(x.sharding.device_set) != len(devices):
                raise RuntimeError(f'{name} {x.shape} is not split over '
                                   f'the mesh: {x.sharding}')
        report = audit(solver.apply_matrix, sketch, V, compile=True)
        FLAT_SHARDED_CONTRACT.enforce(report)
        n_psum = report.counts('hlo').get('all-reduce', 0)
        if n_psum != 1:
            raise RuntimeError(f'compiled flat_sharded apply has {n_psum} '
                               'all-reduces, expected 1')
        got = apply(sketch, V)
        if len(got.sharding.device_set) != len(devices):
            raise RuntimeError(f'apply output not sharded: {got.sharding}')
        got = np.asarray(got)
        del sketch, G, V
    want, _, _ = run_ihvp(p, k, m, FlatBackend(sketch_dtype=jnp.bfloat16),
                          refine=0)
    err = rel_err(got, np.asarray(want))
    print(f'[smoke] flat_sharded 4 devices p={p} k={k} m={m}: one psum, no '
          f'all-gather; |u_sharded - u_flat|/|u_flat| = {err:.3e} '
          f'(tolerance {REL_TOL:.0e})', flush=True)
    if not err <= REL_TOL:
        raise RuntimeError(f'flat_sharded and flat disagree: {err:.3e}')


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        sys.exit(f'chip_smoke: no TPU — JAX found {len(devices)} '
                 f'{devices[0].platform} device(s)')
    if len(devices) < args.chips:
        sys.exit(f'chip_smoke: --chips {args.chips} needs {args.chips} TPU '
                 f'devices, JAX found {len(devices)}')
    cfg = yi9b_cut()
    p = param_count(cfg)
    if args.chips == 1:
        trainer_phase(cfg)
        kernel_phase(p, K, 1)
        # a (p, 32) f32 block at the trainer's p is 30.6 GB, more than the
        # chip holds: m = 32 runs at p/32, the same bytes per block as m = 1
        kernel_phase(p // 32, K, 32)
    else:
        sharded_phase(p, K, SHARDED_M)
    print(json.dumps({'ok': True, 'device': {
        'platform': devices[0].platform, 'kind': devices[0].device_kind,
        'count': len(devices)}}))


if __name__ == '__main__':
    main()

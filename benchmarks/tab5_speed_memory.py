"""Tab. 5: hypergradient speed & memory by solver, and IHVP apply-time by
contraction backend.

No GPU in-container: we report (a) CPU wall-clock per hypergradient on a
~0.3M-param MLP (relative speeds are meaningful: the same HVP primitives
dominate), and (b) the analytic cost model that transfers to TPU —
sequential-HVP count (latency-critical: CG/Neumann chain l HVPs; Nyström's
k column-HVPs are batchable) and sketch-memory bytes (Nyström's O(kp) vs
O(p) — the paper's Tab. 5 memory column).

``run_backend_apply`` times the Nyström apply under the contraction
backends (tree | flat | pallas) over pytrees of growing leaf count at fixed
total p: the tree backend pays per-leaf einsum dispatch that grows with leaf
count, the flat backend is one fused matmul per pass regardless, and pallas
(timed only on a TPU, the one backend its kernels compile for; its cost
model is in benchmarks/roofline.py). Each row also
reports the resident sketch-buffer memory (C plus the whitened factor B),
for f32 and — on the flat family — bf16 sketch storage, so the
docs/backends.md table cites reproducible numbers. All apply timings go
through ``apply_matrix`` (the block path; a width-1 block statically
dispatches to the vector apply, so m=1 rows are the old numbers).

``run_block_apply`` is the headline loop-vs-block measurement: m IHVP
queries served by m jitted vector applies in a Python loop (the
pre-block-path idiom this bench used to hard-code) vs ONE
``apply_matrix`` call on a ``(p, m)`` query block. ``applies_per_sec``
counts queries served per second, so the two rows are directly comparable
at each m — the block path re-reads the O(kp) sketch once instead of m
times, which is where the ≥3× win at m≥32 comes from on CPU.

``run_sharded_backend_apply`` times flat_sharded vs tree on a mesh over all
visible devices; on a 1-device host it emits a SKIPPED row with the
XLA_FLAGS incantation instead (the host device count is fixed before jax
initializes, so this process cannot grow a mesh itself).

All apply rows are persisted as ``BENCH_tab5_apply.json`` (see
benchmarks/common.py for the schema contract).
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import bench_row, emit, solver_cfg, write_bench
from repro.core import (FlatBackend, FlatShardedBackend, NystromIHVP,
                        PallasBackend, PyTreeIndexer, hypergradient,
                        make_hvp, tree_random_like)
from repro.tasks import build_reweighting


def run(sizes=(5, 10, 20), reps: int = 3):
    task = build_reweighting(imbalance=50)
    params = task.init_params(jax.random.PRNGKey(0))
    hp = task.init_hparams(jax.random.PRNGKey(1))
    p_count = sum(x.size for x in jax.tree.leaves(params))
    data = task.reference['dataset']          # the raw seed-stream dataset
    batch = data.train_batch(0, 128)
    vbatch = data.val_batch(0, 128)
    idxr = PyTreeIndexer(params)
    out = {}
    for method in ('cg', 'neumann', 'nystrom'):
        for lk in sizes:
            cfg = solver_cfg(method, k=lk, rho=1e-2, alpha=1e-2)
            solver = cfg.build()

            @jax.jit
            def hg(params, hp, key):
                return hypergradient(task.inner_loss, task.outer_loss, params,
                                     hp, batch, vbatch, solver, key, idxr)

            hg(params, hp, jax.random.PRNGKey(2))  # warmup/compile
            t0 = time.time()
            for r in range(reps):
                jax.block_until_ready(hg(params, hp, jax.random.PRNGKey(r)))
            per = (time.time() - t0) / reps
            seq_hvps = lk if method in ('cg', 'neumann') else 0  # Nyström: k parallel
            sketch_mb = (lk * p_count * 4 / 1e6) if method == 'nystrom' else 0.0
            out[(method, lk)] = per
            emit('tab5_speed_memory', per * 1e6,
                 f'method={method} l_or_k={lk} wall_s={per:.4f} '
                 f'sequential_hvps={seq_hvps} sketch_MB={sketch_mb:.1f}')
    # space-efficient variant timing (κ=1): same sketch, chunked apply
    from repro.core import NystromIHVP
    for lk in sizes:
        solver = NystromIHVP(k=lk, rho=1e-2, kappa=1)

        @jax.jit
        def hg2(params, hp, key):
            return hypergradient(task.inner_loss, task.outer_loss, params, hp,
                                 batch, vbatch, solver, key, idxr)

        # repro: allow[prng-key-reuse] — same keys as the method loop above,
        # deliberately: identical sketch draws make the timings comparable
        hg2(params, hp, jax.random.PRNGKey(2))
        t0 = time.time()
        for r in range(reps):
            # repro: allow[prng-key-reuse] — see above: shared keys by design
            jax.block_until_ready(hg2(params, hp, jax.random.PRNGKey(r)))
        per = (time.time() - t0) / reps
        emit('tab5_speed_memory', per * 1e6,
             f'method=nystrom_kappa1 l_or_k={lk} wall_s={per:.4f} '
             f'sequential_hvps=0 sketch_MB={4*p_count/1e6:.1f}(peak κp)')
    rows = []
    out.update(run_block_apply(rows=rows))
    out.update(run_backend_apply(rows=rows))
    out.update(run_sharded_backend_apply(rows=rows))
    write_bench('tab5_apply', rows,
                meta=dict(device=jax.default_backend(),
                          n_devices=jax.device_count()))
    return out


def _sketch_bytes(sketch) -> int:
    """Resident bytes of the prepared sketch's p-sized state: the operand C
    plus the whitened factor B (flat_sharded's ShardedOperand counts its
    per-device rows once each — replicated leaves genuinely occupy a copy
    per device there)."""
    return sum(x.nbytes for part in (sketch.C, sketch.B) if part is not None
               for x in jax.tree.leaves(part))


def _leafy_params(n_leaves: int, p_total: int) -> dict:
    """n_leaves equal 2-D leaves summing to ~p_total params (an MLP-shaped
    tree: the multi-leaf case the tree backend pays per-leaf dispatch on)."""
    rows = max(1, p_total // (n_leaves * 64))
    return {f'layer{i:02d}': jnp.zeros((rows, 64)) for i in range(n_leaves)}


def _diag_quadratic_hvp(params, idxr):
    """HVP of a diagonal quadratic over ``params`` — sketch construction is
    cheap, so apply-path timing is isolated (what amortization makes hot)."""
    p_count = idxr.total
    d = 1.0 + jnp.arange(p_count, dtype=jnp.float32) / p_count

    def inner(prm, hp, batch):
        th = jnp.concatenate([x.ravel() for x in jax.tree.leaves(prm)])
        return 0.5 * jnp.sum(d * th * th)

    return make_hvp(inner, params, None, None)


def run_block_apply(m_values=(1, 8, 32), n_leaves=8, p_total=1 << 18, k=32,
                    reps: int = 5, rows=None):
    """Headline loop-vs-block row: m queries via m jitted vector applies
    (Python loop — the old idiom) vs one ``apply_matrix`` on a (p, m) block.
    """
    params = _leafy_params(n_leaves, p_total)
    idxr = PyTreeIndexer(params)
    p_count = idxr.total
    hvp = _diag_quadratic_hvp(params, idxr)
    out = {}
    for backend, be in (('tree', 'tree'), ('flat', 'flat')):
        solver = NystromIHVP(k=k, rho=1e-2, backend=be)
        sketch = jax.block_until_ready(
            solver.prepare(hvp, idxr, jax.random.PRNGKey(1)))
        apply_vec = jax.jit(solver.apply)
        apply_blk = jax.jit(solver.apply_matrix)
        for m in m_values:
            cols = [tree_random_like(kk, params)
                    for kk in jax.random.split(jax.random.PRNGKey(2), m)]
            Vm = jax.tree.map(lambda *ls: jnp.stack(ls, axis=-1), *cols)

            def loop_once():
                return [apply_vec(sketch, c) for c in cols]

            jax.block_until_ready(loop_once())           # warmup/compile
            jax.block_until_ready(apply_blk(sketch, Vm))
            t0 = time.time()
            for _ in range(reps):
                jax.block_until_ready(loop_once())
            loop_per = (time.time() - t0) / reps
            t0 = time.time()
            for _ in range(reps):
                jax.block_until_ready(apply_blk(sketch, Vm))
            blk_per = (time.time() - t0) / reps
            if rows is not None:
                # hvp_count=0: the timed region is the pure apply path — the
                # sketch (and its k HVPs) amortizes outside the clock
                rows.append(bench_row(
                    solver='nystrom', backend=backend, m=m,
                    applies_per_sec=m / loop_per, wall_seconds=loop_per,
                    problem='synthetic_quadratic', hvp_count=0,
                    path='loop', p=p_count, k=k, n_leaves=n_leaves))
                rows.append(bench_row(
                    solver='nystrom', backend=backend, m=m,
                    applies_per_sec=m / blk_per, wall_seconds=blk_per,
                    problem='synthetic_quadratic', hvp_count=0,
                    path='block', p=p_count, k=k, n_leaves=n_leaves))
            out[('block_apply', backend, m)] = (loop_per, blk_per)
            emit('tab5_block_apply', blk_per * 1e6,
                 f'backend={backend} m={m} p={p_count} k={k} '
                 f'loop_s={loop_per:.5f} block_s={blk_per:.5f} '
                 f'block_speedup={loop_per / blk_per:.2f}x')
    best = max(loop / blk for (_, _, m), (loop, blk) in out.items()
               if m >= 32)
    emit('tab5_block_apply', 0.0,
         f'headline m>=32 block_vs_loop_speedup={best:.2f}x')
    return out


def run_backend_apply(leaf_counts=(2, 8, 32), p_total=1 << 18, k=32,
                      reps: int = 20, include_pallas: bool = True,
                      rows=None):
    """Apply-time by contraction backend at fixed p, growing leaf count.

    The quadratic inner loss is diagonal so sketch construction is cheap and
    the timing isolates the apply path (two tall-skinny contractions) —
    which is what sketch amortization makes hot in production. Timed through
    ``apply_matrix`` on a width-1 block (statically the vector apply).
    """
    out = {}
    for n_leaves in leaf_counts:
        params = _leafy_params(n_leaves, p_total)
        idxr = PyTreeIndexer(params)
        p_count = idxr.total
        hvp = _diag_quadratic_hvp(params, idxr)
        v1 = jax.tree.map(lambda x: x[..., None],
                          tree_random_like(jax.random.PRNGKey(0), params))
        backends = [('tree', 'tree'), ('flat', 'flat'),
                    ('flat_bf16', FlatBackend(sketch_dtype=jnp.bfloat16))]
        # the Pallas kernels compile only for a TPU; elsewhere there is no
        # pallas row (interpret mode is a test path, not a timing)
        if include_pallas and jax.default_backend() == 'tpu':
            backends.append(('pallas', PallasBackend(block_p=4096)))
        for backend, be in backends:
            solver = NystromIHVP(k=k, rho=1e-2, backend=be)
            sketch = solver.prepare(hvp, idxr, jax.random.PRNGKey(1))
            sketch = jax.block_until_ready(sketch)
            apply_fn = jax.jit(solver.apply_matrix)
            jax.block_until_ready(apply_fn(sketch, v1))     # warmup/compile
            t0 = time.time()
            for _ in range(reps):
                jax.block_until_ready(apply_fn(sketch, v1))
            per = (time.time() - t0) / reps
            out[('apply', backend, n_leaves)] = per
            if rows is not None:
                rows.append(bench_row(
                    solver='nystrom', backend=backend, m=1,
                    applies_per_sec=1.0 / per, wall_seconds=per,
                    problem='synthetic_quadratic', hvp_count=0,
                    path='block', p=p_count, k=k, n_leaves=n_leaves,
                    sketch_mb=_sketch_bytes(sketch) / 1e6))
            emit('tab5_backend_apply', per * 1e6,
                 f'backend={backend} n_leaves={n_leaves} p={p_count} k={k} '
                 f'apply_wall_s={per:.6f} '
                 f'sketch_MB={_sketch_bytes(sketch) / 1e6:.1f}')
        tree_t = out[('apply', 'tree', n_leaves)]
        flat_t = out[('apply', 'flat', n_leaves)]
        emit('tab5_backend_apply', 0.0,
             f'summary n_leaves={n_leaves} flat_speedup_vs_tree='
             f'{tree_t / flat_t:.2f}x')
    return out


def run_sharded_backend_apply(n_leaves: int = 16, p_total=1 << 18, k: int = 32,
                              reps: int = 20, rows=None):
    """flat_sharded vs tree apply-time on a mesh over every visible device.

    Every leaf's rows shard over the single 'model' axis except one
    deliberately replicated leaf, so the psum down-weighting path is always
    exercised. Emits f32 and bf16 sketch rows. On 1 visible device this
    emits a SKIPPED pointer instead — relaunch under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for the
    host-mesh numbers quoted in docs/backends.md.
    """
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = jax.device_count()
    out = {}
    if n_dev < 2:
        emit('tab5_sharded_apply', 0.0,
             'SKIPPED (1 device): rerun under '
             'XLA_FLAGS=--xla_force_host_platform_device_count=8')
        return out
    mesh = Mesh(np.array(jax.devices()), ('model',))
    params = _leafy_params(n_leaves, p_total)
    # rows divide n_dev for every leaf but one: 'layer00' stays replicated
    # so the 1/replication psum weighting is part of the measured path.
    specs = {name: (P() if name == 'layer00' else P('model', None))
             for name in params}
    idxr = PyTreeIndexer(params)
    p_count = idxr.total
    hvp = _diag_quadratic_hvp(params, idxr)
    v1 = jax.tree.map(lambda x: x[..., None],
                      tree_random_like(jax.random.PRNGKey(0), params))
    cases = {
        'tree': 'tree',
        'flat_sharded': FlatShardedBackend(mesh=mesh, specs=specs),
        'flat_sharded_bf16': FlatShardedBackend(mesh=mesh, specs=specs,
                                                sketch_dtype=jnp.bfloat16),
    }
    for name, be in cases.items():
        solver = NystromIHVP(k=k, rho=1e-2, backend=be)
        sketch = jax.block_until_ready(
            solver.prepare(hvp, idxr, jax.random.PRNGKey(1)))
        apply_fn = jax.jit(solver.apply_matrix)
        jax.block_until_ready(apply_fn(sketch, v1))         # warmup/compile
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(apply_fn(sketch, v1))
        per = (time.time() - t0) / reps
        out[('sharded_apply', name)] = per
        if rows is not None:
            rows.append(bench_row(
                solver='nystrom', backend=name, m=1,
                applies_per_sec=1.0 / per, wall_seconds=per,
                problem='synthetic_quadratic', hvp_count=0, path='block',
                p=p_count, k=k, n_leaves=n_leaves, n_dev=n_dev))
        emit('tab5_sharded_apply', per * 1e6,
             f'backend={name} n_dev={n_dev} n_leaves={n_leaves} p={p_count} '
             f'k={k} apply_wall_s={per:.6f} '
             f'sketch_MB={_sketch_bytes(sketch) / 1e6:.1f}')
    emit('tab5_sharded_apply', 0.0,
         f'summary n_dev={n_dev} sharded_speedup_vs_tree='
         f"{out[('sharded_apply', 'tree')] / out[('sharded_apply', 'flat_sharded')]:.2f}x")
    return out

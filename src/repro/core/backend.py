"""Pluggable tall-skinny contraction backends for the Nyström solver.

Everything expensive the Nyström IHVP does after the sketch HVPs is one of
four contractions against the tall-skinny operand C (p × k, p up to
billions, k ≤ a few hundred):

    ctv        t = Cᵀ v       → (k,)      (apply pass 1)
    cv         u = C w        → p-vector  (apply pass 2)
    gram       G = CᵀC        → (k, k)    (prepare / Eq. 6 core)
    mul_right  B = C M        → p × j     (spectral whitening, Alg. 1 U-mix)

The seed implementation ran each of these as a per-leaf ``jnp.einsum`` over
the parameter pytree plus a Python-level sum — n_leaves kernel launches and
n_leaves partial results per contraction, which is exactly the overhead the
paper's "matrix operations without iterations" claim says we should not pay.
A backend owns the operand representation and fuses the p-pass. Four ship
(full design doc: ``docs/backends.md``):

* ``tree``         — the seed behavior: C stays a parameter pytree with a
  leading k axis, contractions are per-leaf einsums. Never flattens a leaf,
  so multi-axis pjit shardings pass through untouched; the default and the
  parity oracle for the others.
* ``flat``         — the pytree is fused ONCE (at ``prepare()``) into a
  single sketch-major (k, p) buffer; every contraction is then one XLA
  matmul. One p-pass per contraction regardless of leaf count; wins on
  CPU/GPU/single-chip TPU whenever the tree has more than a few leaves.
  Flattening a pjit-sharded leaf all-gathers it — unsharded steps only.
* ``flat_sharded`` — ``flat``'s fusion under GSPMD sharding: each device
  fuses only its *local* parameter shards into a per-device (k, p_local)
  buffer inside ``shard_map`` (PartitionSpec rules from
  ``repro.distributed.sharding``), contractions run on the local buffer,
  and the reductions (``ctv``/``gram``/``cross``) finish with a psum of
  k (resp. k×k) floats across the mesh. Leaves replicated along some mesh
  axes are down-weighted by 1/replication so the psum never overcounts.
  No parameter leaf is ever all-gathered.
* ``pallas``       — ``flat``'s (k, p) buffer, read in place by the
  hand-tiled TPU kernels in ``repro.kernels`` for ``gram``/``ctv`` and the
  fused Woodbury pass-2 (``v/ρ + C w``): one HBM read of C per pass,
  VMEM-resident k-wide accumulator. The kernels compile only for a TPU;
  tests run them in interpret mode with an explicit ``interpret=True``.

All flat-family backends take ``sketch_dtype=`` (default f32): the fused
sketch buffer — the dominant O(kp) state — may be stored in bf16 while
every contraction still *accumulates* in f32 (XLA ``preferred_element_type``
/ the Pallas kernels' in-kernel upcast), halving sketch HBM at large p.

Vectors travel in the backend's native form: ``vec()`` converts a parameter
pytree once per apply, ``unvec()`` converts the result back (identity for
``tree``). ``NystromIHVP`` threads a backend instance through prepare/apply;
see ``repro.core.solvers``.

Matrix-valued queries ride the same contract with an ``m`` suffix: a *query
block* is a pytree whose every leaf carries the parameter shape plus one
trailing (m,) axis (m stacked cotangents). ``vecm``/``unvecm`` fuse it to the
backend's (p, m) form, ``ctm`` is CᵀV → (k, m), ``cm`` is C·W → block, and
``combinem`` the fused Woodbury pass 2 for all m queries in one C-read.
``flat_sharded`` finishes ``ctm`` with a single (k, m) psum — one collective
per apply pass regardless of m (contract details: ``docs/backends.md``).

Examples
--------
Fuse a two-leaf sketch (k=2) and run contractions under ``flat``
(``jax.tree.leaves`` orders dict keys, so 'b' precedes 'w'):

>>> import jax.numpy as jnp
>>> from repro.core.backend import get_backend
>>> C = {'b': jnp.ones((2, 2)), 'w': jnp.arange(6.0).reshape(2, 3)}
>>> v = {'b': jnp.full((2,), 2.0), 'w': jnp.ones((3,))}
>>> be = get_backend('flat')
>>> op = be.prepare_operand(C)          # fused sketch-major (k, p) buffer
>>> op.shape
(2, 5)
>>> [float(t) for t in be.ctv(op, be.vec(v))]                   # Cᵀv
[7.0, 16.0]
>>> [[float(g) for g in row] for row in be.gram(op)]            # CᵀC
[[7.0, 16.0], [16.0, 52.0]]

``flat_sharded`` produces the same numbers from per-device local buffers —
here on a trivial 1-device mesh; on a real mesh each device only ever
touches its own parameter shards:

>>> import jax, numpy as np
>>> from jax.sharding import Mesh, PartitionSpec as P
>>> mesh = Mesh(np.array(jax.devices()[:1]), ('model',))
>>> sb = get_backend('flat_sharded', mesh=mesh,
...                  specs={'b': P(), 'w': P(None, 'model')})
>>> sop = sb.prepare_operand(C)
>>> [float(t) for t in sb.ctv(sop, sb.vec(v))]
[7.0, 16.0]

bf16 sketch storage halves the buffer; contractions still accumulate f32:

>>> bf = get_backend('flat', sketch_dtype=jnp.bfloat16)
>>> str(bf.prepare_operand(C).dtype)
'bfloat16'
>>> [float(t) for t in bf.ctv(bf.prepare_operand(C), bf.vec(v))]
[7.0, 16.0]
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.auditor import Contract
from repro.core.tree_util import PyTree, tree_axpy, tree_scale, tree_sub

# ---------------------------------------------------------------------------
# Declared structural contracts.  These are the docstring guarantees above,
# written as checkable objects: tests (and any caller holding a lowered
# apply) audit the real program against them instead of grepping HLO text.
# ---------------------------------------------------------------------------

#: ``flat_sharded`` apply / apply_matrix (refine=0): the k-output reduction
#: is exactly ONE psum — a (k,) or (k, m) all-reduce — per apply pass, no
#: parameter leaf is ever all-gathered (in lowered StableHLO or in the
#: GSPMD-partitioned HLO), every contraction accumulates f32 even under
#: bf16 sketch storage, and nothing round-trips through the host.
FLAT_SHARDED_CONTRACT = Contract(
    name='flat_sharded apply',
    no_all_gather=True,
    exact_collectives={'psum': 1},
    min_accum_dtype='float32',
    min_reduction_dtype='float32',
    no_host_transfer=True,
)

#: bf16 sketch storage (any flat-family backend): the buffer may be bf16
#: but every dot accumulates f32 (``preferred_element_type``) and every
#: cross-device reduction carries f32 — storage precision never leaks into
#: accumulation.
BF16_SKETCH_CONTRACT = Contract(
    name='bf16 sketch contraction',
    min_accum_dtype='float32',
    min_reduction_dtype='float32',
)

# ---------------------------------------------------------------------------
# pytree <-> fused-buffer conversion (the one-time cost of the flat backends)
# ---------------------------------------------------------------------------


def flatten_sketch(C: PyTree, dtype=jnp.float32) -> jax.Array:
    """Fuse a leading-k pytree (leaves (k, *shape)) into one (k, p) buffer
    of ``dtype``, leaves concatenated in ``jax.tree.leaves`` order.

    Sketch-major (k, p) is the cache-friendly layout for XLA-on-CPU/GPU:
    every contraction streams contiguous p-rows (measured 35× over the
    transposed layout for Cᵀv at p=8M on CPU). The Pallas kernels stream
    this same buffer, one (k, block_p) slab per grid step."""
    cols = [c.astype(dtype).reshape(c.shape[0], -1)
            for c in jax.tree.leaves(C)]
    return jnp.concatenate(cols, axis=1)


def flatten_vec(v: PyTree) -> jax.Array:
    """Parameter pytree → (p,) f32, same leaf order as ``flatten_sketch``."""
    return jnp.concatenate([x.astype(jnp.float32).ravel()
                            for x in jax.tree.leaves(v)])


def unflatten_vec(u: jax.Array, like: PyTree) -> PyTree:
    """(p,) → pytree shaped/dtyped like ``like`` (the unflatten spec is read
    off the reference tree, so sketches never store shape metadata)."""
    leaves, treedef = jax.tree.flatten(like)
    outs, off = [], 0
    for l in leaves:
        outs.append(u[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return treedef.unflatten(outs)


def flatten_vecm(V: PyTree) -> jax.Array:
    """Query-block pytree (every leaf = param shape + trailing (m,)) →
    (p, m) f32, rows in ``flatten_vec``'s leaf order.

    A *query block* is the matrix-apply form of a parameter vector: m
    cotangents stacked on one trailing axis, so leaf ``(27, 37)`` travels as
    ``(27, 37, m)`` and a scalar leaf as ``(m,)``."""
    return jnp.concatenate(
        [x.astype(jnp.float32).reshape(-1, x.shape[-1])
         for x in jax.tree.leaves(V)], axis=0)


def unflatten_vecm(U: jax.Array, like: PyTree) -> PyTree:
    """(p, m) → query-block pytree shaped/dtyped like ``like`` (a reference
    block whose leaves already carry the trailing query axis)."""
    leaves, treedef = jax.tree.flatten(like)
    outs, off = [], 0
    for l in leaves:
        rows = l.size // l.shape[-1]
        outs.append(U[off:off + rows].reshape(l.shape).astype(l.dtype))
        off += rows
    return treedef.unflatten(outs)


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TreeBackend:
    """Per-leaf einsum contractions on the parameter pytree (seed behavior,
    pjit/sharding-transparent)."""
    name = 'tree'

    def prepare_operand(self, C: PyTree):
        return C

    def vec(self, v: PyTree):
        return v

    def unvec(self, u, like: PyTree) -> PyTree:
        del like
        return u

    def ctv(self, C, v) -> jax.Array:
        parts = jax.tree.leaves(jax.tree.map(
            lambda c, x: jnp.einsum('k...,...->k', c.astype(jnp.float32),
                                    x.astype(jnp.float32)), C, v))
        return sum(parts)

    def cv(self, C, w: jax.Array):
        return jax.tree.map(
            lambda c: jnp.einsum('k...,k->...', c.astype(jnp.float32), w), C)

    def gram(self, C) -> jax.Array:
        return self.cross(C, C)

    def cross(self, A, B) -> jax.Array:
        parts = jax.tree.leaves(jax.tree.map(
            lambda a, b: jnp.einsum('k...,j...->kj', a.astype(jnp.float32),
                                    b.astype(jnp.float32)), A, B))
        return sum(parts)

    def mul_right(self, C, M: jax.Array):
        return jax.tree.map(
            lambda c: jnp.einsum('k...,kj->j...', c.astype(jnp.float32), M), C)

    def slice_k(self, C, start: int, width: int):
        return jax.tree.map(
            lambda c: jax.lax.slice_in_dim(c, start, start + width, axis=0), C)

    def scale(self, x, s):
        return tree_scale(x, s)

    def sub(self, a, b):
        return tree_sub(a, b)

    def add(self, a, b):
        return jax.tree.map(jnp.add, a, b)

    def combine(self, C, w: jax.Array, v, rho: float):
        """u = v/ρ + C w (the fused Woodbury pass 2)."""
        return tree_axpy(1.0, self.cv(C, w), tree_scale(v, 1.0 / rho))

    # -- matrix-valued queries: trailing (m,) axis on every leaf ------------
    def vecm(self, V: PyTree):
        return V

    def unvecm(self, U, like: PyTree) -> PyTree:
        del like
        return U

    def ctm(self, C, V) -> jax.Array:
        """CᵀV over an m-query block → (k, m)."""
        parts = jax.tree.leaves(jax.tree.map(
            lambda c, x: jnp.einsum('k...,...m->km', c.astype(jnp.float32),
                                    x.astype(jnp.float32)), C, V))
        return sum(parts)

    def cm(self, C, W: jax.Array):
        """C W for W (k, m) → a query-block pytree."""
        return jax.tree.map(
            lambda c: jnp.einsum('k...,km->...m', c.astype(jnp.float32), W), C)

    def combinem(self, C, W: jax.Array, V, rho: float):
        """U = V/ρ + C W (the fused Woodbury pass 2, m queries at once)."""
        return tree_axpy(1.0, self.cm(C, W), tree_scale(V, 1.0 / rho))


@dataclasses.dataclass(frozen=True)
class FlatBackend:
    """One fused XLA matmul per contraction over the sketch-major (k, p)
    buffer (contiguous p-rows — see ``flatten_sketch``).

    ``sketch_dtype``: storage dtype of the fused buffer (bf16 halves sketch
    HBM); every contraction accumulates f32 via ``preferred_element_type``.
    """
    name = 'flat'
    sketch_dtype: Any = jnp.float32

    def prepare_operand(self, C: PyTree) -> jax.Array:
        return flatten_sketch(C, dtype=self.sketch_dtype)

    def vec(self, v: PyTree) -> jax.Array:
        return flatten_vec(v)

    def unvec(self, u: jax.Array, like: PyTree) -> PyTree:
        return unflatten_vec(u, like)

    def ctv(self, Ckp: jax.Array, vf: jax.Array) -> jax.Array:
        return jnp.einsum('kp,p->k', Ckp, vf,
                          preferred_element_type=jnp.float32)

    def cv(self, Ckp: jax.Array, w: jax.Array) -> jax.Array:
        return jnp.einsum('kp,k->p', Ckp, w,
                          preferred_element_type=jnp.float32)

    def gram(self, Ckp: jax.Array) -> jax.Array:
        return self.cross(Ckp, Ckp)

    def cross(self, Akp: jax.Array, Bkp: jax.Array) -> jax.Array:
        return jnp.einsum('kp,jp->kj', Akp, Bkp,
                          preferred_element_type=jnp.float32)

    def mul_right(self, Ckp: jax.Array, M: jax.Array) -> jax.Array:
        out = jnp.einsum('kp,kj->jp', Ckp, M,               # (j, p)
                         preferred_element_type=jnp.float32)
        return out.astype(self.sketch_dtype)

    def slice_k(self, Ckp: jax.Array, start: int, width: int) -> jax.Array:
        return jax.lax.slice_in_dim(Ckp, start, start + width, axis=0)

    def scale(self, x: jax.Array, s) -> jax.Array:
        return x * s

    def sub(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return a - b

    def add(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return a + b

    def combine(self, Ckp: jax.Array, w: jax.Array, vf: jax.Array,
                rho: float) -> jax.Array:
        return vf / rho + self.cv(Ckp, w)

    # -- matrix-valued queries: (p, m) fused blocks -------------------------
    def vecm(self, V: PyTree) -> jax.Array:
        return flatten_vecm(V)

    def unvecm(self, U: jax.Array, like: PyTree) -> PyTree:
        return unflatten_vecm(U, like)

    def ctm(self, Ckp: jax.Array, Vm: jax.Array) -> jax.Array:
        return jnp.einsum('kp,pm->km', Ckp, Vm,
                          preferred_element_type=jnp.float32)

    def cm(self, Ckp: jax.Array, W: jax.Array) -> jax.Array:
        return jnp.einsum('kp,km->pm', Ckp, W,
                          preferred_element_type=jnp.float32)

    def combinem(self, Ckp: jax.Array, W: jax.Array, Vm: jax.Array,
                 rho: float) -> jax.Array:
        return Vm / rho + self.cm(Ckp, W)


@dataclasses.dataclass(frozen=True)
class PallasBackend(FlatBackend):
    """FlatBackend's (k, p) buffer + Pallas TPU kernels for the C-streaming
    reduction passes.

    The kernels read the sketch-major buffer ``prepare_operand`` builds in
    place — no padded or transposed copy of C. ``interpret`` is the
    kernels' explicit mode: ``None`` (default) compiles them, which needs a
    TPU and raises elsewhere; tests pass ``interpret=True``. ``block_p`` is
    the p-tile the grid streams. gram/ctv/combine (and their block forms)
    run the kernels; ``cv``/``mul_right``/``cross``/``cm`` stay on
    FlatBackend's XLA matmuls, which already tile those p-output and
    k×k-output products well. ``sketch_dtype=bf16`` composes: the kernels
    upcast each streamed slab to f32 in VMEM, so HBM traffic and storage
    halve while accumulation stays f32.
    """
    name = 'pallas'
    interpret: bool | None = None
    block_p: int = 1024

    def ctv(self, Ckp: jax.Array, vf: jax.Array) -> jax.Array:
        from repro.kernels import ops
        return ops.woodbury_ctv(Ckp, vf, block_p=self.block_p,
                                interpret=self.interpret)

    def gram(self, Ckp: jax.Array) -> jax.Array:
        from repro.kernels import ops
        return ops.nystrom_gram(Ckp, block_p=self.block_p,
                                interpret=self.interpret)

    def combine(self, Ckp: jax.Array, w: jax.Array, vf: jax.Array,
                rho: float) -> jax.Array:
        from repro.kernels import ops
        # woodbury_apply computes v/ρ − Cᵀw̃/ρ²; w̃ = −ρ² w gives v/ρ + Cᵀw.
        return ops.woodbury_apply(Ckp, -(rho * rho) * w, vf, rho,
                                  block_p=self.block_p,
                                  interpret=self.interpret)

    # -- matrix-valued queries: the kernels stream query-major (m, p)
    # blocks, so the (p, m) block is handed over transposed. XLA lays the
    # block out p-minor, which makes each .T a bitcast, not an HBM copy
    # (tests/test_tpu_compile.py compiles the block apply to check) -------
    def ctm(self, Ckp: jax.Array, Vm: jax.Array) -> jax.Array:
        from repro.kernels import ops
        return ops.woodbury_ctv(Ckp, Vm.T, block_p=self.block_p,
                                interpret=self.interpret)

    def combinem(self, Ckp: jax.Array, W: jax.Array, Vm: jax.Array,
                 rho: float) -> jax.Array:
        from repro.kernels import ops
        return ops.woodbury_apply(Ckp, -(rho * rho) * W, Vm.T, rho,
                                  block_p=self.block_p,
                                  interpret=self.interpret).T


# ---------------------------------------------------------------------------
# flat_sharded: per-device fused buffers + psum reductions
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedOperand:
    """FlatShardedBackend's operand: per-device fused buffer + psum weights.

    ``buf`` is (n_dev, k, p_local), sharded so device d holds exactly the
    (1, k, p_local) row it fused from its own parameter shards — the global
    leading axis is the mesh itself (P(mesh.axis_names, None, None)).
    ``w`` is the (p_local,) reduction-weight vector: column j carries
    1/replication(leaf(j)), so a psum over every mesh axis counts each
    *distinct* parameter exactly once even when some leaves are replicated
    along some axes. The weights ride with the operand (not the backend) so
    a prepared sketch is self-describing across applies.
    """
    buf: jax.Array
    w: jax.Array


@dataclasses.dataclass(frozen=True, eq=False)
class FlatShardedBackend:
    """``flat``'s one-matmul-per-contraction fusion under GSPMD sharding.

    ``prepare_operand`` runs inside ``shard_map``: each device flattens and
    concatenates only its local blocks of each sketch leaf into a
    (k, p_local) buffer — a pjit-sharded leaf is never all-gathered (the
    failure mode that forced sharded steps onto the ``tree`` backend).
    Contractions then run on the local buffer; the k-output reductions
    (``ctv``, and ``gram``/``cross`` at k×k) finish with one
    ``jax.lax.psum`` over every mesh axis, down-weighting columns of
    replicated leaves by 1/replication so nothing is overcounted. p-output
    passes (``cv``/``mul_right``/``combine``) are purely local and their
    results stay sharded exactly like the parameters.

    ``specs`` is a PartitionSpec pytree matching the parameter structure
    (e.g. ``repro.distributed.sharding.param_specs(cfg, mesh)``); entries
    that cannot shard a leaf on ``mesh`` degrade to replication via
    ``sanitize_spec`` — never error — so any (arch × mesh) combination is
    accepted, including the non-divisible-leaf fallback. ``specs=None``
    replicates everything (correct, no memory win). ``sketch_dtype=bf16``
    stores the per-device buffers half-size; reductions accumulate f32.
    """
    name = 'flat_sharded'
    mesh: Any = None
    specs: Any = None
    sketch_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.mesh is None:
            raise ValueError(
                "flat_sharded requires a mesh: get_backend('flat_sharded', "
                "mesh=mesh, specs=param_spec_tree)")

    # -- static shard planning (host-side; specs × mesh × leaf shapes) ------
    def _axes(self) -> tuple:
        return tuple(self.mesh.axis_names)

    def _plan(self, tree, lead: int, trail: int = 0):
        """Per-leaf (sanitized spec, local shape/size, psum weight), in
        ``jax.tree.leaves`` order; ``lead`` leading unsharded dims (the
        sketch's k axis) and ``trail`` trailing unsharded dims (a query
        block's m axis) are stripped before planning."""
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import (local_shape,
                                                replication_factor,
                                                sanitize_spec)
        leaves = jax.tree.leaves(tree)
        if self.specs is None:
            spec_leaves = [P()] * len(leaves)
        else:
            spec_leaves = jax.tree.structure(tree).flatten_up_to(self.specs)
        plan = []
        for leaf, sp in zip(leaves, spec_leaves):
            gshape = tuple(leaf.shape)[lead:len(leaf.shape) - trail]
            sp = sanitize_spec(gshape, sp, self.mesh)
            lshape = local_shape(gshape, sp, self.mesh)
            lsize = int(np.prod(lshape, dtype=np.int64)) if lshape else 1
            weight = 1.0 / replication_factor(sp, self.mesh)
            plan.append((sp, lshape, lsize, weight))
        return plan

    def _weight_vec(self, plan) -> jax.Array:
        segs = [jnp.full((lsize,), weight, jnp.float32)
                for _, _, lsize, weight in plan if lsize]
        return jnp.concatenate(segs)

    def _smap(self, f, in_specs, out_specs):
        from repro.distributed.ctx import shard_map_unchecked
        return shard_map_unchecked(f, self.mesh, in_specs, out_specs)

    def _op_spec(self, ndim: int):
        from jax.sharding import PartitionSpec as P
        return P(self._axes(), *([None] * (ndim - 1)))

    # -- pytree <-> per-device fused form -----------------------------------
    def prepare_operand(self, C: PyTree) -> ShardedOperand:
        from jax.sharding import PartitionSpec as P
        plan = self._plan(C, lead=1)
        leaves = jax.tree.leaves(C)

        def fuse(*ls):
            cols = [l.astype(self.sketch_dtype).reshape(l.shape[0], -1)
                    for l in ls]
            return jnp.concatenate(cols, axis=1)[None]      # (1, k, p_local)

        buf = self._smap(fuse,
                         tuple(P(None, *sp) for sp, _, _, _ in plan),
                         self._op_spec(3))(*leaves)
        return ShardedOperand(buf=buf, w=self._weight_vec(plan))

    def vec(self, v: PyTree) -> jax.Array:
        from jax.sharding import PartitionSpec as P
        plan = self._plan(v, lead=0)
        leaves = jax.tree.leaves(v)

        def fuse(*ls):
            return jnp.concatenate(
                [l.astype(jnp.float32).reshape(-1) for l in ls])[None]

        return self._smap(fuse, tuple(P(*sp) for sp, _, _, _ in plan),
                          self._op_spec(2))(*leaves)

    def unvec(self, u: jax.Array, like: PyTree) -> PyTree:
        from jax.sharding import PartitionSpec as P
        plan = self._plan(like, lead=0)
        leaves, treedef = jax.tree.flatten(like)
        dtypes = [l.dtype for l in leaves]

        def split(ub):
            u1, outs, off = ub[0], [], 0
            for (_, lshape, lsize, _), dt in zip(plan, dtypes):
                outs.append(u1[off:off + lsize].reshape(lshape).astype(dt))
                off += lsize
            return tuple(outs)

        outs = self._smap(split, (self._op_spec(2),),
                          tuple(P(*sp) for sp, _, _, _ in plan))(u)
        return treedef.unflatten(list(outs))

    # -- reductions: local fused contraction + k-float (k×k) psum -----------
    def ctv(self, C: ShardedOperand, vf: jax.Array) -> jax.Array:
        from jax.sharding import PartitionSpec as P
        axes = self._axes()

        def local(s, w, v):
            t = jnp.einsum('kp,p->k', s[0], v[0] * w,
                           preferred_element_type=jnp.float32)
            return jax.lax.psum(t, axes)

        return self._smap(local, (self._op_spec(3), P(None),
                                  self._op_spec(2)), P())(C.buf, C.w, vf)

    def gram(self, C: ShardedOperand) -> jax.Array:
        return self.cross(C, C)

    def cross(self, A: ShardedOperand, B: ShardedOperand) -> jax.Array:
        from jax.sharding import PartitionSpec as P
        axes = self._axes()

        def local(a, w, b):
            g = jnp.einsum('kp,jp->kj', a[0] * w, b[0],
                           preferred_element_type=jnp.float32)
            return jax.lax.psum(g, axes)

        return self._smap(local, (self._op_spec(3), P(None),
                                  self._op_spec(3)), P())(A.buf, A.w, B.buf)

    # -- p-output passes: purely local, results stay parameter-sharded ------
    def cv(self, C: ShardedOperand, w: jax.Array) -> jax.Array:
        from jax.sharding import PartitionSpec as P

        def local(s, wk):
            return jnp.einsum('kp,k->p', s[0], wk,
                              preferred_element_type=jnp.float32)[None]

        return self._smap(local, (self._op_spec(3), P(None)),
                          self._op_spec(2))(C.buf, w)

    def mul_right(self, C: ShardedOperand, M: jax.Array) -> ShardedOperand:
        from jax.sharding import PartitionSpec as P

        def local(s, m):
            out = jnp.einsum('kp,kj->jp', s[0], m,
                             preferred_element_type=jnp.float32)
            return out[None].astype(self.sketch_dtype)

        buf = self._smap(local, (self._op_spec(3), P(None, None)),
                         self._op_spec(3))(C.buf, M)
        return ShardedOperand(buf=buf, w=C.w)

    def combine(self, C: ShardedOperand, w: jax.Array, vf: jax.Array,
                rho: float) -> jax.Array:
        from jax.sharding import PartitionSpec as P

        def local(s, wk, v):
            u = v[0] / rho + jnp.einsum('kp,k->p', s[0], wk,
                                        preferred_element_type=jnp.float32)
            return u[None]

        return self._smap(local, (self._op_spec(3), P(None),
                                  self._op_spec(2)),
                          self._op_spec(2))(C.buf, w, vf)

    # -- matrix-valued queries: local (p_local, m) blocks, ONE (k, m) psum --
    def vecm(self, V: PyTree) -> jax.Array:
        """Query-block pytree (leaves = param shape + (m,)) → per-device
        (1, p_local, m) fused block; the trailing m axis is never sharded."""
        from jax.sharding import PartitionSpec as P
        plan = self._plan(V, lead=0, trail=1)
        leaves = jax.tree.leaves(V)

        def fuse(*ls):
            return jnp.concatenate(
                [l.astype(jnp.float32).reshape(-1, l.shape[-1])
                 for l in ls], axis=0)[None]

        return self._smap(fuse, tuple(P(*sp, None) for sp, _, _, _ in plan),
                          self._op_spec(3))(*leaves)

    def unvecm(self, U: jax.Array, like: PyTree) -> PyTree:
        from jax.sharding import PartitionSpec as P
        plan = self._plan(like, lead=0, trail=1)
        leaves, treedef = jax.tree.flatten(like)
        dtypes = [l.dtype for l in leaves]

        def split(ub):
            u1, outs, off = ub[0], [], 0
            for (_, lshape, lsize, _), dt in zip(plan, dtypes):
                outs.append(u1[off:off + lsize]
                            .reshape(lshape + (u1.shape[-1],)).astype(dt))
                off += lsize
            return tuple(outs)

        outs = self._smap(split, (self._op_spec(3),),
                          tuple(P(*sp, None) for sp, _, _, _ in plan))(U)
        return treedef.unflatten(list(outs))

    def ctm(self, C: ShardedOperand, Vm: jax.Array) -> jax.Array:
        """CᵀV over an m-query block → (k, m): the local contraction covers
        the whole block, so exactly ONE psum of (k, m) floats crosses the
        mesh per apply pass — not m separate k-float psums."""
        from jax.sharding import PartitionSpec as P
        axes = self._axes()

        def local(s, w, v):
            t = jnp.einsum('kp,pm->km', s[0], v[0] * w[:, None],
                           preferred_element_type=jnp.float32)
            return jax.lax.psum(t, axes)

        return self._smap(local, (self._op_spec(3), P(None),
                                  self._op_spec(3)), P())(C.buf, C.w, Vm)

    def cm(self, C: ShardedOperand, W: jax.Array) -> jax.Array:
        from jax.sharding import PartitionSpec as P

        def local(s, wm):
            return jnp.einsum('kp,km->pm', s[0], wm,
                              preferred_element_type=jnp.float32)[None]

        return self._smap(local, (self._op_spec(3), P(None, None)),
                          self._op_spec(3))(C.buf, W)

    def combinem(self, C: ShardedOperand, W: jax.Array, Vm: jax.Array,
                 rho: float) -> jax.Array:
        from jax.sharding import PartitionSpec as P

        def local(s, wm, v):
            u = v[0] / rho + jnp.einsum('kp,km->pm', s[0], wm,
                                        preferred_element_type=jnp.float32)
            return u[None]

        return self._smap(local, (self._op_spec(3), P(None, None),
                                  self._op_spec(3)),
                          self._op_spec(3))(C.buf, W, Vm)

    # -- structural helpers (operand- and vector-form aware) ----------------
    def slice_k(self, C: ShardedOperand, start: int,
                width: int) -> ShardedOperand:
        return ShardedOperand(
            buf=jax.lax.slice_in_dim(C.buf, start, start + width, axis=1),
            w=C.w)

    def scale(self, x, s):
        if isinstance(x, ShardedOperand):
            return ShardedOperand(buf=x.buf * s, w=x.w)
        return x * s

    def sub(self, a, b):
        if isinstance(a, ShardedOperand):
            return ShardedOperand(buf=a.buf - b.buf, w=a.w)
        return a - b

    def add(self, a, b):
        if isinstance(a, ShardedOperand):
            return ShardedOperand(buf=a.buf + b.buf, w=a.w)
        return a + b


BACKENDS = {'tree': TreeBackend, 'flat': FlatBackend,
            'flat_sharded': FlatShardedBackend, 'pallas': PallasBackend}


def get_backend(name: str, **kwargs):
    """'tree' | 'flat' | 'flat_sharded' | 'pallas' → backend instance.
    kwargs reach the backend constructor (``mesh=``/``specs=`` for
    flat_sharded, ``sketch_dtype=`` for the flat family, ``interpret=True``
    for pallas in tests).

    >>> get_backend('flat').name
    'flat'
    >>> get_backend('flat_sharded')
    Traceback (most recent call last):
        ...
    ValueError: flat_sharded requires a mesh: get_backend('flat_sharded', \
mesh=mesh, specs=param_spec_tree)
    """
    try:
        cls = BACKENDS[name]
    except KeyError:
        raise ValueError(
            f'unknown backend {name!r}; expected one of {sorted(BACKENDS)}')
    return cls(**kwargs)

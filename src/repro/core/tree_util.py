"""Pytree linear-algebra helpers.

Everything in ``repro.core`` treats model parameters as arbitrary pytrees; the
hypergradient math only ever needs the vector-space operations below, so that
a parameter tree sharded over a (pod, data, model) mesh behaves exactly like a
flat vector without ever being flattened on-device.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def tree_vdot(a: PyTree, b: PyTree) -> jax.Array:
    """<a, b> over all leaves (float32 accumulation).

    Uses elementwise-multiply + full reduce, NOT jnp.vdot: vdot flattens to
    1-D first, and flattening a multi-axis-sharded array forces GSPMD to
    all-gather the whole operand per device (measured: ~35 GB/chip on the
    yi-9b dry-run before this was fixed)."""
    leaves = jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)),
        a, b))
    return jnp.sum(jnp.stack(leaves)) if leaves else jnp.float32(0.0)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, a)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_zeros_like(a: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, a)


def tree_norm(a: PyTree) -> jax.Array:
    return jnp.sqrt(tree_vdot(a, a))


def tree_size(a: PyTree) -> int:
    """Total number of scalar parameters (static)."""
    return sum(int(x.size) for x in jax.tree.leaves(a))


def tree_cast(a: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype), a)


def tree_random_like(rng: jax.Array, a: PyTree, scale: float = 1.0) -> PyTree:
    """Gaussian pytree with the same structure/shapes as ``a``."""
    leaves, treedef = jax.tree.flatten(a)
    keys = jax.random.split(rng, len(leaves))
    out = [scale * jax.random.normal(k, l.shape, l.dtype) for k, l in zip(keys, leaves)]
    return treedef.unflatten(out)


# ---------------------------------------------------------------------------
# Global flat indexing across a pytree (used for Nyström column selection).
# ---------------------------------------------------------------------------
def sample_distinct(rng: jax.Array, n: int, k: int) -> jax.Array:
    """k distinct int32 indices from range(n), k ≤ n < 2³¹: a uniformly random
    ordered sample without replacement, in O(k²) scalar work.

    Floyd's algorithm draws a uniform k-subset: step i takes t uniform in
    [0, j], j = n − k + i, or j itself when t is already taken. Its order is
    not uniform (the last slot holds j more often than chance), and the
    chunked apply groups columns by position, so a length-k permutation
    shuffles it. ``jax.random.choice(replace=False)`` would instead permute
    all n indices (sorts of length n) to keep k."""
    keys = jax.random.split(rng, k + 1)

    def pick(i, taken):
        j = n - k + i
        t = jax.random.randint(keys[i], (), 0, j + 1, dtype=jnp.int32)
        return taken.at[i].set(jnp.where(jnp.any(taken == t), j, t))

    taken = jax.lax.fori_loop(0, k, pick, jnp.full((k,), -1, jnp.int32))
    return jax.random.permutation(keys[k], taken)


class PyTreeIndexer:
    """Maps parameter coordinates to one-hot tangent pytrees.

    Indices are *structured* — ``{'leaf': (k,) int32, 'dims': (k, R) int32}``
    with R = max leaf rank — never a global flat offset, so the scheme is
    int32-safe at any parameter count (a flat index overflows int32 beyond
    2.1B params; yi-9b already has 8.8B). The mapping is static shape
    information, so one-hots trace into jit with *dynamic* index values: a
    new random index set per outer step does not retrace.
    """

    def __init__(self, tree: PyTree):
        leaves, self.treedef = jax.tree.flatten(tree)
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(np.prod(s, dtype=np.int64)) for s in self.shapes]
        self.total = sum(self.sizes)
        self.max_rank = max((len(s) for s in self.shapes), default=1) or 1
        # (n_leaves, R) dim-size + row-major stride tables, padded with 1s
        L = len(self.shapes)
        self._dim_table = np.ones((L, self.max_rank), np.int32)
        self._stride_table = np.ones((L, self.max_rank), np.int32)
        for i, s in enumerate(self.shapes):
            for d, n in enumerate(s):
                assert n < 2 ** 31, (
                    f'leaf dim {n} exceeds int32; reshape the leaf — the '
                    'structured indexer is per-dimension int32')
                self._dim_table[i, d] = n
            stride = 1
            for d in range(len(s) - 1, -1, -1):
                self._stride_table[i, d] = stride
                stride *= s[d]

    # -- representation helpers -------------------------------------------
    def from_flat(self, flat: np.ndarray | list[int]) -> dict:
        """Concrete global flat indices → structured (host-side; tests/Exact)."""
        leaf_ids, dims = [], []
        offs = np.cumsum([0] + self.sizes)
        for f in np.asarray(flat, np.int64):
            lid = int(np.searchsorted(offs, f, 'right') - 1)
            local = int(f - offs[lid])
            coord = np.unravel_index(local, self.shapes[lid] or (1,))
            coord = list(coord) + [0] * (self.max_rank - len(coord))
            leaf_ids.append(lid)
            dims.append(coord)
        return {'leaf': jnp.asarray(leaf_ids, jnp.int32),
                'dims': jnp.asarray(dims, jnp.int32)}

    def one_hot(self, idx: dict) -> PyTree:
        """One-hot pytree for a single structured index
        ({'leaf': () int32, 'dims': (R,) int32}); traced values ok."""
        outs = []
        for lid, (shape, dtype) in enumerate(zip(self.shapes, self.dtypes)):
            mask = (idx['leaf'] == lid)
            oh = jnp.ones(shape or (), dtype)
            for d, n in enumerate(shape):
                eq = (jnp.arange(n, dtype=jnp.int32) == idx['dims'][d])
                oh = oh * eq.astype(dtype).reshape(
                    (1,) * d + (n,) + (1,) * (len(shape) - d - 1))
            outs.append(oh * mask.astype(dtype))
        return self.treedef.unflatten(outs)

    def one_hots(self, indices: dict) -> PyTree:
        """Batched one-hots: leaves carry a leading k axis."""
        return jax.vmap(self.one_hot)(indices)

    def gather(self, batched_tree: PyTree, indices: dict) -> jax.Array:
        """Entries of each batched-tree column at the structured indices:
        (k_batch, k_idx) — computed as a cross-contraction against the
        one-hot batch (fuses; no flat reshape of sharded leaves)."""
        oh = self.one_hots(indices)
        parts = jax.tree.leaves(jax.tree.map(
            lambda c, o: jnp.einsum('k...,j...->kj', c.astype(jnp.float32),
                                    o.astype(jnp.float32)), batched_tree, oh))
        return sum(parts)

    def _structure_flat_traced(self, flat: jax.Array) -> dict:
        """Traced flat→structured conversion (valid while p < 2³¹)."""
        offs = jnp.asarray(np.cumsum([0] + self.sizes[:-1]), jnp.int32)
        leaf = jnp.searchsorted(offs, flat, side='right') - 1
        local = flat - offs[leaf]
        strides = jnp.asarray(self._stride_table)[leaf]      # (k, R)
        sizes_k = jnp.asarray(self._dim_table)[leaf]
        dims = (local[:, None] // strides) % sizes_k
        return {'leaf': leaf.astype(jnp.int32), 'dims': dims.astype(jnp.int32)}

    def sample_indices(self, rng: jax.Array, k: int,
                       weights: jax.Array | None = None) -> dict:
        """k structured indices, uniform over all parameters.

        p < 2³¹: min(k, p) distinct flat indices, a uniformly random ordered
        sample without replacement (``sample_distinct``: O(k²) scalar work,
        nothing of length p), converted with traced int32 math; at k ≥ p
        every index once. Beyond int32 range: leaf ∝ size + per-dim uniform
        coordinates — with-replacement across the whole space (collision
        probability ≤ k²/2p, negligible at p ≥ 10⁹ and harmless: a duplicate
        column only lowers the sketch rank by one).

        ``weights`` (Drineas–Mahoney diag² sampling, Remark 1) requires a
        flat weight vector and is only supported when p < 2³¹; that draw,
        ``jax.random.choice(..., replace=False, p=...)``, is O(p)."""
        if self.total < 2 ** 31:
            kk = min(k, self.total)
            if weights is None:
                flat = sample_distinct(rng, self.total, kk)
            else:
                flat = jax.random.choice(rng, self.total, (kk,), replace=False,
                                         p=weights / weights.sum()
                                         ).astype(jnp.int32)
            return self._structure_flat_traced(flat)
        if weights is not None:
            raise ValueError('importance sampling needs p < 2^31')
        k_leaf, k_dims = jax.random.split(rng)
        # sizes exceed int32 here — go through float64 numpy, never jnp ints
        probs = jnp.asarray(np.asarray(self.sizes, np.float64)
                            / float(self.total), jnp.float32)
        leaf = jax.random.choice(k_leaf, len(self.sizes), (k,), p=probs)
        table = jnp.asarray(self._dim_table)                 # (L, R)
        sizes_k = table[leaf]                                # (k, R)
        u = jax.random.uniform(k_dims, (k, self.max_rank))
        dims = jnp.minimum((u * sizes_k).astype(jnp.int32), sizes_k - 1)
        return {'leaf': leaf.astype(jnp.int32), 'dims': dims}

    def all_indices(self) -> dict:
        """Every parameter (tiny models only — ExactIHVP)."""
        assert self.total < 2 ** 31
        return self.from_flat(np.arange(self.total))

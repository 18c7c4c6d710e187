"""Pallas TPU kernel: tall-skinny Gram matrix CᵀC for the Nyström sketch.

C is stored sketch-major, (k, p), with p up to billions (a sharded parameter
pytree flattens to a local p-shard per device) and k ≤ a few hundred — the
same buffer ``FlatBackend`` builds, read in place. TPU mapping:

  * each grid step streams one (k, block_p) slab HBM→VMEM: the block spans
    all of k (a full-extent block needs no 8/16-row alignment) and block_p
    lanes of p, so no padded or transposed copy of C is ever written to HBM;
  * the step issues one (k × block_p) @ (block_p × k) MXU matmul into a
    (k, k) accumulator whose index_map is constant (0, 0) — Pallas keeps it
    resident in VMEM across the whole grid and writes HBM exactly once
    (arithmetic intensity ≈ k FLOPs/byte, the roofline optimum here);
  * when block_p does not divide p the last slab overhangs the array; its
    out-of-range columns hold unspecified values, so they are masked to
    zero before the dot.

f32 accumulation regardless of input dtype (bf16 C is the production case):
each slab is upcast to f32 in VMEM and the dot runs at HIGHEST precision,
so products are f32 products — the same arithmetic as the XLA contractions
of ``FlatBackend`` under ``jax.default_matmul_precision('highest')``.

``nystrom_cross`` is the same kernel with a second operand: Cᵀ·[v₁…v_m]
for a query block stored query-major, (m, p), like the sketch — the batched
pass 1 of the matrix-valued IHVP apply, one C-read for m queries. Query
blocks keep p on the lanes too: a (p, m) block with m < 128 would occupy
128/m times its size in HBM under the TPU's (8, 128) tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lane_mask(p: int, block_p: int, shape, axis: int):
    """True where the global p index of this grid step's slab is < p."""
    cols = pl.program_id(0) * block_p + jax.lax.broadcasted_iota(
        jnp.int32, shape, axis)
    return cols < p


def _slab(ref, p: int, block_p: int) -> jax.Array:
    """This grid step's (rows, block_p) slab as f32, overhang zeroed."""
    x = ref[...].astype(jnp.float32)
    if p % block_p:
        x = jnp.where(_lane_mask(p, block_p, x.shape, 1), x, 0.0)
    return x


def _accumulate(out_ref, a, b):
    """out += a bᵀ, contracting the block_p lanes; zeroed at step 0."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _gram_kernel(c_ref, out_ref, *, p: int, block_p: int):
    c = _slab(c_ref, p, block_p)                    # (k, block_p)
    _accumulate(out_ref, c, c)


def _cross_kernel(a_ref, b_ref, out_ref, *, p: int, block_p: int):
    _accumulate(out_ref, _slab(a_ref, p, block_p),  # (k, block_p)
                _slab(b_ref, p, block_p))           # (m, block_p)


@functools.partial(jax.jit, static_argnames=('block_p', 'interpret'))
def nystrom_gram(C: jax.Array, *, block_p: int = 1024,
                 interpret: bool = False) -> jax.Array:
    """CᵀC for the sketch-major C (k, p) → (k, k) f32."""
    k, p = C.shape
    block_p = min(block_p, p)
    return pl.pallas_call(
        functools.partial(_gram_kernel, p=p, block_p=block_p),
        grid=(pl.cdiv(p, block_p),),
        in_specs=[pl.BlockSpec((k, block_p), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.float32),
        interpret=interpret,
    )(C)


@functools.partial(jax.jit, static_argnames=('block_p', 'interpret'))
def nystrom_cross(A: jax.Array, B: jax.Array, *, block_p: int = 1024,
                  interpret: bool = False) -> jax.Array:
    """A Bᵀ for the sketch-major A (k, p) and a query-major block B (m, p) →
    (k, m): Cᵀ·[v₁…v_m] in the column-sketch notation.

    The gram kernel generalized to a second operand: the same p-blocked grid
    streams both slabs HBM→VMEM and accumulates one (k, m) MXU tile
    (constant index_map, one HBM write) — m query vectors per C-read instead
    of one. f32 accumulation regardless of input dtypes.
    """
    k, p = A.shape
    m, pb = B.shape
    if p != pb:
        raise ValueError(f'p mismatch: A is (k, {p}), B is (m, {pb})')
    block_p = min(block_p, p)
    return pl.pallas_call(
        functools.partial(_cross_kernel, p=p, block_p=block_p),
        grid=(pl.cdiv(p, block_p),),
        in_specs=[pl.BlockSpec((k, block_p), lambda i: (0, i)),
                  pl.BlockSpec((m, block_p), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, m), jnp.float32),
        interpret=interpret,
    )(A, B)

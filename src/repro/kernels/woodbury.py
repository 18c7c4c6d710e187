"""Pallas TPU kernels for the Woodbury IHVP apply (Eq. 6's two p-passes).

Pass 1 — ``ctv``:    t = Cᵀ v           (k, p) · (p,)  → (k,)
Pass 2 — ``apply``:  u = v/ρ − Cᵀw/ρ²   (k, p) · (k,)  → (p,)

C is the sketch-major (k, p) buffer ``FlatBackend`` builds, read in place:
both passes stream it over a p-blocked grid exactly like nystrom_gram — one
(k, block_p) slab per step, one HBM read of C per pass, no padded or
transposed copy — so a full Nyström IHVP apply costs 2 C-reads, the paper's
"no iterations" property in memory-traffic form; compare l sequential HVPs
(l full fwd+bwd passes) for CG/Neumann.

Matrix-valued queries: both entry points also take a query block stored
query-major, v (m, p), like the sketch, so p stays on the 128-wide lane axis
(see nystrom_gram). Pass 1 is then ``nystrom_cross`` and pass 2 writes
(m, block_p) output slabs, each one (m, k) @ (k, block_p) MXU matmul. A
vector is the block with m = 1: v (p,) travels as a (1, p) row, a free
reshape. Output columns past the end of p are never written back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.nystrom_gram import nystrom_cross


@functools.partial(jax.jit, static_argnames=('block_p', 'interpret'))
def woodbury_ctv(C: jax.Array, v: jax.Array, *, block_p: int = 1024,
                 interpret: bool = False) -> jax.Array:
    """t = Cᵀv for C (k, p): v (p,) → (k,), or a block v (m, p) → (k, m);
    one C-read either way."""
    if v.ndim == 2:
        return nystrom_cross(C, v, block_p=block_p, interpret=interpret)
    t = nystrom_cross(C, v.reshape(1, -1), block_p=block_p,
                      interpret=interpret)
    return t.reshape(-1)


def _apply_kernel(c_ref, v_ref, w_ref, out_ref, *, rho: float):
    c = c_ref[...].astype(jnp.float32)              # (k, block_p)
    v = v_ref[...].astype(jnp.float32)              # (m, block_p)
    w = w_ref[...].astype(jnp.float32)              # (m, k)
    corr = jax.lax.dot_general(
        w, c, (((1,), (0,)), ((), ())),             # (m,k) @ (k,bp) → (m,bp)
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    out_ref[...] = v * (1.0 / rho) - corr * (1.0 / (rho * rho))


@functools.partial(jax.jit, static_argnames=('rho', 'block_p', 'interpret'))
def woodbury_apply(C: jax.Array, w: jax.Array, v: jax.Array, rho: float, *,
                   block_p: int = 1024, interpret: bool = False) -> jax.Array:
    """u = v/ρ − Cᵀw/ρ² for C (k, p). ρ is a compile-time constant
    (hyperparam).

    Vector form: w (k,), v (p,) → (p,). Block form (``v.ndim == 2``):
    w (k, m), v (m, p) → (m, p) — still one C-read for all m queries.
    """
    k, p = C.shape
    if v.ndim == 1:
        return woodbury_apply(C, w.reshape(k, 1), v.reshape(1, p), rho,
                              block_p=block_p, interpret=interpret
                              ).reshape(p)
    m = v.shape[0]
    block_p = min(block_p, p)
    slab = pl.BlockSpec((m, block_p), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_apply_kernel, rho=rho),
        grid=(pl.cdiv(p, block_p),),
        in_specs=[pl.BlockSpec((k, block_p), lambda i: (0, i)), slab,
                  pl.BlockSpec((m, k), lambda i: (0, 0))],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((m, p), jnp.float32),
        interpret=interpret,
    )(C, v, w.T)

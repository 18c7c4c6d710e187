"""Gradient compression for cross-pod (DCN) reductions.

Int8 block-quantized all-reduce with error feedback: the outer-step
hypergradient (and optionally the inner grads) cross the slow pod-to-pod
links at 1/4 the bytes; the quantization residual is fed back into the next
step (error feedback makes the *accumulated* update unbiased, the standard
convergence-preserving trick from 1-bit SGD / EF-SGD).

Implementation notes: a true int8 wire format is a runtime/transport
property — inside XLA we model it as quantize → psum(int32) → dequantize
with a shared (pmax) scale, which is bit-faithful to what an int8 collective
would compute; the roofline's collective term counts the *int8* bytes for
the compressed path (benchmarks/roofline.py applies the 4× discount to
reductions tagged compressed).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any
BLOCK = 256


def _quantize(x: jax.Array):
    """Block-wise symmetric int8 quantization. Returns (q int8, scale f32)."""
    flat = x.astype(jnp.float32).ravel()
    pad = (-flat.size) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale, pad


def _dequantize(q, scale, pad, shape):
    out = (q.astype(jnp.float32) * scale).ravel()
    if pad:
        out = out[:-pad]
    return out.reshape(shape)


def quantize_roundtrip(x: jax.Array) -> jax.Array:
    q, s, pad = _quantize(x)
    return _dequantize(q, s, pad, x.shape)


def compressed_all_reduce(contribs: jax.Array, mesh,
                          axis_name: str) -> jax.Array:
    """jit-able entry point: int8-wire all-reduce of per-device terms.

    ``contribs``: (n_contributions, *shape) — row i is one device's local
    contribution (the hypergradient cross-pod reduction shape: each pod
    holds its own outer-step gradient term); the leading axis must be a
    multiple of the mesh axis size. Returns the quantized sum of ALL rows,
    replicated on every device: each shard sums its local rows in f32, then
    the int8-wire psum crosses the axis. NOTE: a psum of a *replicated*
    operand multiplies by the axis size — the leading contribution axis is
    what makes this a reduction rather than a scale-by-n."""
    from jax.sharding import PartitionSpec as P

    rest = (None,) * (contribs.ndim - 1)
    return jax.shard_map(
        lambda v: compressed_psum(v.astype(jnp.float32).sum(axis=0),
                                  axis_name),
        mesh=mesh, in_specs=P(axis_name, *rest),
        out_specs=P(*rest))(contribs)


def compressed_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """int8-wire psum (use inside shard_map): shared pmax scale, int32
    accumulate — numerically identical to an int8 ring all-reduce."""
    flat = x.astype(jnp.float32).ravel()
    pad = (-flat.size) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    local_scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0 + 1e-12
    scale = jax.lax.pmax(local_scale, axis_name)          # shared scale
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int32)
    total = jax.lax.psum(q, axis_name)                    # int accumulation
    out = (total.astype(jnp.float32) * scale).ravel()
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


@dataclasses.dataclass(frozen=True)
class ErrorFeedbackInt8:
    """Gradient transform: g ← Q(g + e);  e ← (g + e) − Q(g + e).

    Compose before the optimizer:  chain(ErrorFeedbackInt8().transform(), adamw(...)).
    """

    def init(self, params: PyTree) -> PyTree:
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def update(self, grads: PyTree, residual: PyTree):
        corrected = jax.tree.map(
            lambda g, e: g.astype(jnp.float32) + e, grads, residual)
        quantized = jax.tree.map(quantize_roundtrip, corrected)
        new_residual = jax.tree.map(jnp.subtract, corrected, quantized)
        return quantized, new_residual

    def transform(self):
        from repro.optim.optimizers import Optimizer

        def update(grads, state, params, step):
            q, state = self.update(grads, state)
            return q, state

        return Optimizer(self.init, update)

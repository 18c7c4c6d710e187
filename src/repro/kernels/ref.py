"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def nystrom_gram(C: jax.Array) -> jax.Array:
    """CᵀC for the sketch-major C (k, p) → (k, k), f32 accumulation."""
    Cf = C.astype(jnp.float32)
    return Cf @ Cf.T


def nystrom_cross(A: jax.Array, B: jax.Array) -> jax.Array:
    """A Bᵀ : (k, p), (m, p) → (k, m), f32 accumulation."""
    return A.astype(jnp.float32) @ B.astype(jnp.float32).T


def woodbury_ctv(C: jax.Array, v: jax.Array) -> jax.Array:
    """t = Cᵀ v : (k, p), (p,) → (k,) — or (m, p) → (k, m) for a block."""
    return C.astype(jnp.float32) @ v.astype(jnp.float32).T


def woodbury_apply(C: jax.Array, w: jax.Array, v: jax.Array,
                   rho: float) -> jax.Array:
    """u = v/ρ − Cᵀw / ρ² for C (k, p): the p-dimensional Woodbury
    correction apply (vector w (k,), v (p,) — or block w (k, m), v (m, p))."""
    vf = v.astype(jnp.float32)
    corr = (C.astype(jnp.float32).T @ w.astype(jnp.float32)).T
    return vf / rho - corr / (rho * rho)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
            * scale.astype(x.dtype))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None
                    ) -> jax.Array:
    """Dense-softmax attention. q/k/v: (B, S, H, hd) with H already
    GQA-expanded (matches the kernel's contract)."""
    B, S, H, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale
    logits = jnp.einsum('bshd,bthd->bhst', q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        T = k.shape[1]
        mask = jnp.tril(jnp.ones((S, T), bool), T - S)
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bhst,bthd->bshd', w, v.astype(jnp.float32))
    return out.astype(q.dtype)

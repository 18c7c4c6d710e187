"""jit'd public wrappers for the Pallas kernels.

``interpret`` is an explicit choice: ``interpret=True`` runs a kernel body
in the Pallas interpreter (CPU tests), ``False`` compiles it to Mosaic, and
the default ``None`` means "compiled" — which only a TPU backend can do, so
off the TPU it raises instead of quietly interpreting. ``use_pallas`` model
configs route through here; the dry-run keeps the XLA twins so
cost_analysis sees real FLOPs.

The Nyström kernels take the sketch-major (k, p) buffer ``FlatBackend``
builds, unpadded and untransposed.
"""
from __future__ import annotations

import jax

from repro.analysis.auditor import Contract
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.nystrom_gram import nystrom_cross as _cross
from repro.kernels.nystrom_gram import nystrom_gram as _gram
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.woodbury import woodbury_apply as _wapply
from repro.kernels.woodbury import woodbury_ctv as _wctv


#: Every kernel wrapper here — Pallas grid or XLA twin — accumulates f32
#: (bf16 slabs are upcast in VMEM before the MXU dot) and never leaves the
#: device. The jaxpr auditor recurses into ``pallas_call`` kernel jaxprs,
#: so this is checkable on the *kernel body's* dots, not just the wrapper.
KERNEL_CONTRACT = Contract(
    name='pallas kernel accumulation',
    min_accum_dtype='float32',
    no_host_transfer=True,
)


def _interpret(interpret: bool | None) -> bool:
    """``None`` → compile for the TPU; raise where there is none."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend != 'tpu':
        raise RuntimeError(
            f'Pallas kernels compile only for a TPU, and the JAX backend is '
            f'{backend!r}; pass interpret=True to run them in the Pallas '
            'interpreter instead')
    return False


def nystrom_gram(C, *, block_p: int = 1024, interpret: bool | None = None):
    """CᵀC for the sketch-major C (k, p) → (k, k)."""
    return _gram(C, block_p=block_p, interpret=_interpret(interpret))


def nystrom_cross(A, B, *, block_p: int = 1024, interpret: bool | None = None):
    """A Bᵀ for A (k, p), a query-major block B (m, p) → (k, m): the gram
    kernel's two-operand form (batched Cᵀv over m queries, one C-read)."""
    return _cross(A, B, block_p=block_p, interpret=_interpret(interpret))


def woodbury_ctv(C, v, *, block_p: int = 1024, interpret: bool | None = None):
    """Cᵀv for C (k, p). v may be (p,) → (k,) or a query-major block
    (m, p) → (k, m)."""
    return _wctv(C, v, block_p=block_p, interpret=_interpret(interpret))


def woodbury_apply(C, w, v, rho: float, *, block_p: int = 1024,
                   interpret: bool | None = None):
    """v/ρ − Cᵀw/ρ² for C (k, p). Vector (w (k,), v (p,)) or query-major
    block (w (k, m), v (m, p))."""
    return _wapply(C, w, v, rho, block_p=block_p,
                   interpret=_interpret(interpret))


def rmsnorm(x, scale, eps: float = 1e-5, *, interpret: bool | None = None):
    return _rmsnorm(x, scale, eps, interpret=_interpret(interpret))


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_block: int = 512, k_block: int = 512,
                    interpret: bool | None = None):
    return _flash(q, k, v, causal=causal, scale=scale, q_block=q_block,
                  k_block=k_block, interpret=_interpret(interpret))


def nystrom_ihvp_apply(C, H_KK, v, rho: float, *, interpret: bool | None = None):
    """Full Eq. 6 apply through the kernel pipeline for C (k, p):
    t = Cᵀv (kernel) → w = solve(H_KK + CᵀC/ρ, t) (replicated k×k) →
    u = v/ρ − Cᵀw/ρ² (kernel). One C-read per pass."""
    import jax.numpy as jnp
    t = woodbury_ctv(C, v, interpret=interpret)
    gram = nystrom_gram(C, interpret=interpret)
    M = H_KK + gram / rho
    M = 0.5 * (M + M.T)
    d = jnp.sqrt(jnp.clip(jnp.abs(jnp.diagonal(M)), 1e-30, None))
    w = jnp.linalg.solve(M / d[:, None] / d[None, :]
                         + 1e-7 * jnp.eye(M.shape[0]), t / d) / d
    return woodbury_apply(C, w, v, rho, interpret=interpret)

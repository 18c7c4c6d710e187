"""Pallas kernel tests: shape/dtype sweeps, assert_allclose vs ref.py oracles
(interpret mode executes the kernel bodies in Python on CPU). The Nyström
kernels take the sketch-major (k, p) sketch, as FlatBackend stores it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip('hypothesis', reason='property tests need the test extra')
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

I = dict(interpret=True)


@pytest.mark.parametrize('p,k', [(64, 5), (1000, 10), (2048, 128), (4096, 33)])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_nystrom_gram(p, k, dtype):
    C = jax.random.normal(jax.random.PRNGKey(0), (k, p)).astype(dtype)
    got = ops.nystrom_gram(C, block_p=256, **I)
    want = ref.nystrom_gram(C)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize('p,k', [(100, 7), (2048, 64), (3000, 16)])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_woodbury_ctv(p, k, dtype):
    key = jax.random.PRNGKey(1)
    C = jax.random.normal(key, (k, p)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (p,)).astype(dtype)
    got = ops.woodbury_ctv(C, v, block_p=512, **I)
    want = ref.woodbury_ctv(C, v)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize('p,k,rho', [(100, 7, 0.1), (2048, 64, 0.01),
                                     (999, 5, 1.0)])
def test_woodbury_apply(p, k, rho):
    C = jax.random.normal(jax.random.PRNGKey(3), (k, p))
    w = jax.random.normal(jax.random.PRNGKey(4), (k,))
    v = jax.random.normal(jax.random.PRNGKey(5), (p,))
    got = ops.woodbury_apply(C, w, v, rho, block_p=256, **I)
    want = ref.woodbury_apply(C, w, v, rho)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize('p,k,m', [(100, 7, 3), (2048, 8, 32), (3000, 16, 5)])
def test_query_block_forms(p, k, m):
    """Query-major (m, p) blocks: Cᵀ·V → (k, m) and the block Woodbury
    apply → (m, p), each one C-read, against the ref oracles."""
    C = jax.random.normal(jax.random.PRNGKey(12), (k, p)).astype(jnp.bfloat16)
    V = jax.random.normal(jax.random.PRNGKey(13), (m, p))
    W = jax.random.normal(jax.random.PRNGKey(14), (k, m))
    got = ops.woodbury_ctv(C, V, block_p=512, **I)
    want = ref.woodbury_ctv(C, V)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    got = ops.woodbury_apply(C, W, V, 0.1, block_p=512, **I)
    want = ref.woodbury_apply(C, W, V, 0.1)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_compiled_kernels_need_a_tpu():
    """interpret unset means compiled: off the TPU that is an error, never
    a silent fall back to the interpreter."""
    if jax.default_backend() == 'tpu':
        pytest.skip('on a TPU the kernels compile')
    C = jnp.ones((4, 256))
    with pytest.raises(RuntimeError, match='interpret=True'):
        ops.woodbury_ctv(C, jnp.ones((256,)))


def test_kernel_ihvp_matches_solver():
    """End-to-end: kernel-pipeline IHVP == the core solver's spectral apply
    (both approximate (H_k + ρI)⁻¹ v; compare against the dense oracle)."""
    p, r, k, rho = 96, 12, 16, 0.05
    A = jax.random.normal(jax.random.PRNGKey(6), (p, r))
    H = A @ A.T
    idx = jax.random.choice(jax.random.PRNGKey(7), p, (k,), replace=False)
    C = H[:, idx]
    H_KK = 0.5 * (C[idx, :] + C[idx, :].T)
    v = jax.random.normal(jax.random.PRNGKey(8), (p,))
    got = ops.nystrom_ihvp_apply(C.T, H_KK, v, rho, interpret=True)
    H_k = C @ jnp.linalg.pinv(H_KK, rcond=1e-7) @ C.T
    want = jnp.linalg.solve(H_k + rho * jnp.eye(p), v)
    np.testing.assert_allclose(got, want, rtol=5e-3,
                               atol=5e-3 * float(jnp.abs(want).max()))


@pytest.mark.parametrize('shape', [(4, 128), (2, 3, 256), (5, 640)])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(9), shape).astype(dtype)
    scale = jax.random.normal(jax.random.PRNGKey(10), (shape[-1],)).astype(dtype)
    got = ops.rmsnorm(x, scale, 1e-5, **I)
    want = ref.rmsnorm(x, scale, 1e-5)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize('B,S,H,hd', [(1, 128, 2, 64), (2, 256, 4, 128)])
@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, hd, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, q_block=64, k_block=64, **I)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_attention_uneven_blocks_rejected():
    q = jnp.zeros((1, 100, 2, 64))
    with pytest.raises(AssertionError):
        ops.flash_attention(q, q, q, q_block=64, k_block=64, **I)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 7), st.integers(1, 96), st.sampled_from([0.01, 0.5]))
def test_woodbury_apply_property(seed, k, rho):
    """Random (p, k) sweep incl. non-multiples of the block size."""
    p = 37 * k + 11
    C = jax.random.normal(jax.random.PRNGKey(seed), (k, p))
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (k,))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (p,))
    got = ops.woodbury_apply(C, w, v, rho, block_p=128, **I)
    want = ref.woodbury_apply(C, w, v, rho)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * float(np.abs(want).max() + 1))

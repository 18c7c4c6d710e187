"""IHVP solver unit tests: Nyström (all variants) vs dense oracles + baselines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CGIHVP, ExactIHVP, NeumannIHVP, NystromIHVP,
                        PyTreeIndexer, make_hvp, nystrom_inverse_dense,
                        tree_random_like)
from repro.core.tree_util import sample_distinct

PARAMS = {'w': jnp.zeros((8,)), 'b': jnp.zeros((2, 2)), 's': jnp.zeros(())}


def _flat(tree):
    return jnp.concatenate([x.ravel() for x in jax.tree.leaves(tree)])


def _quadratic(Hm):
    def loss(prm, hp, batch):
        th = _flat(prm)
        return 0.5 * th @ Hm @ th
    return loss


def _setup(seed=0, rank=None, cond=1.0):
    idxr = PyTreeIndexer(PARAMS)
    p = idxr.total
    r = rank or p
    B = jax.random.normal(jax.random.PRNGKey(seed), (p, r))
    Hm = B @ B.T + cond * jnp.eye(p) * (rank is None)
    hvp = make_hvp(_quadratic(Hm), PARAMS, None, None)
    v = tree_random_like(jax.random.PRNGKey(seed + 1), PARAMS)
    return idxr, p, Hm, hvp, v


class TestNystrom:
    def test_full_rank_k_equals_p(self):
        idxr, p, Hm, hvp, v = _setup()
        rho = 1e-2
        u = NystromIHVP(k=p, rho=rho).solve(hvp, idxr, v, jax.random.PRNGKey(2))
        u_true = jnp.linalg.solve(Hm + rho * jnp.eye(p), _flat(v))
        np.testing.assert_allclose(_flat(u), u_true, rtol=5e-3, atol=5e-3)

    @pytest.mark.parametrize('r', [2, 4, 8])
    def test_lowrank_exact_recovery(self, r):
        """Rank-r PSD Hessian is recovered exactly from k=r columns (Remark 1).

        Compared at the vector scale (as in test_kappa_equivalence): at k=r
        the f32 *reference* solve itself deviates from the f64 truth by up to
        ~3e-3 on small components (ρ=1e-2 amplifies the null-space noise of
        the rank-deficient H by 1/ρ), so a per-component rtol at 1e-3 asserts
        below the reference's own noise floor.
        """
        idxr, p, Hm, hvp, v = _setup(seed=3, rank=r)
        rho = 1e-2
        u = NystromIHVP(k=r, rho=rho).solve(hvp, idxr, v, jax.random.PRNGKey(4))
        u_true = jnp.linalg.solve(Hm + rho * jnp.eye(p), _flat(v))
        scale = jnp.abs(u_true).max()
        np.testing.assert_allclose(_flat(u) / scale, u_true / scale, atol=1e-3)

    @pytest.mark.parametrize('kappa', [1, 2, 3, 5])
    def test_kappa_equivalence(self, kappa):
        """Alg. 1: every κ produces the same result (paper §2.4)."""
        idxr, p, Hm, hvp, v = _setup(seed=5)
        rho = 0.1  # moderate damping keeps the f32 comparison tight
        solver = NystromIHVP(k=p, rho=rho)
        sketch = solver.prepare(hvp, idxr, jax.random.PRNGKey(6))
        ref = _flat(solver.apply(sketch, v))
        out = _flat(NystromIHVP(k=p, rho=rho, kappa=kappa).apply(sketch, v))
        scale = jnp.abs(ref).max()
        np.testing.assert_allclose(out / scale, ref / scale, atol=2e-3)

    def test_kappa_precedence_over_stabilized(self):
        """kappa<k selects the Alg. 1 chunked apply, which carries its own
        deactivated-eigenvalue stabilization: ``stabilized`` must be inert
        (identical results) rather than silently changing numerics, and
        prepare must not build the never-consulted whitened factor."""
        idxr, p, Hm, hvp, v = _setup(seed=25)
        rho = 0.1
        rng = jax.random.PRNGKey(26)
        a = NystromIHVP(k=p, rho=rho, kappa=3, stabilized=True).solve(
            hvp, idxr, v, rng)
        b = NystromIHVP(k=p, rho=rho, kappa=3, stabilized=False).solve(
            hvp, idxr, v, rng)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        sketch = NystromIHVP(k=p, rho=rho, kappa=3).prepare(hvp, idxr, rng)
        assert sketch.B is None            # whitened factor skipped
        assert sketch.gram_C is not None   # Eq. 6 fallback stays 2-pass

    def test_kappa_honors_refine(self):
        """``refine`` is live on the chunked path: residual sweeps against
        H_k + ρI drive the f32 cancellation error (~2e-4 relative at ρ=1e-3
        here) down to roundoff, matching the whitened path's behavior."""
        idxr, p, Hm, hvp, v = _setup(seed=27)
        rho = 1e-3
        truth = jnp.linalg.solve(Hm + rho * jnp.eye(p), _flat(v))
        sketch = NystromIHVP(k=p, rho=rho).prepare(hvp, idxr,
                                                   jax.random.PRNGKey(28))
        errs = []
        for refine in (0, 2):
            u = NystromIHVP(k=p, rho=rho, kappa=3, refine=refine).apply(
                sketch, v)
            errs.append(float(jnp.abs(_flat(u) - truth).max()
                              / jnp.abs(truth).max()))
        assert errs[1] < errs[0] / 10      # measured: 2e-4 → 6e-7
        assert errs[1] < 1e-5

    def test_literal_eq6_matches_stabilized(self):
        idxr, p, Hm, hvp, v = _setup(seed=7)
        rho = 0.5  # well-damped ⇒ Eq. 6's squared conditioning is benign
        a = NystromIHVP(k=p, rho=rho, stabilized=True).solve(
            hvp, idxr, v, jax.random.PRNGKey(8))
        b = NystromIHVP(k=p, rho=rho, stabilized=False).solve(
            hvp, idxr, v, jax.random.PRNGKey(8))
        np.testing.assert_allclose(_flat(a), _flat(b), rtol=2e-2, atol=2e-2)

    def test_column_chunk_equivalence(self):
        """lax.map-chunked column extraction == one-shot vmap extraction."""
        idxr, p, Hm, hvp, v = _setup(seed=9)
        a = NystromIHVP(k=8, rho=1e-2, column_chunk=3).solve(
            hvp, idxr, v, jax.random.PRNGKey(10))
        b = NystromIHVP(k=8, rho=1e-2).solve(hvp, idxr, v, jax.random.PRNGKey(10))
        np.testing.assert_allclose(_flat(a), _flat(b), rtol=1e-5, atol=1e-5)

    def test_sketch_retargets_across_rho(self):
        """The sketch is ρ-free: one prepare, applied under two different
        damping values, matches each value's own dense truth (the amortized
        rho-sweep use the pre-built-sketch hypergradient path supports)."""
        idxr, p, Hm, hvp, v = _setup(seed=23)
        sketch = NystromIHVP(k=p, rho=1e-2).prepare(hvp, idxr,
                                                    jax.random.PRNGKey(24))
        for rho in (1e-2, 1e-1, 1.0):
            u = NystromIHVP(k=p, rho=rho).apply(sketch, v)
            u_true = jnp.linalg.solve(Hm + rho * jnp.eye(p), _flat(v))
            np.testing.assert_allclose(_flat(u), u_true, rtol=5e-3, atol=5e-3,
                                       err_msg=f'rho={rho}')

    def test_zero_hessian_degenerate(self):
        """All-zero H (the ReLU dead-column pathology §5): falls back to v/ρ."""
        idxr = PyTreeIndexer(PARAMS)
        hvp = make_hvp(lambda prm, hp, b: 0.0 * _flat(prm).sum(), PARAMS, None, None)
        v = tree_random_like(jax.random.PRNGKey(11), PARAMS)
        rho = 0.1
        u = NystromIHVP(k=5, rho=rho).solve(hvp, idxr, v, jax.random.PRNGKey(12))
        np.testing.assert_allclose(_flat(u), _flat(v) / rho, rtol=1e-5)
        assert not jnp.isnan(_flat(u)).any()

    def test_dense_fig1_shape(self):
        """Fig. 1 setting: rank-20 40-dim matrix, k=5..40."""
        p, r, rho = 40, 20, 0.1
        A = jax.random.normal(jax.random.PRNGKey(13), (p, r))
        H = A @ A.T
        truth = jnp.linalg.inv(H + rho * jnp.eye(p))
        err_prev = jnp.inf
        for k in (5, 20, 40):
            ny = nystrom_inverse_dense(H, k=k, rho=rho, rng=jax.random.PRNGKey(14))
            err = jnp.abs(ny - truth).max()
            assert err <= err_prev + 1e-5, f'error must not grow with k (k={k})'
            err_prev = err
        assert err_prev < 5e-3  # k=p ⇒ near-exact


class TestBaselines:
    def test_cg_converges(self):
        idxr, p, Hm, hvp, v = _setup(seed=15)
        rho = 1e-2
        u = CGIHVP(iters=4 * p, rho=rho).solve(hvp, idxr, v, None)
        u_true = jnp.linalg.solve(Hm + rho * jnp.eye(p), _flat(v))
        np.testing.assert_allclose(_flat(u), u_true, rtol=1e-3, atol=1e-3)

    def test_neumann_converges_well_conditioned(self):
        """Neumann targets H⁻¹v and needs ‖I−αH‖<1; use a benign spectrum."""
        idxr = PyTreeIndexer(PARAMS)
        p = idxr.total
        evals = jnp.linspace(0.5, 1.5, p)
        Q, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(16), (p, p)))
        Hm = (Q * evals) @ Q.T
        hvp = make_hvp(_quadratic(Hm), PARAMS, None, None)
        v = tree_random_like(jax.random.PRNGKey(17), PARAMS)
        u = NeumannIHVP(iters=200, alpha=0.5).solve(hvp, idxr, v, None)
        u_true = jnp.linalg.solve(Hm, _flat(v))
        np.testing.assert_allclose(_flat(u), u_true, rtol=1e-3, atol=1e-3)

    def test_neumann_diverges_when_alpha_violates_norm_bound(self):
        """The instability the paper fixes: ‖αH‖>2 ⇒ series diverges."""
        idxr, p, Hm, hvp, v = _setup(seed=18)  # ‖H‖ ~ tens
        u = NeumannIHVP(iters=100, alpha=1.0).solve(hvp, idxr, v, None)
        assert (~jnp.isfinite(_flat(u))).any() or jnp.abs(_flat(u)).max() > 1e6

    def test_exact_is_oracle(self):
        idxr, p, Hm, hvp, v = _setup(seed=19)
        rho = 1e-2
        u = ExactIHVP(rho=rho).solve(hvp, idxr, v, None)
        u_true = jnp.linalg.solve(Hm + rho * jnp.eye(p), _flat(v))
        np.testing.assert_allclose(_flat(u), u_true, rtol=1e-4, atol=1e-4)


class TestIndexer:
    def test_one_hot_roundtrip(self):
        idxr = PyTreeIndexer(PARAMS)
        for j in (0, 7, 8, 11, idxr.total - 1):
            oh_tree = idxr.one_hot(jax.tree.map(lambda a: a[0],
                                                idxr.from_flat([j])))
            flat = _flat(oh_tree)
            assert flat[j] == 1.0 and flat.sum() == 1.0

    def test_gather_matches_flat_indexing(self):
        idxr = PyTreeIndexer(PARAMS)
        k = 4
        batched = jax.tree.map(
            lambda l: jax.random.normal(jax.random.PRNGKey(20),
                                        (k,) + l.shape), PARAMS)
        flat = jnp.stack([_flat(jax.tree.map(lambda x: x[i], batched))
                          for i in range(k)])
        flat_idx = [0, 5, 9, 12]
        idx = idxr.from_flat(flat_idx)
        np.testing.assert_allclose(idxr.gather(batched, idx),
                                   flat[:, jnp.array(flat_idx)], rtol=1e-6)

    def test_sample_indices_cover_all_leaves(self):
        idxr = PyTreeIndexer(PARAMS)
        idx = idxr.sample_indices(jax.random.PRNGKey(21), 8)
        assert idx['leaf'].shape == (8,)
        assert idx['dims'].shape == (8, idxr.max_rank)
        # distinct below the int32 boundary (replace=False path)
        pairs = {(int(l), tuple(map(int, d)))
                 for l, d in zip(idx['leaf'], idx['dims'])}
        assert len(pairs) == 8
        # every sampled coordinate is in range
        table = np.asarray(idxr._dim_table)[np.asarray(idx['leaf'])]
        assert (np.asarray(idx['dims']) < table).all()

    @pytest.mark.parametrize('p,k', [(13, 1), (13, 3), (13, 13),
                                     (239_087_616, 3)])
    def test_sample_distinct_in_range(self, p, k):
        flat = np.asarray(sample_distinct(jax.random.PRNGKey(p + k), p, k))
        assert flat.shape == (k,) and flat.dtype == np.int32
        assert len(set(flat.tolist())) == k
        assert ((flat >= 0) & (flat < p)).all()
        if k == p:
            assert sorted(flat.tolist()) == list(range(p))

    @pytest.mark.parametrize('k', [13, 20])
    def test_sample_indices_at_k_ge_p_takes_every_index_once(self, k):
        idxr = PyTreeIndexer(PARAMS)
        pairs = lambda ix: [(int(l), tuple(map(int, d)))  # noqa: E731
                            for l, d in zip(ix['leaf'], ix['dims'])]
        got = pairs(idxr.sample_indices(jax.random.PRNGKey(k), k))
        assert len(got) == idxr.total
        assert sorted(got) == sorted(pairs(idxr.from_flat(range(idxr.total))))

    @pytest.mark.parametrize('p,k', [(13, 3), (239_087_616, 3)])
    def test_sample_distinct_jit_vmap_matches_eager(self, p, k):
        keys = jax.random.split(jax.random.PRNGKey(7), 16)
        batched = jax.jit(jax.vmap(lambda r: sample_distinct(r, p, k)))(keys)
        eager = np.stack([sample_distinct(r, p, k) for r in keys])
        np.testing.assert_array_equal(batched, eager)
        assert all(len(set(row)) == k for row in eager.tolist())

    def test_sample_distinct_law_is_uniform_ordered(self):
        """p = 6, k = 3: all 120 ordered triples equally likely (chi-square,
        119 dof, under its 0.9999 quantile 185.1), each index kept k/p of the
        time and each position uniform — Floyd's draw unshuffled puts the
        last candidate in the last slot half of the time."""
        p, k, n = 6, 3, 24_000
        keys = jax.random.split(jax.random.PRNGKey(2024), n)
        draws = np.asarray(jax.jit(jax.vmap(
            lambda r: sample_distinct(r, p, k)))(keys))
        codes = (draws[:, 0] * p + draws[:, 1]) * p + draws[:, 2]
        counts = np.bincount(codes, minlength=p ** 3)
        triples = [(a * p + b) * p + c for a in range(p) for b in range(p)
                   for c in range(p) if len({a, b, c}) == 3]
        assert counts.sum() == counts[triples].sum() == n  # all distinct
        expected = n / len(triples)
        chi2 = ((counts[triples] - expected) ** 2 / expected).sum()
        assert chi2 < 185.1, chi2
        sd = np.sqrt(expected)
        assert (np.abs(counts[triples] - expected) < 5 * sd).all()
        share = np.stack([(draws == i).any(1).mean() for i in range(p)])
        np.testing.assert_allclose(share, k / p, atol=0.015)
        marginal = (draws[:, :, None] == np.arange(p)).mean(0)   # (k, p)
        np.testing.assert_allclose(marginal, 1 / p, atol=0.012)

    def test_structured_safe_beyond_int32(self):
        """Index math never forms a global flat offset: a (virtual) tree
        with > 2^31 params samples/one-hots fine (the yi-9b hypergrad cell
        overflowed here before structuring)."""
        big = {'a': jax.ShapeDtypeStruct((50_000, 50_000), jnp.float32),
               'b': jax.ShapeDtypeStruct((126, 16384, 53248), jnp.float32)}
        idxr = PyTreeIndexer(big)
        assert idxr.total > 2 ** 31
        idx = idxr.sample_indices(jax.random.PRNGKey(0), 16)
        assert (np.asarray(idx['dims']) >= 0).all()

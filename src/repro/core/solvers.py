"""IHVP solvers: the paper's Nyström method plus the baselines it compares to.

Every solver approximates  u ≈ (H + ρI)⁻¹ v  where H = ∇²_θ f is accessed only
through Hessian-vector products (HVPs).

Uniform solver protocol: every solver implements

    prepare(hvp, indexer, rng) -> state     # touches the model (HVPs)
    apply(state, v)            -> u         # touches only the state
    apply_matrix(state, V)     -> U         # m queries per state pass
    solve(hvp, indexer, v, rng) == apply(prepare(hvp, indexer, rng), v)

``apply_matrix`` takes a *query block*: a pytree shaped like v with one
trailing (m,) axis on every leaf (m stacked cotangents / query gradients).
One prepared state then serves all m queries per pass — for Nyström that
means the tall-skinny contractions become genuine GEMMs ((k, p) × (p, m))
instead of m separate matvecs, and under ``flat_sharded`` the cross-device
reduction is a single (k, m) psum instead of m k-float psums. m = 1
dispatches statically to the vector ``apply``, so a width-1 block is
bit-identical to the vector path on every backend.

``prepare`` does all the work that can be amortized across right-hand sides
(and, for the Nyström sketch / dense factor, across outer steps); ``apply``
is the per-v cost. For the iterative baselines (CG/Neumann) there is nothing
to amortize — their ``prepare`` returns a thin :class:`IterativeOperator`
that closes over the traced hvp, so it is valid only inside the enclosing
trace and cannot be shipped across a jit boundary the way a
:class:`NystromSketch` (pure pytree-of-arrays) can. The class attribute
``amortizable`` declares which kind a solver is: True means ``prepare``
returns a pytree-of-arrays state that survives jit boundaries and outer
steps (Nyström, exact); False means the state is trace-local (CG, Neumann).
The protocol is what ``repro.core.implicit.implicit_root`` drives in its
custom_vjp backward pass; it replaces the previous
``hasattr(solver, 'apply')`` duck-typing.

The *lifecycle* of an amortizable state — build it at a linearization point,
reuse it for a few outer steps, rebuild when stale — is owned by
:class:`SketchPolicy` (bottom of this module): ``BilevelTrainer``'s loop,
the manual ``build_sketch``/``outer_step_with_sketch`` pair, and the
shared-sketch meta-batch path of ``implicit_root`` all drive the same
policy object instead of hand-rolling refresh logic.

* ``NystromIHVP`` — the paper's contribution (Eq. 4/6, Alg. 1). Non-iterative:
  k parallel HVPs build the sketch once, then every apply is two tall-skinny
  contractions and one k×k solve. The κ dial selects the time/space tradeoff
  (κ=k: Eq. 6 "time-efficient"; κ=1: Eq. 9 "space-efficient"; in between:
  Alg. 1 hybrid) with bit-identical results.
* ``CGIHVP`` — conjugate gradient (Pedregosa 2016; Rajeswaran et al. 2019).
* ``NeumannIHVP`` — Neumann series (Lorraine et al. 2020).
* ``ExactIHVP`` — dense solve, for tiny problems / oracles in tests.

Contraction backends: every tall-skinny contraction in the Nyström hot path
(Cᵀv, Cw, CᵀC, CᵀB) goes through a pluggable backend
(``repro.core.backend``), selected by ``NystromIHVP(backend=...)``:

  'tree'         per-leaf pytree einsums — the default and the parity
                 oracle; sharding-transparent but pays n_leaves dispatches
                 per contraction.
  'flat'         the sketch is fused once at prepare() into a single (k, p)
                 buffer; each contraction is then ONE fused XLA matmul
                 instead of n_leaves einsums + a Python sum. Fastest on
                 CPU/GPU/single-chip; unsharded steps only.
  'flat_sharded' flat's fusion under GSPMD sharding: per-device local
                 (k, p_local) buffers built inside shard_map, reductions
                 finished by a k-float (k×k) psum. Needs mesh + param
                 PartitionSpecs; never all-gathers a parameter leaf.
  'pallas'       flat buffer with the gram / Cᵀv / fused-apply passes in
                 the hand-tiled Pallas TPU kernels (repro.kernels) — one
                 HBM read of C per pass. Compiled for the TPU only.

Sharding: solvers are pure jax; under pjit with backend='tree', C (leading-k
parameter pytree) inherits the parameter sharding and CᵀC / Cᵀv lower to
per-shard contractions + one psum. backend='flat_sharded' keeps that
sharding story while also fusing the per-device p-pass into one matmul —
the fast path for sharded steps (docs/backends.md has the full design and
measured numbers). No solver holds any p×p object.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar

import jax
import jax.numpy as jnp

from repro.core.backend import get_backend
from repro.core.hvp import extract_columns, make_hvp
from repro.core.tree_util import (PyTree, PyTreeIndexer, tree_axpy, tree_scale,
                                  tree_size, tree_vdot, tree_zeros_like)

HVP = Callable[[PyTree], PyTree]

# Eigenvalues below this (relative) threshold are deactivated by sending them
# to SAFE_BIG, which makes their rank-1/rank-κ Woodbury contribution vanish —
# the static-shape analogue of a truncated pseudo-inverse (paper §5: zero
# Hessian columns under ReLU break the plain inverse).
_EIG_REL_TOL = 1e-7
_SAFE_BIG = 1e30


def _sym_solve(M: jax.Array, t: jax.Array) -> jax.Array:
    """Solve M w = t for symmetric (possibly indefinite) k×k M; t may be a
    (k,) vector or a (k, m) block of right-hand sides.

    Jacobi (diagonal) preconditioning: M = H_KK + CᵀC/ρ mixes scales of H and
    H²/ρ, which costs ~3 digits in f32; symmetric diagonal scaling restores
    them (measured in tests/test_solvers.py). Jitter handles the zero-column
    degeneracy the paper works around with leaky-ReLU.
    """
    M = 0.5 * (M + M.T)
    d = jnp.sqrt(jnp.clip(jnp.abs(jnp.diagonal(M)), 1e-30, None))
    Ms = M / d[:, None] / d[None, :]
    jitter = 1e-7
    k = M.shape[0]
    ds = d if t.ndim == 1 else d[:, None]
    w = jnp.linalg.solve(Ms + jitter * jnp.eye(k, dtype=M.dtype), t / ds)
    return w / ds


def query_width(V: PyTree) -> int:
    """The m of a query block: the shared trailing-axis width of every leaf.

    Raises ValueError when leaves disagree (the usual symptom of passing a
    plain parameter vector where a block was expected — a block leaf is the
    parameter shape *plus* one trailing (m,) axis, even at m = 1).
    """
    leaves = jax.tree.leaves(V)
    if not leaves:
        raise ValueError('query block has no leaves')
    widths = {l.shape[-1] if l.ndim else None for l in leaves}
    if len(widths) != 1 or None in widths:
        raise ValueError(
            'inconsistent query block: every leaf must carry the same '
            f'trailing (m,) query axis, got widths {sorted(map(str, widths))}')
    return leaves[0].shape[-1]


def _matrix_via_vector(apply_fn, V: PyTree) -> PyTree:
    """m = 1 static dispatch: strip the query axis, run the vector apply,
    restore the axis — bit-identical to the vector path by construction."""
    u = apply_fn(jax.tree.map(lambda x: x[..., 0], V))
    return jax.tree.map(lambda x: x[..., None], u)


# ---------------------------------------------------------------------------
# Nyström (the paper)
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class NystromSketch:
    """Prepared sketch: reusable across many IHVP applies (and outer steps).

    ``C`` is the backend-native sketch operand: a leading-k parameter pytree
    for backend='tree', the fused sketch-major (k, p) buffer for
    backend='flat', the per-device ``ShardedOperand`` (local fused buffer +
    psum weights) for backend='flat_sharded' (and the same (k, p) buffer
    for backend='pallas') — there is no separate unflatten spec;
    apply() reads the output structure off the incoming ``v``.

    ``B``/``gram_B`` is the numerically-stable whitened form of H_k
    (H_k = B Bᵀ with B = C·U diag(λ†^(1/2)); gram_B = BᵀB): present when the
    solver was built with ``stabilized=True`` and the whitened apply is
    reachable (``kappa`` unset or ≥ k — the Alg. 1 chunked apply never
    consults it, so those sketches skip it). ``B`` uses the same
    backend-native representation as ``C``; ``gram_C`` = CᵀC is cached
    instead otherwise (the Eq. 6 apply's k×k system needs it, and it is
    ρ-independent).

    The sketch is ρ-free: every apply path solves against the *applying*
    solver's rho (the k×k system (gram + ρI-ish) w = t is re-solved per
    apply — O(k³) replicated flops, negligible), so one sketch can be
    reused across a damping sweep. ``rho`` records the prepare-time value
    for reference only.
    """
    C: Any              # H[:, K], backend-native (see class docstring)
    H_KK: jax.Array     # (k, k), symmetrized
    indices: dict       # structured {'leaf', 'dims'} (PyTreeIndexer)
    rho: jax.Array      # scalar (prepare-time record; applies use solver rho)
    B: Any = None
    gram_B: jax.Array | None = None
    gram_C: jax.Array | None = None


@dataclasses.dataclass(frozen=True)
class NystromIHVP:
    """The paper's method. κ=None ⇒ Eq. 6 (time-efficient).

    ``stabilized=True`` (default) applies the inverse through the whitened
    factor of H_k (Frangella–Tropp–Udell-style): Eq. 6's k×k system
    H_KK + CᵀC/ρ carries cond(H)² and costs ~3 digits in f32; the whitened
    Woodbury identity is backward-stable (its k×k system BᵀB + ρI carries
    cond(H), not cond(H)²). Either way the apply's rho is *this solver's*
    rho — a sketch is ρ-free and retargets across damping values
    (tests/test_solvers.py::test_sketch_retargets_across_rho).
    ``stabilized=False`` is the literal Eq. 6 for paper-faithful
    benchmarking; both agree to solver tolerance on well-conditioned H.

    ``backend`` selects the contraction backend ('tree' | 'flat' |
    'flat_sharded' | 'pallas', see module docstring), or accepts a
    pre-built backend instance (e.g. ``PallasBackend(interpret=True)`` in
    tests, or a ``FlatShardedBackend(mesh=..., specs=...)`` — the string
    form of flat_sharded cannot carry its mesh, so sharded steps pass the
    instance or go through ``HypergradConfig``). A sketch prepared under
    one backend must be applied under the same backend.

    ``refine``: iterative-refinement sweeps on the apply. An f32 Woodbury
    apply bottoms out at ~eps·λmax/ρ absolute error (the v/ρ-scale
    cancellation); each sweep re-applies the inverse to the residual
    v − (H_k + ρI)u — four extra C-passes, still zero HVPs — and drives the
    error to f32 roundoff (measured: 3e-3 → 5e-6 at ρ=1e-3 on the analytic
    quadratic). refine=0 restores the literal two-pass apply.

    Precedence when ``kappa < k`` (Alg. 1 requested): the chunked apply is
    the *literal* recursive-Woodbury path and takes precedence over
    ``stabilized`` — it carries its own deactivated-eigenvalue handling (the
    ``_SAFE_BIG`` truncation), so the whitened factor is never consulted and
    ``prepare`` does not build it (it caches ``gram_C`` instead, keeping the
    Eq. 6 fallback two-pass). ``refine`` *is* honored on the chunked path:
    the residual sweeps only need C-passes against the eigen-factor, not the
    whitened form. Asserted in
    tests/test_solvers.py::TestNystrom::test_kappa_precedence_over_stabilized.

    At full rank (k = p) the Nyström inverse is exact — the quickest
    end-to-end check:

    >>> import jax, jax.numpy as jnp
    >>> from repro.core.hvp import make_hvp
    >>> from repro.core.tree_util import PyTreeIndexer
    >>> params = {'w': jnp.zeros((6,))}
    >>> d = 1.0 + jnp.arange(6.0)                       # H = diag(d)
    >>> hvp = make_hvp(lambda p, hp, b: 0.5 * jnp.sum(d * p['w'] ** 2),
    ...                params, None, None)
    >>> solver = NystromIHVP(k=6, rho=1e-3, backend='flat')
    >>> u = solver.solve(hvp, PyTreeIndexer(params), {'w': jnp.ones((6,))},
    ...                  jax.random.PRNGKey(0))
    >>> bool(jnp.allclose(u['w'], 1.0 / (d + 1e-3), rtol=1e-3))
    True
    """
    amortizable: ClassVar[bool] = True   # NystromSketch is pytree-of-arrays

    k: int
    rho: float = 1e-2
    kappa: int | None = None
    column_chunk: int | None = None
    importance_sampling: bool = False  # Remark 1 (Drineas–Mahoney weights)
    stabilized: bool = True
    backend: Any = 'tree'
    refine: int = 1

    def _be(self):
        if isinstance(self.backend, str):
            return get_backend(self.backend)
        return self.backend

    # -- sketch construction (k HVPs; the only part that touches the model) --
    def prepare(self, hvp: HVP, indexer: PyTreeIndexer, rng: jax.Array,
                diag_weights: jax.Array | None = None) -> NystromSketch:
        be = self._be()
        weights = diag_weights if self.importance_sampling else None
        # named scopes: per-phase device times in a profiler trace
        # (docs/tracing.md); they change op metadata only
        with jax.named_scope('column_draw'):
            idx = indexer.sample_indices(rng, self.k, weights)
        with jax.named_scope('sketch_hvps'):
            C_tree = extract_columns(hvp, indexer, idx, self.column_chunk)
        H_KK = indexer.gather(C_tree, idx)
        H_KK = 0.5 * (H_KK + H_KK.T)
        C_op = be.prepare_operand(C_tree)
        B, gram_B, gram_C = (None, None, None)
        # kappa<k selects the Alg. 1 chunked apply, which never consults the
        # whitened factor (precedence — see class docstring): skip building it.
        if self.stabilized and not (self.kappa is not None
                                    and self.kappa < self.k):
            B, gram_B = _whitened_form(be, C_op, H_KK)
        else:
            # ρ-independent, so cached here: the Eq. 6 apply stays 2-pass.
            gram_C = be.gram(C_op)
        return NystromSketch(C=C_op, H_KK=H_KK, indices=idx,
                             rho=jnp.float32(self.rho), B=B,
                             gram_B=gram_B, gram_C=gram_C)

    # -- apply (no HVPs; two tall-skinny contractions + tiny replicated math)
    def apply(self, sketch: NystromSketch, v: PyTree) -> PyTree:
        be = self._be()
        if self.kappa is not None and self.kappa < self.k:
            return _apply_woodbury_chunked(be, sketch, v, self.kappa,
                                           self.rho, self.refine)
        if self.stabilized and sketch.B is not None:
            return _apply_whitened(be, sketch, v, self.rho, self.refine)
        return _apply_woodbury_direct(be, sketch, v, self.rho)

    def apply_matrix(self, sketch: NystromSketch, V: PyTree) -> PyTree:
        """m IHVPs per sketch pass: every contraction of the vector apply
        widens to a (·, m) GEMM (same dispatch precedence — chunked >
        whitened > direct), so m queries cost one set of C-reads, not m."""
        if query_width(V) == 1:
            return _matrix_via_vector(lambda v: self.apply(sketch, v), V)
        be = self._be()
        if self.kappa is not None and self.kappa < self.k:
            return _apply_woodbury_chunked_m(be, sketch, V, self.kappa,
                                             self.rho, self.refine)
        if self.stabilized and sketch.B is not None:
            return _apply_whitened_m(be, sketch, V, self.rho, self.refine)
        return _apply_woodbury_direct_m(be, sketch, V, self.rho)

    def solve(self, hvp: HVP, indexer: PyTreeIndexer, v: PyTree,
              rng: jax.Array) -> PyTree:
        return self.apply(self.prepare(hvp, indexer, rng), v)


def _whitened_form(be, C_op, H_KK: jax.Array):
    """H_k = C H_KK† Cᵀ = B Bᵀ with B = C · U diag(λ†^(1/2)), via k×k eighs.

    Every p-sized op is one backend contraction; every decomposition is
    replicated k×k math. The apply then uses the *exact* Woodbury identity

        (B Bᵀ + ρI)⁻¹ = (I − B (BᵀB + ρI)⁻¹ Bᵀ) / ρ

    which holds for any B — unlike the previous spectral form it never needs
    an orthonormal p×k basis, so f32 eigenvector error is not amplified by
    1/ρ (that error cost ~1% at ρ=1e-3 on the full-rank analytic test; this
    form is ~1e-4 there, ~1e-6 with one refinement sweep). Directions with
    λ(H_KK) below the relative threshold are dropped from B (zero columns),
    reproducing the truncated pseudo-inverse semantics for the ReLU
    dead-column pathology (§5). ρ enters only at apply time.
    """
    lam, U = jnp.linalg.eigh(H_KK)
    lam_max = jnp.max(jnp.abs(lam)) + 1e-30
    tol = _EIG_REL_TOL * lam_max * H_KK.shape[0]
    inv_sqrt = jnp.where(lam > tol, 1.0 / jnp.sqrt(jnp.clip(lam, tol, None)),
                         0.0)
    B = be.mul_right(C_op, U * inv_sqrt[None, :])
    G = be.gram(B)                              # (k, k)  [psum of k² floats]
    return B, 0.5 * (G + G.T)


def _apply_whitened(be, s: NystromSketch, v: PyTree, rho: float,
                    refine: int = 1) -> PyTree:
    """u = v/ρ − B (BᵀB + ρI)⁻¹ (Bᵀ v) / ρ  with BᵀB stored in the sketch
    (ρ enters only here, so the sketch retargets across damping values),
    plus ``refine`` residual-correction sweeps against H_k = BBᵀ."""
    vf = be.vec(v)
    k = s.gram_B.shape[0]
    M = s.gram_B + rho * jnp.eye(k, dtype=s.gram_B.dtype)

    def woodbury(x):
        t = be.ctv(s.B, x)                     # (k,) [psum of k floats]
        w = -jnp.linalg.solve(M, t) / rho      # tiny replicated math
        return be.combine(s.B, w, x, rho)

    u = woodbury(vf)
    for _ in range(refine):
        h_u = be.cv(s.B, be.ctv(s.B, u))       # H_k u
        r = be.sub(be.sub(vf, be.scale(u, rho)), h_u)
        u = be.add(u, woodbury(r))
    return be.unvec(u, v)


def _apply_whitened_m(be, s: NystromSketch, V: PyTree, rho: float,
                      refine: int = 1) -> PyTree:
    """The whitened apply over an m-query block: identical algebra with every
    k-vector widened to (k, m) and every p-vector to the backend's (p, m)
    block form — one C-read per pass for all m queries, and under
    flat_sharded exactly one (k, m) psum per ``ctm``."""
    Vm = be.vecm(V)
    k = s.gram_B.shape[0]
    M = s.gram_B + rho * jnp.eye(k, dtype=s.gram_B.dtype)

    def woodbury(X):
        T = be.ctm(s.B, X)                     # (k, m)  [ONE psum]
        W = -jnp.linalg.solve(M, T) / rho      # tiny replicated math
        return be.combinem(s.B, W, X, rho)

    U = woodbury(Vm)
    for _ in range(refine):
        h_u = be.cm(s.B, be.ctm(s.B, U))       # H_k U
        r = be.sub(be.sub(Vm, be.scale(U, rho)), h_u)
        U = be.add(U, woodbury(r))
    return be.unvecm(U, V)


def _apply_woodbury_direct(be, s: NystromSketch, v: PyTree,
                           rho: float) -> PyTree:
    """Eq. 6:  u = v/ρ − C (H_KK + CᵀC/ρ)⁻¹ (Cᵀv) / ρ²."""
    vf = be.vec(v)
    t = be.ctv(s.C, vf)                    # (k,)   [psum of k floats]
    # gram_C is cached at prepare() for stabilized=False sketches; fall back
    # to one extra C-pass when applying a stabilized sketch Eq. 6-style.
    gram_C = s.gram_C if s.gram_C is not None else be.gram(s.C)
    M = s.H_KK + gram_C / rho              # (k,k)
    w = _sym_solve(M, t)                   # replicated tiny solve
    return be.unvec(be.combine(s.C, -w / (rho * rho), vf, rho), v)


def _apply_woodbury_direct_m(be, s: NystromSketch, V: PyTree,
                             rho: float) -> PyTree:
    """Eq. 6 over an m-query block: the k×k system is solved once against m
    right-hand sides (multi-RHS ``_sym_solve``)."""
    Vm = be.vecm(V)
    T = be.ctm(s.C, Vm)                    # (k, m)  [ONE psum]
    gram_C = s.gram_C if s.gram_C is not None else be.gram(s.C)
    M = s.H_KK + gram_C / rho
    W = _sym_solve(M, T)
    return be.unvecm(be.combinem(s.C, -W / (rho * rho), Vm, rho), V)


def _eig_factors(be, s: NystromSketch):
    """L = C·U and deactivated-eigenvalue diagonal for Alg. 1 paths."""
    lam, U = jnp.linalg.eigh(s.H_KK)
    scale = jnp.max(jnp.abs(lam)) + 1e-30
    lam_safe = jnp.where(jnp.abs(lam) < _EIG_REL_TOL * scale, _SAFE_BIG, lam)
    return be.mul_right(s.C, U), lam_safe


def _chunk_factors(be, s: NystromSketch, kappa: int, rho: float):
    """Alg. 1 factor construction, shared by the vector and block appliers.

    State after chunk m: Ĥ_m x = x/ρ − Σ_{j≤m} G_j R_j (G_jᵀ x), held as the
    factor list {(G_j, R_j)}. Per chunk: apply Ĥ_m to the κ new columns
    (one block of backend contractions — no vmap), solve a κ×κ system,
    append a factor. Bit-equivalent to Eq. 6 for every κ. Returns
    (L, λ_safe, factors) — L = C·U with deactivated eigenvalues sent to
    _SAFE_BIG so their reciprocal contribution vanishes
    (truncated-pseudo-inverse semantics)."""
    k = s.indices['leaf'].shape[0]
    L, lam = _eig_factors(be, s)
    factors: list[tuple[Any, jax.Array]] = []

    def apply_running_block(X):
        """Ĥ_m applied to a tall-skinny block (backend-native layout)."""
        out = be.scale(X, 1.0 / rho)
        for G, R in factors:
            out = be.sub(out, be.mul_right(G, R @ be.cross(G, X)))
        return out

    for start in range(0, k, kappa):
        width = min(kappa, k - start)
        Lm = be.slice_k(L, start, width)
        Jm = jnp.diag(lam[start:start + width])
        HmL = apply_running_block(Lm)
        S = Jm + be.cross(Lm, HmL)
        S = 0.5 * (S + S.T)
        jitter = 1e-8 * (jnp.trace(jnp.abs(S)) / width + 1.0)
        R = jnp.linalg.inv(S + jitter * jnp.eye(width, dtype=S.dtype))
        factors.append((HmL, 0.5 * (R + R.T)))
    return L, lam, factors


def _apply_woodbury_chunked(be, s: NystromSketch, v: PyTree, kappa: int,
                            rho: float, refine: int = 0) -> PyTree:
    """Alg. 1: recursive rank-κ Woodbury updates, applied in operator form
    (factor construction: :func:`_chunk_factors`).

    ``refine`` residual sweeps correct u against H_k + ρI exactly as on the
    whitened path, with H_k u = L diag(λ_safe⁻¹) (Lᵀ u) — deactivated
    eigenvalues were sent to _SAFE_BIG, so their reciprocal contribution
    vanishes, matching the truncated-pseudo-inverse semantics.
    """
    L, lam, factors = _chunk_factors(be, s, kappa, rho)

    def apply_factors(x):
        out = be.scale(x, 1.0 / rho)
        for G, R in factors:
            out = be.sub(out, be.cv(G, R @ be.ctv(G, x)))
        return out

    vf = be.vec(v)
    u = apply_factors(vf)
    for _ in range(refine):
        h_u = be.cv(L, be.ctv(L, u) / lam)     # H_k u (λ_safe⁻¹ ≈ λ† trunc.)
        r = be.sub(be.sub(vf, be.scale(u, rho)), h_u)
        u = be.add(u, apply_factors(r))
    return be.unvec(u, v)


def _apply_woodbury_chunked_m(be, s: NystromSketch, V: PyTree, kappa: int,
                              rho: float, refine: int = 0) -> PyTree:
    """Alg. 1 over an m-query block: the factor list is built once (it is
    query-independent — the expensive part of the chunked apply) and each
    factor's rank-κ correction hits all m queries as one GEMM pair."""
    L, lam, factors = _chunk_factors(be, s, kappa, rho)

    def apply_factors(X):
        out = be.scale(X, 1.0 / rho)
        for G, R in factors:
            out = be.sub(out, be.cm(G, R @ be.ctm(G, X)))
        return out

    Vm = be.vecm(V)
    U = apply_factors(Vm)
    for _ in range(refine):
        h_u = be.cm(L, be.ctm(L, U) / lam[:, None])   # H_k U, truncated λ†
        r = be.sub(be.sub(Vm, be.scale(U, rho)), h_u)
        U = be.add(U, apply_factors(r))
    return be.unvecm(U, V)


def nystrom_inverse_dense(H: jax.Array, k: int, rho: float,
                          rng: jax.Array) -> jax.Array:
    """Dense-matrix Nyström inverse (Fig. 1 oracle / tests): returns
    (H_k + ρI)⁻¹ as an explicit p×p matrix. Test-scale only."""
    p = H.shape[0]
    idx = jax.random.choice(rng, p, (min(k, p),), replace=False)
    C = H[:, idx]                      # (p, k)
    H_KK = 0.5 * (C[idx, :] + C[idx, :].T)
    M = H_KK + C.T @ C / rho
    M = 0.5 * (M + M.T) + 1e-8 * jnp.eye(M.shape[0])
    return jnp.eye(p) / rho - C @ jnp.linalg.solve(M, C.T) / rho**2


# ---------------------------------------------------------------------------
# Iterative baselines
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class IterativeOperator:
    """Prepared state of an iterative solver: a thin operator handle.

    Iterative methods have no sketch to amortize — ``prepare`` just closes
    over the hvp so that ``apply`` fits the uniform protocol. Because the
    handle holds a *callable over traced values*, it lives only within the
    trace that built it: it cannot be checkpointed, donated, or reused after
    the parameters change (unlike a :class:`NystromSketch` or
    :class:`DenseFactor`, which are pytrees of arrays)."""
    hvp: HVP


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseFactor:
    """ExactIHVP's prepared state: the materialized, symmetrized Hessian.

    ρ-free like the Nyström sketch — ``apply`` adds the *applying* solver's
    ρI, so one factor serves a whole damping sweep (tests / Fig. 1 oracles).
    """
    H: jax.Array    # (p, p)


@dataclasses.dataclass(frozen=True)
class CGIHVP:
    """Truncated conjugate gradient on (H + ρI) x = v.

    ρ=0 reproduces the paper's baseline exactly; ρ>0 is Tikhonov damping.
    """
    amortizable: ClassVar[bool] = False  # IterativeOperator is trace-local

    iters: int = 5
    rho: float = 0.0

    def prepare(self, hvp: HVP, indexer: PyTreeIndexer,
                rng: jax.Array | None = None) -> IterativeOperator:
        del indexer, rng
        return IterativeOperator(hvp=hvp)

    def apply(self, state: IterativeOperator, v: PyTree) -> PyTree:
        hvp = state.hvp

        def matvec(x: PyTree) -> PyTree:
            return tree_axpy(self.rho, x, hvp(x))

        x = tree_zeros_like(v)
        r = v
        p = v
        rs = tree_vdot(r, r)

        def body(_, carry):
            x, r, p, rs = carry
            Ap = matvec(p)
            denom = tree_vdot(p, Ap)
            alpha = rs / jnp.where(jnp.abs(denom) < 1e-30, 1e-30, denom)
            x = tree_axpy(alpha, p, x)
            r = tree_axpy(-alpha, Ap, r)
            rs_new = tree_vdot(r, r)
            beta = rs_new / jnp.where(rs < 1e-30, 1e-30, rs)
            p = tree_axpy(beta, p, r)
            return x, r, p, rs_new

        x, _, _, _ = jax.lax.fori_loop(0, self.iters, body, (x, r, p, rs))
        return x

    def apply_matrix(self, state: IterativeOperator, V: PyTree) -> PyTree:
        """vmap over the trailing query axis: CG's recurrence couples the
        scalars (α, β) to each right-hand side, so the m solves stay
        independent — but the HVPs inside batch across queries under vmap
        (one batched fwd+bwd per iteration instead of m)."""
        if query_width(V) == 1:
            return _matrix_via_vector(lambda v: self.apply(state, v), V)
        return jax.vmap(lambda v: self.apply(state, v),
                        in_axes=-1, out_axes=-1)(V)

    def solve(self, hvp: HVP, indexer: PyTreeIndexer, v: PyTree,
              rng: jax.Array | None = None) -> PyTree:
        return self.apply(self.prepare(hvp, indexer, rng), v)


@dataclasses.dataclass(frozen=True)
class NeumannIHVP:
    """Truncated Neumann series (Lorraine et al. 2020):
    (H)⁻¹ ≈ α Σ_{j=0}^{l} (I − αH)^j, requires ‖αH‖ < 1 to converge."""
    amortizable: ClassVar[bool] = False  # IterativeOperator is trace-local

    iters: int = 5
    alpha: float = 1e-2

    def prepare(self, hvp: HVP, indexer: PyTreeIndexer,
                rng: jax.Array | None = None) -> IterativeOperator:
        del indexer, rng
        return IterativeOperator(hvp=hvp)

    def apply(self, state: IterativeOperator, v: PyTree) -> PyTree:
        hvp = state.hvp

        def body(_, carry):
            p, acc = carry
            p = tree_axpy(-self.alpha, hvp(p), p)   # p ← (I − αH) p
            acc = tree_axpy(1.0, p, acc)
            return p, acc

        p, acc = jax.lax.fori_loop(0, self.iters, body, (v, v))
        return tree_scale(acc, self.alpha)

    def apply_matrix(self, state: IterativeOperator, V: PyTree) -> PyTree:
        """vmap over the trailing query axis (the series recursion is
        per-query, but the inner HVPs batch under vmap)."""
        if query_width(V) == 1:
            return _matrix_via_vector(lambda v: self.apply(state, v), V)
        return jax.vmap(lambda v: self.apply(state, v),
                        in_axes=-1, out_axes=-1)(V)

    def solve(self, hvp: HVP, indexer: PyTreeIndexer, v: PyTree,
              rng: jax.Array | None = None) -> PyTree:
        return self.apply(self.prepare(hvp, indexer, rng), v)


@dataclasses.dataclass(frozen=True)
class ExactIHVP:
    """Materialize H column-by-column and dense-solve (tests / tiny models)."""
    amortizable: ClassVar[bool] = True   # DenseFactor is pytree-of-arrays

    rho: float = 1e-2

    def prepare(self, hvp: HVP, indexer: PyTreeIndexer,
                rng: jax.Array | None = None) -> DenseFactor:
        del rng
        idx = indexer.all_indices()                     # flat-order structured
        C = extract_columns(hvp, indexer, idx)          # full H, (p, ...) tree
        H = indexer.gather(C, idx)                      # (p, p)
        return DenseFactor(H=0.5 * (H + H.T))

    def apply(self, state: DenseFactor, v: PyTree) -> PyTree:
        leaves, treedef = jax.tree.flatten(v)
        v_flat = jnp.concatenate([x.astype(jnp.float32).ravel()
                                  for x in leaves])
        p = state.H.shape[0]
        u_flat = jnp.linalg.solve(state.H + self.rho * jnp.eye(p), v_flat)
        # unflatten back into v's structure (no indexer needed at apply time)
        outs, off = [], 0
        for leaf in leaves:
            outs.append(u_flat[off:off + leaf.size].reshape(leaf.shape)
                        .astype(leaf.dtype))
            off += leaf.size
        return treedef.unflatten(outs)

    def apply_matrix(self, state: DenseFactor, V: PyTree) -> PyTree:
        """One factorization against m right-hand sides (multi-RHS solve)."""
        if query_width(V) == 1:
            return _matrix_via_vector(lambda v: self.apply(state, v), V)
        from repro.core.backend import flatten_vecm, unflatten_vecm
        Vm = flatten_vecm(V)                            # (p, m)
        p = state.H.shape[0]
        Um = jnp.linalg.solve(state.H + self.rho * jnp.eye(p), Vm)
        return unflatten_vecm(Um, V)

    def solve(self, hvp: HVP, indexer: PyTreeIndexer, v: PyTree,
              rng: jax.Array | None = None) -> PyTree:
        return self.apply(self.prepare(hvp, indexer, rng), v)


# ---------------------------------------------------------------------------
# Tangent-system apply — the solver as a transposable linear-solve op
# ---------------------------------------------------------------------------
def tangent_apply(solver, state, hvp: HVP, w: PyTree) -> PyTree:
    """Apply the solver's IHVP to ``w`` as a *linear-system solve*:
    ``u ≈ (H + ρI)⁻¹ w``, expressed through ``jax.lax.custom_linear_solve``.

    This is the same estimator as ``solver.apply(state, w)`` — bit-identical
    at first order — but packaged as a linear op JAX knows how to
    differentiate and transpose:

      * transposition (reverse mode over a forward-mode rule) re-invokes
        ``solver.apply`` on the cotangent — the system is symmetric, so the
        transpose solve IS the solve, exactly the backward pass
        :func:`repro.core.implicit._implicit_phi_vjp` runs;
      * further forward differentiation (hyper-Hessian products) gets the
        linear-system JVP ``du = solve(dw − dH·u)``, with ``dH`` taken
        through ``hvp`` — the true system matvec — rather than through the
        sketch, matching the AID convention of differentiating at a frozen
        linearization point.

    ``hvp`` must be the inner Hessian-vector product at the linearization
    point (``make_hvp(inner_loss, theta, phi, batch)``); ``solver.rho``
    (when present) supplies the damping of the system matvec. Iterative
    solvers pass their trace-local ``IterativeOperator`` state; amortizable
    solvers pass a prepared sketch/factor.
    """
    rho = float(getattr(solver, 'rho', 0.0))

    def matvec(u: PyTree) -> PyTree:
        return tree_axpy(rho, u, hvp(u))

    def _solve(mv, b: PyTree) -> PyTree:
        del mv
        return solver.apply(state, b)

    return jax.lax.custom_linear_solve(matvec, w, _solve, symmetric=True)


# ---------------------------------------------------------------------------
# State sizing + identity — what a serving cache needs from a solver
# ---------------------------------------------------------------------------
def build_hvp_bill(solver, params_like: PyTree) -> int:
    """HVPs ONE prepared-state build bills for ``solver`` at this size:
    Nyström rank ``k``, or the full parameter count for the exact solver's
    column scan. ``params_like`` may be concrete params or the shape structs
    from ``jax.eval_shape`` — only sizes are read.

    This is the single definition every accounting surface shares —
    ``influence()``'s ``hvp_count``, the engine's per-edge bills
    (``repro.engine.engine_edge_bills``), and the store's per-entry
    ``build_hvps`` — so a warm cache hit billing zero means the same thing
    everywhere and the cold bills are comparable across paths.
    """
    k = getattr(solver, 'k', None)
    if k is not None:
        return int(k)
    return tree_size(params_like)


def state_nbytes(state) -> int:
    """Byte footprint of a prepared solver state (its pytree-of-arrays leaves).

    The sketch-size accounting a byte-budgeted cache
    (:class:`repro.serve.SketchStore`) evicts against: a NystromSketch is
    dominated by its C/B operands (~2 · k · p · itemsize with the whitened
    form), a DenseFactor by its p×p Hessian. Trace-local states
    (:class:`IterativeOperator`) have no array footprint to account and are
    rejected — they cannot outlive their trace, let alone sit in a cache.

    >>> import jax, jax.numpy as jnp
    >>> from repro.core.hvp import make_hvp
    >>> from repro.core.tree_util import PyTreeIndexer
    >>> params = {'w': jnp.zeros((6,))}
    >>> hvp = make_hvp(lambda p, hp, b: jnp.sum(p['w'] ** 2), params,
    ...                None, None)
    >>> s = NystromIHVP(k=4, backend='flat').prepare(
    ...     hvp, PyTreeIndexer(params), jax.random.PRNGKey(0))
    >>> state_nbytes(s) >= 4 * 6 * 4      # at least the (k, p) f32 buffer
    True
    """
    total = 0
    for leaf in jax.tree.leaves(state):
        nbytes = getattr(leaf, 'nbytes', None)
        if nbytes is None:
            raise TypeError(
                f'{type(state).__name__} holds a non-array leaf '
                f'({type(leaf).__name__}) — only amortizable solver states '
                '(pytrees of arrays) have a byte footprint; trace-local '
                'IterativeOperator states cannot be sized or cached')
        total += int(nbytes)
    return total


def _backend_tag(backend) -> str:
    """A stable content tag for a backend selection (string or instance)."""
    if isinstance(backend, str):
        return backend
    tag = getattr(backend, 'name', type(backend).__name__)
    dtype = getattr(backend, 'sketch_dtype', None)
    if dtype is not None:
        tag += f':{jnp.dtype(dtype).name}'
    return tag


def solver_fingerprint(solver) -> str:
    """Content fingerprint of the *prepared-state identity* of a solver.

    Two solvers with equal fingerprints prepare interchangeable states from
    the same (params, data) point — the solver half of a serving-cache key
    (:func:`repro.serve.sketch_key`). Fields that do not change the prepared
    state are deliberately excluded:

    * ``rho`` — sketches and dense factors are ρ-free (every apply re-solves
      the k×k system against the *applying* solver's damping), so one cached
      state serves a whole damping sweep;
    * ``refine`` — apply-time residual sweeps, not state content.

    Iterative solvers raise: their prepared state is trace-local, so it has
    no cacheable identity.

    >>> solver_fingerprint(NystromIHVP(k=8, rho=1e-3)) == \\
    ...     solver_fingerprint(NystromIHVP(k=8, rho=1e-1))
    True
    >>> solver_fingerprint(NystromIHVP(k=8)) == \\
    ...     solver_fingerprint(NystromIHVP(k=16))
    False
    """
    if not getattr(type(solver), 'amortizable', False):
        raise TypeError(
            f'{type(solver).__name__} prepares a trace-local state — it has '
            'no cacheable identity (nothing survives the trace to cache)')
    rho_free = {'rho', 'refine'}
    parts = [type(solver).__name__]
    for f in sorted(dataclasses.fields(solver), key=lambda f: f.name):
        if f.name in rho_free:
            continue
        value = getattr(solver, f.name)
        if f.name == 'backend':
            value = _backend_tag(value)
        parts.append(f'{f.name}={value!r}')
    return ';'.join(parts)


# ---------------------------------------------------------------------------
# Sketch lifecycle — build / refresh / invalidate of amortizable states
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SketchState:
    """A prepared solver state plus its age, carried across outer steps.

    ``sketch`` is whatever the solver's ``prepare`` returns (a
    :class:`NystromSketch` / :class:`DenseFactor` — pytree-of-arrays, so the
    whole SketchState crosses jit boundaries and can be checkpointed).
    ``age`` counts outer steps served since the last rebuild (int32, traced),
    which is what makes the refresh decision ``lax.cond``-friendly.
    """
    sketch: Any
    age: jax.Array      # int32 scalar: steps served since last build


@dataclasses.dataclass(frozen=True)
class SketchPolicy:
    """Owns the lifecycle of an amortizable solver state.

    One policy object serves every consumer of sketch amortization — the
    ``BilevelTrainer`` loop (automatic ``sketch_refresh_every`` cadence), the
    manual ``build_sketch``/``outer_step_with_sketch`` pair, and the
    shared-sketch meta-batch path (``implicit_root``'s ``prepare_state``) —
    so there is exactly one definition of "build", "stale", and "refresh".

    ``refresh_every=N`` rebuilds the state every N uses: N=1 is the
    always-fresh cadence (trajectory-identical to preparing inside the
    backward pass), larger N trades hypergradient accuracy (the backward
    linearizes at a stale θ — the approximation error analyzed by Grazzi et
    al. 2020) for k fewer HVPs on N−1 of every N outer steps.

    Construction rejects solvers whose prepared state is trace-local
    (``amortizable = False``: CG/Neumann return an :class:`IterativeOperator`
    closing over the step's hvp) — reusing one across steps would only fail
    later, opaquely, inside the next jitted step.
    """
    solver: Any                      # built solver (uniform protocol)
    inner_loss: Callable[..., jax.Array]   # f(theta, phi, batch) -> scalar
    refresh_every: int = 1

    def __post_init__(self):
        if self.refresh_every < 1:
            raise ValueError(
                f'refresh_every must be >= 1, got {self.refresh_every}')
        if not getattr(type(self.solver), 'amortizable', False):
            raise TypeError(
                f'{type(self.solver).__name__}.prepare returns a trace-local '
                'IterativeOperator — iterative solvers have nothing to '
                'amortize across outer steps; use the fresh-prepare path '
                '(sketch_refresh_every=1 / outer_step_fn) instead')

    # ------------------------------------------------------------- build
    def build(self, params: PyTree, hparams: PyTree, batch: Any,
              rng: jax.Array):
        """Prepare the solver state at the linearization point
        (params, hparams, batch) — the only lifecycle stage that runs HVPs."""
        hvp = make_hvp(self.inner_loss, params, hparams, batch)
        return self.solver.prepare(hvp, PyTreeIndexer(params), rng)

    def init_state(self, params: PyTree, hparams: PyTree, batch: Any,
                   rng: jax.Array) -> SketchState:
        """A structurally-correct *stale* SketchState (zero arrays, age =
        refresh_every) — the first ``refresh`` rebuilds it, so initialization
        costs no HVPs and the refresh cadence stays uniform from step 0."""
        shapes = jax.eval_shape(self.build, params, hparams, batch, rng)
        sketch0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return SketchState(sketch=sketch0,
                           age=jnp.int32(self.refresh_every))

    # ----------------------------------------------------------- refresh
    def refresh(self, state: SketchState, params: PyTree, hparams: PyTree,
                batch: Any, rng: jax.Array) -> tuple[SketchState, jax.Array]:
        """Advance the lifecycle by one outer step: rebuild under
        ``lax.cond`` when the state has served ``refresh_every`` steps, else
        keep it and age it. Returns (state', rebuilt) where ``rebuilt`` is a
        traced bool — callers that thread an rng stream consume their split
        only when it fires (``jnp.where(rebuilt, new_rng, old_rng)``), so
        cadence changes do not shift the stream on non-refresh steps."""
        rebuilt = state.age >= self.refresh_every
        sketch = jax.lax.cond(
            rebuilt,
            lambda: self.build(params, hparams, batch, rng),
            lambda: state.sketch)
        age = jnp.where(rebuilt, jnp.int32(1), state.age + 1)
        return SketchState(sketch=sketch, age=age), rebuilt

    # -------------------------------------------------------- invalidate
    def invalidate(self, state: SketchState) -> SketchState:
        """Mark the state stale (age = refresh_every) so the next
        ``refresh`` rebuilds regardless of cadence — e.g. after
        ``reset_inner`` re-initializes θ and the curvature jumps."""
        return SketchState(sketch=state.sketch,
                           age=jnp.int32(self.refresh_every))


# ---------------------------------------------------------------------------
# Registry — drives HypergradConfig.build()
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """Registry entry: constructor + which HypergradConfig fields it consumes.

    ``fields`` maps config-field name → constructor kwarg (the paper reuses
    ``k`` as the iteration count l for the iterative baselines, hence the
    renames). ``builds_backend`` marks the solvers that additionally consume
    the backend-selection fields (``backend`` / ``mesh`` / ``param_specs`` /
    ``sketch_dtype``) via ``HypergradConfig._build_backend()``. Any config
    field set to a non-default value that the chosen solver does not consume
    is an error at ``build()`` — never silently ignored."""
    cls: type
    fields: dict[str, str]
    builds_backend: bool = False


SOLVERS = {
    'nystrom': SolverSpec(NystromIHVP,
                          {'k': 'k', 'rho': 'rho', 'kappa': 'kappa',
                           'column_chunk': 'column_chunk',
                           'importance_sampling': 'importance_sampling',
                           'refine': 'refine', 'stabilized': 'stabilized'},
                          builds_backend=True),
    'cg': SolverSpec(CGIHVP, {'k': 'iters', 'rho': 'rho'}),
    'neumann': SolverSpec(NeumannIHVP, {'k': 'iters', 'alpha': 'alpha'}),
    'exact': SolverSpec(ExactIHVP, {'rho': 'rho'}),
}

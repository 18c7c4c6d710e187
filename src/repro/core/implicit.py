"""First-class differentiable implicit solutions (the repo's public API).

The paper's estimator is an *inverse-Hessian-vector product*; what users
actually want to write is the natural JAX thing

    theta_star = solve(phi, batch)                  # inner optimization
    jax.grad(lambda phi: g(solve(phi, batch), phi)) # hypergradient, Eq. 3

``implicit_root`` makes that work: it wraps an inner solver in a
``jax.custom_jvp`` whose tangent rule solves the implicit-function-theorem
tangent system ``(H + ρI) θ̇ = −(∂²f/∂θ∂φ) φ̇`` with the Nyström (or CG /
Neumann / exact) IHVP. Reverse mode falls out by transposition: the tangent
solve is staged through ``jax.lax.custom_linear_solve(symmetric=True)``, so
transposing it re-invokes the *same* ``solver.apply`` on the cotangent and
the backward pass is exactly the IHVP-plus-mixed-term VJP of Grazzi et al.
2020 — the ``jax.custom_vjp`` formula the repo has always run (and still
ships, as the ``forward_mode=False`` escape hatch), now derived rather than
hand-written. Because the solution map is a plain JAX primitive-like
function, it composes for free:

  * ``jax.grad``  → Eq. 3 hypergradients (direct term included, since φ also
    flows into the outer loss directly);
  * ``jax.jvp`` / ``jax.jacfwd`` → oracle tangents ``dθ*/dφ`` — the forward
    path of approximate implicit differentiation, and the enabler for
    *nested* solution maps: an HVP of a loss that contains an
    ``implicit_root`` is jvp-of-grad, which needs both modes at once
    (see ``repro.engine`` for the multi-level machinery built on this);
  * ``jax.vmap``  → batched per-task hypergradients (iMAML meta-batches: the
    k sketch HVPs of every task run as one batched program instead of a
    per-task Python loop — see benchmarks/tab3_imaml.py);
  * ``jax.jit`` / pjit → compiles once; fresh ``rng`` / batch values do not
    retrace (index sampling is traced, not staged out).

Backward-pass cost is exactly the solver's ``prepare`` + ``apply`` + one VJP
through the inner gradient; the forward pass is whatever ``inner_solver_fn``
does (typically T optimizer steps, run *without* differentiation through the
unroll — that is the point of implicit differentiation).

Example — a quadratic inner problem with an analytic solution map
(``f = ½·Σ d·θ² − θ·φ`` has ``θ*(φ) = φ/d``, so ``dθ*/dφ = 1/d``):

>>> import jax, jax.numpy as jnp
>>> from repro.core.implicit import implicit_root
>>> from repro.core.hypergrad import HypergradConfig
>>> d = jnp.array([1.0, 2.0, 4.0])
>>> def inner(theta, phi, batch):
...     return 0.5 * jnp.sum(d * theta ** 2) - jnp.sum(theta * phi)
>>> solve = implicit_root(lambda phi, batch: phi / d, inner,
...                       HypergradConfig(solver='exact', rho=0.0))
>>> g = jax.grad(lambda phi: jnp.sum(solve(phi, None)))(jnp.ones(3))
>>> bool(jnp.allclose(g, 1.0 / d, atol=1e-5))
True

``jax.vmap`` over a task axis gives per-task hypergradients in one program:

>>> phis = jnp.stack([jnp.ones(3), 2.0 * jnp.ones(3)])
>>> per_task = jax.vmap(
...     jax.grad(lambda phi: jnp.sum(solve(phi, None))))(phis)
>>> per_task.shape
(2, 3)

Shared-sketch meta-batches: by default every task in a vmapped meta-batch
re-prepares its own sketch in the backward pass (tasks × k HVPs per
meta-batch). ``solve.prepare_state`` builds one amortizable state at a
single linearization point (e.g. the meta-initialization); closing the
vmapped function over it broadcasts the state across tasks, cutting the
meta-batch cost to k HVPs total (see benchmarks/tab3_imaml.py and the
sketch-lifecycle section of docs/implicit-api.md):

>>> shared = solve.prepare_state(jnp.ones(3), jnp.ones(3), None,
...                              jax.random.PRNGKey(0))
>>> shared_task = jax.vmap(jax.grad(
...     lambda phi: jnp.sum(solve(phi, None, state=shared))))(phis)
>>> bool(jnp.allclose(shared_task, per_task, atol=1e-5))
True

Forward mode gives the oracle tangent of the solution map (here
``dθ*/dφ = 1/d``, so the jvp along ``v`` is ``v/d``):

>>> v = jnp.array([3.0, 2.0, 4.0])
>>> _, tangent = jax.jvp(lambda phi: solve(phi, None), (jnp.ones(3),), (v,))
>>> bool(jnp.allclose(tangent, v / d, atol=1e-5))
True
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hvp import make_hvp
from repro.core.tree_util import PyTree, PyTreeIndexer, tree_scale

InnerSolver = Callable[[PyTree, Any], PyTree]   # (phi, batch) -> theta*
InnerLoss = Callable[..., jax.Array]            # f(theta, phi, batch) -> scalar


def _zeros_cotangent(tree: PyTree) -> PyTree:
    """Zero cotangents for a non-differentiated argument pytree.

    Inexact leaves get ordinary zeros; integer / PRNG-key leaves get the
    ``float0`` zeros JAX expects as their tangent type (a plain ``jnp.zeros``
    there would fail custom_vjp's output-type check)."""
    def z(x):
        aval = jax.core.get_aval(x)
        if jnp.issubdtype(aval.dtype, jnp.inexact):
            return jnp.zeros(aval.shape, aval.dtype)
        return np.zeros(aval.shape, jax.dtypes.float0)
    return jax.tree.map(z, tree)


def _implicit_phi_vjp(solver, inner_loss: InnerLoss, theta: PyTree,
                      phi: PyTree, batch: Any, v: PyTree,
                      rng: jax.Array, state) -> PyTree:
    """The φ-cotangent of the solution map θ*(φ): −(∂²f/∂φ∂θ)ᵀ (H+ρI)⁻¹ v.

    ``state`` is an optional pre-built solver state (e.g. an amortized
    ``NystromSketch``); when absent the solver's ``prepare`` runs here —
    inside the backward pass, so under ``jax.vmap`` the per-task sketch HVPs
    batch across tasks."""
    if state is None:
        hvp = make_hvp(inner_loss, theta, phi, batch)
        state = solver.prepare(hvp, PyTreeIndexer(theta), rng)
    with jax.named_scope('ihvp_apply'):          # docs/tracing.md
        u = jax.lax.stop_gradient(solver.apply(state, v))

    # mixed term: ∇_φ ⟨∇_θ f(θ*, φ), u⟩  (= (∂²f/∂φ∂θ)ᵀ u); f32 accumulation
    def inner_grad_dot_u(p):
        g_theta = jax.grad(inner_loss, argnums=0)(theta, p, batch)
        leaves = jax.tree.leaves(jax.tree.map(
            lambda a, b: jnp.vdot(a.astype(jnp.float32),
                                  b.astype(jnp.float32)), g_theta, u))
        return sum(leaves)

    with jax.named_scope('mixed_vjp'):
        mixed = jax.grad(inner_grad_dot_u)(phi)
    return tree_scale(mixed, -1.0)


def _stop_gradient_arrays(tree) -> PyTree:
    """``stop_gradient`` on every array leaf, passing non-array leaves (the
    closures of a trace-local ``IterativeOperator``) through untouched."""
    return jax.tree.map(
        lambda x: jax.lax.stop_gradient(x)
        if isinstance(x, (jax.Array, np.ndarray)) else x, tree)


def _implicit_phi_tangent(solver, inner_loss: InnerLoss, theta: PyTree,
                          phi: PyTree, batch: Any, phi_dot: PyTree,
                          rng: jax.Array, state) -> PyTree:
    """The φ-tangent of the solution map θ*(φ): −(H+ρI)⁻¹ (∂²f/∂θ∂φ) φ̇.

    The forward-mode mirror of :func:`_implicit_phi_vjp`: differentiate the
    stationarity condition ``∇_θ f(θ*(φ), φ) = 0`` to get the tangent system
    ``(H + ρI) θ̇ = −M φ̇``, build ``M φ̇`` as a jvp of the inner gradient in
    the φ slot, and solve with the same solver ``apply`` the backward pass
    uses — via :func:`~repro.core.solvers.tangent_apply`, so the solve is a
    transposable linear op (reverse mode over this rule reproduces the vjp)
    and further differentiation (hyper-Hessian products) stays correct.

    ``state`` semantics match the vjp: None prepares here (k sketch HVPs,
    batched under ``jax.vmap``); a pre-built state amortizes them away. The
    linearization point is frozen (``stop_gradient`` on θ and the state
    arrays) — AID differentiates the implicit map, never the sketch."""
    from repro.core.solvers import tangent_apply
    theta_c = jax.lax.stop_gradient(theta)
    if state is None:
        hvp = make_hvp(inner_loss, theta_c, phi, batch)
        state = solver.prepare(hvp, PyTreeIndexer(theta_c), rng)
    state = _stop_gradient_arrays(state)

    def inner_grad(p):
        return jax.grad(inner_loss, argnums=0)(theta_c, p, batch)

    # the same scopes as the vjp's: reverse mode runs this rule transposed
    with jax.named_scope('mixed_vjp'):
        m_dot = jax.jvp(inner_grad, (phi,), (phi_dot,))[1]
    hvp_sys = make_hvp(inner_loss, theta_c, phi, batch)
    with jax.named_scope('ihvp_apply'):
        u = tangent_apply(solver, state, hvp_sys, m_dot)
    return tree_scale(u, -1.0)


def phi_vjp_block(solver, inner_loss: InnerLoss, theta: PyTree,
                  phi: PyTree, batch: Any, V: PyTree,
                  rng: jax.Array | None = None, state=None) -> PyTree:
    """The φ-cotangent of θ*(φ) for an m-query block of cotangents.

    ``V`` is a query block: every leaf is the matching θ-leaf's shape plus a
    trailing (m,) axis (m stacked cotangents — e.g. the per-query gradients
    of an influence-function sweep). Returns the φ-shaped block
    −(∂²f/∂φ∂θ)ᵀ (H+ρI)⁻¹ V with the same trailing axis.

    One solver state serves all m queries: the IHVP runs through
    ``solver.apply_matrix`` (a single set of sketch passes — GEMMs, not m
    matvecs), and only the mixed-term VJP — whose cost is a fwd+bwd of the
    inner gradient, independent of the sketch — is vmapped per query.
    ``state=None`` prepares here (k HVPs); pass a prepared state to amortize
    across blocks. m = 1 matches ``m`` separate vector VJPs bit-for-bit on
    the IHVP side (see ``Solver.apply_matrix``).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if state is None:
        hvp = make_hvp(inner_loss, theta, phi, batch)
        state = solver.prepare(hvp, PyTreeIndexer(theta), rng)
    U = jax.lax.stop_gradient(solver.apply_matrix(state, V))

    def phi_bar(u):
        def inner_grad_dot_u(p):
            g_theta = jax.grad(inner_loss, argnums=0)(theta, p, batch)
            leaves = jax.tree.leaves(jax.tree.map(
                lambda a, b: jnp.vdot(a.astype(jnp.float32),
                                      b.astype(jnp.float32)), g_theta, u))
            return sum(leaves)
        return tree_scale(jax.grad(inner_grad_dot_u)(phi), -1.0)

    return jax.vmap(phi_bar, in_axes=-1, out_axes=-1)(U)


def implicit_root(inner_solver_fn: InnerSolver, inner_loss: InnerLoss,
                  hypergrad=None, forward_mode: bool = True) -> Callable:
    """Wrap an inner solver into a differentiable solution map ``φ, batch → θ*``.

    Args:
      inner_solver_fn: ``(phi, batch) -> theta_star`` — any approximate inner
        optimization (T optimizer steps, a warm-started closure over the
        current parameters, or an analytic solve). It is *not* differentiated
        through; the returned map's derivatives come from the implicit
        function theorem at the point it returns.
      inner_loss: ``f(theta, phi, batch) -> scalar`` — the inner objective
        whose stationarity defines θ*. Its Hessian (through HVPs only) and
        mixed partial drive the derivative rules.
      hypergrad: a ``HypergradConfig`` (built once here), a solver instance
        implementing the uniform protocol (``prepare``/``apply``), or None
        for the default Nyström configuration.
      forward_mode: True (default) wraps the map in ``jax.custom_jvp`` — the
        tangent rule solves the IFT tangent system with the solver's
        ``apply``, and reverse mode is its transpose (numerically the same
        IHVP + mixed-term VJP, staged through
        ``jax.lax.custom_linear_solve``). Both ``jax.grad`` and
        ``jax.jvp``/``jax.jacfwd`` compose, which nested solution maps
        (``repro.engine``) require. False restores the legacy
        ``jax.custom_vjp``-only wrapper (reverse mode only) — the escape
        hatch if a workflow depends on the hand-written backward trace.

    Returns:
      ``solve(phi, batch=None, rng=None, state=None)`` — a function returning
      θ*, differentiable in ``phi``:

      * ``rng`` seeds the derivative pass's sketch-column sampling (Nyström);
        pass a fresh key per outer step for fresh columns, or reuse one to
        pin them. Defaults to ``PRNGKey(0)``.
      * ``state`` optionally injects a pre-built solver state (an amortized
        ``NystromSketch`` / ``DenseFactor``) so the derivative pass skips
        ``prepare`` — the sketch-amortization story of BilevelTrainer, and
        the shared-sketch meta-batch mode under ``jax.vmap`` (an unbatched
        state closed over by the vmapped function broadcasts across tasks:
        k HVPs per meta-batch instead of per task).
      * ``batch`` and ``rng`` receive zero cotangents (and contribute zero
        tangents): the map is treated as non-differentiable in the data (see
        docs/implicit-api.md for the residual caveats). θ* carries no
        residual connection to the forward unroll — gradients flow *only*
        through the implicit rules.

      The returned function also carries
      ``solve.prepare_state(theta, phi, batch=None, rng=None)`` — it builds
      such a state at an explicit linearization point via the shared
      :class:`~repro.core.solvers.SketchPolicy` code path (k HVPs; raises
      TypeError for iterative solvers, whose state is trace-local).
    """
    from repro.core.hypergrad import HypergradConfig
    if hypergrad is None:
        hypergrad = HypergradConfig()
    solver = (hypergrad.build() if isinstance(hypergrad, HypergradConfig)
              else hypergrad)

    # ``state`` is an ordinary pytree argument: None (the fresh-prepare path)
    # flattens to an empty subtree, a NystromSketch/DenseFactor flattens to
    # arrays — switching between them retraces once, as any structure change
    # does.
    if forward_mode:
        @jax.custom_jvp
        def _solve(phi, batch, rng, state):
            return inner_solver_fn(phi, batch)

        @_solve.defjvp
        def _solve_jvp(primals, tangents):
            phi, batch, rng, state = primals
            # batch/rng/state tangents are ignored by contract (the map is
            # non-differentiable in them); the self-call keeps higher-order
            # differentiation re-entering this rule instead of the unroll.
            phi_dot = tangents[0]
            theta = _solve(phi, batch, rng, state)
            theta_dot = _implicit_phi_tangent(solver, inner_loss, theta, phi,
                                              batch, phi_dot, rng, state)
            return theta, theta_dot
    else:
        @jax.custom_vjp
        def _solve(phi, batch, rng, state):
            return inner_solver_fn(phi, batch)

        def _solve_fwd(phi, batch, rng, state):
            theta = inner_solver_fn(phi, batch)
            return theta, (theta, phi, batch, rng, state)

        def _solve_bwd(res, v):
            theta, phi, batch, rng, state = res
            phi_bar = _implicit_phi_vjp(solver, inner_loss, theta, phi,
                                        batch, v, rng, state)
            return (phi_bar, _zeros_cotangent(batch), _zeros_cotangent(rng),
                    _zeros_cotangent(state))

        _solve.defvjp(_solve_fwd, _solve_bwd)

    def solve(phi: PyTree, batch: Any = None, rng: jax.Array | None = None,
              state=None) -> PyTree:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        return _solve(phi, batch, rng, state)

    def prepare_state(theta: PyTree, phi: PyTree, batch: Any = None,
                      rng: jax.Array | None = None):
        """Build an amortizable solver state at (theta, phi, batch), for the
        ``state=`` argument — one sketch shared across a vmapped meta-batch
        or across outer steps. theta is the linearization point (e.g. the
        meta-initialization); the k sketch HVPs run here, once."""
        from repro.core.solvers import SketchPolicy
        if rng is None:
            rng = jax.random.PRNGKey(0)
        return SketchPolicy(solver=solver, inner_loss=inner_loss).build(
            theta, phi, batch, rng)

    solve.prepare_state = prepare_state
    return solve


def sgd_solver(inner_loss: InnerLoss, steps: int, lr: float,
               init: Callable[[PyTree, Any], PyTree] | None = None
               ) -> InnerSolver:
    """Canonical ``inner_solver_fn``: ``steps`` plain-SGD steps on
    ``inner_loss``, unrolled with ``lax.scan`` (no differentiation through
    the unroll — that is ``implicit_root``'s job).

    ``init``: ``(phi, batch) → θ0``. The default starts from φ itself — the
    iMAML pattern, where φ is the meta-initialization (and typically also
    the proximal anchor inside ``inner_loss``). Pass an explicit ``init``
    when θ and φ live in different spaces (e.g. §5.1 weight-decay HPO).
    """
    def solve(phi: PyTree, batch: Any) -> PyTree:
        theta0 = phi if init is None else init(phi, batch)

        def step(p, _):
            g = jax.grad(inner_loss)(p, phi, batch)
            return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

        theta, _ = jax.lax.scan(step, theta0, None, length=steps)
        return theta

    return solve

"""Hypergradient assembly (Eq. 3) — solver-agnostic implicit differentiation.

    dg/dφ = −(∂g/∂θ) (∇²_θ f + ρI)⁻¹ (∂²f/∂φ∂θ) + ∂g/∂φ

The mixed second derivative is never materialized: with u = IHVP(∂g/∂θ), the
first term is the φ-gradient of ⟨∇_θ f, stop_grad(u)⟩ (one VJP through the
inner gradient). Total cost per hypergradient:

  * Nyström: k + 1 batched-parallel HVPs (sketch, reusable) + 1 VJP
  * CG/Neumann: l *sequential* HVPs + 1 VJP

The assembly itself lives in ``repro.core.implicit``: the inner solution is a
``jax.custom_vjp`` map whose backward pass *is* the IHVP + mixed-term VJP, so
Eq. 3 falls out of plain ``jax.grad`` composition. ``hypergradient`` below is
the original imperative entry point, kept as a thin compatibility wrapper
(see docs/implicit-api.md for the migration table).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.tree_util import PyTree, PyTreeIndexer

InnerLoss = Callable[..., jax.Array]   # f(params, hparams, batch) -> scalar
OuterLoss = Callable[..., jax.Array]   # g(params, hparams, batch) -> scalar


def hypergradient(inner_loss: InnerLoss,
                  outer_loss: OuterLoss,
                  params: PyTree,
                  hparams: PyTree,
                  inner_batch: Any,
                  outer_batch: Any,
                  solver,
                  rng: jax.Array,
                  indexer: PyTreeIndexer | None = None,
                  sketch=None) -> PyTree:
    """Approximate dg/dφ at (params, hparams) via implicit differentiation.

    Compatibility wrapper: treats ``params`` as the (already-computed) inner
    solution, wraps it in the ``implicit_root`` solution map, and
    differentiates ``g(θ*(φ), φ)`` — new code should use
    ``repro.core.implicit.implicit_root`` directly, which also composes with
    ``jax.vmap`` over task batches.

    ``sketch``: an optional pre-built solver state (e.g. a ``NystromSketch``)
    — production trainers amortize one sketch over several outer steps (see
    BilevelTrainer).
    """
    from repro.core.implicit import implicit_root
    del indexer   # the implicit map rebuilds it from θ*; kept for API compat

    solve = implicit_root(lambda phi, batch: params, inner_loss, solver)

    def outer_obj(phi):
        theta = solve(phi, inner_batch, rng=rng, state=sketch)
        return outer_loss(theta, phi, outer_batch)

    return jax.grad(outer_obj)(hparams)


def unrolled_hypergradient(inner_loss: InnerLoss,
                           outer_loss: OuterLoss,
                           params: PyTree,
                           hparams: PyTree,
                           inner_batch: Any,
                           outer_batch: Any,
                           steps: int,
                           lr: float) -> PyTree:
    """Oracle baseline: differentiate through ``steps`` unrolled SGD steps.

    O(steps × activations) memory — tiny problems only; used in tests to
    validate the implicit estimates, and as the paper's §2.5 fallback for
    hyperparameters that do not influence the training loss directly.
    """
    def inner_sgd(phi):
        def step(p, _):
            g = jax.grad(inner_loss, argnums=0)(p, phi, inner_batch)
            p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
            return p, None
        final, _ = jax.lax.scan(step, params, None, length=steps)
        return outer_loss(final, phi, outer_batch)

    return jax.grad(inner_sgd)(hparams)


def config_from_cli(solver: str, flags: dict, defaults: dict,
                    **consumed_extras) -> 'HypergradConfig':
    """Build a HypergradConfig from CLI flags, registry-driven (shared by
    ``launch/train.py`` and ``examples/quickstart.py``).

    ``flags`` maps field → parsed value with ``None`` meaning "flag not
    passed" (use argparse ``default=None`` sentinels). An explicitly passed
    flag the chosen solver does not consume raises here — never a silent
    drop, even when the value coincides with the config default (which
    ``build()``'s own strictness check could not distinguish). Unpassed
    flags fall back to ``defaults`` when (and only when) the solver consumes
    them. ``consumed_extras`` are script-level tunings (e.g.
    ``column_chunk``) forwarded only to solvers that consume them and
    *silently dropped* otherwise — they are the solver-agnostic channel; put
    anything the user typed in ``flags`` so it gets the strictness check.

    "Consumes" is the same notion ``build()`` enforces: the solver's
    ``SolverSpec.fields``, plus the backend-selection family (``backend``,
    ``sketch_dtype``, ``mesh``, ``param_specs``) for solvers that build a
    backend, plus the trainer-level fields (``sketch_refresh_every``) which
    every solver's config carries:

    >>> config_from_cli('nystrom', flags={'backend': 'flat'},
    ...                 defaults={}).backend
    'flat'
    >>> config_from_cli('cg', flags={'backend': 'flat'}, defaults={})
    Traceback (most recent call last):
        ...
    ValueError: --backend=flat is not consumed by solver='cg' (it consumes: \
k, rho, sketch_refresh_every)
    """
    from repro.core.solvers import SOLVERS
    if solver not in SOLVERS:
        raise ValueError(f'unknown solver {solver!r}; registered: '
                         f'{sorted(SOLVERS)}')
    spec = SOLVERS[solver]
    consumed = set(spec.fields) | (set(_TRAINER_FIELDS) - {'solver'})
    if spec.builds_backend:
        consumed |= set(_BACKEND_FIELDS)
    kwargs = {'solver': solver}
    for name, value in flags.items():
        if value is not None:
            if name not in consumed:
                raise ValueError(
                    f'--{name}={value} is not consumed by solver='
                    f'{solver!r} (it consumes: '
                    f'{", ".join(sorted(consumed))})')
            kwargs[name] = value
        elif name in consumed and name in defaults:
            kwargs[name] = defaults[name]
    for name, value in consumed_extras.items():
        if name in consumed:
            kwargs[name] = value
    return HypergradConfig(**kwargs)


# Config fields consumed outside solver construction: ``solver`` selects the
# registry entry. ``sketch_refresh_every`` is the sketch-lifecycle cadence
# consumed by the trainer layer — BilevelTrainer.run and launch/train.py
# rebuild the amortized sketch every that-many outer steps (SketchPolicy);
# it is trainer-level by design, so it stays exempt from the solver-field
# strictness rather than erroring for every solver (run() itself raises when
# asked to amortize an iterative solver).
_TRAINER_FIELDS = ('solver', 'sketch_refresh_every')
# Backend-selection fields, consumed via _build_backend() by solvers whose
# SolverSpec sets builds_backend (today: nystrom).
_BACKEND_FIELDS = ('backend', 'mesh', 'param_specs', 'sketch_dtype')


@dataclasses.dataclass
class HypergradConfig:
    """Config-system entry for the hypergradient feature (see configs/).

    Backend selection (full decision table: README.md / docs/backends.md):
    ``backend`` names a contraction backend; ``flat_sharded`` additionally
    needs ``mesh`` (the jax.sharding.Mesh the step runs under) and
    ``param_specs`` (the PartitionSpec pytree for the parameters, e.g.
    ``repro.distributed.sharding.param_specs(cfg, mesh)``) — ``build()``
    constructs the bound backend instance from them. ``sketch_dtype``
    ('bfloat16' halves sketch memory; contractions accumulate f32) applies
    to the flat family and is rejected for ``tree``, which never builds a
    fused buffer.

    >>> cfg = HypergradConfig(solver='nystrom', k=4, backend='flat')
    >>> solver = cfg.build()
    >>> (solver.k, solver.backend)
    (4, 'flat')
    >>> import jax, numpy as np
    >>> from jax.sharding import Mesh, PartitionSpec as P
    >>> mesh = Mesh(np.array(jax.devices()[:1]), ('model',))
    >>> sharded = HypergradConfig(backend='flat_sharded', mesh=mesh,
    ...                           sketch_dtype='bfloat16').build()
    >>> sharded.backend.name
    'flat_sharded'
    """
    solver: str = 'nystrom'       # nystrom | cg | neumann | exact
    k: int = 10                   # Nyström rank / iterations l for baselines
    rho: float = 1e-2             # damping (Nyström/exact) or CG Tikhonov
    alpha: float = 1e-2           # Neumann step size
    kappa: int | None = None      # Alg. 1 chunk width (None = Eq. 6)
    column_chunk: int | None = None
    sketch_refresh_every: int = 1  # outer steps between sketch rebuilds
    importance_sampling: bool = False
    backend: str = 'tree'         # tree | flat | flat_sharded | pallas
    #   tree         = pytree einsums, sharding-transparent, the default
    #   flat         = fused (k, p) buffer, one XLA matmul per contraction
    #   flat_sharded = per-device fused shards + psum (needs mesh/specs)
    #   pallas       = flat buffer + TPU kernels (compiled; TPU only)
    mesh: Any = None              # flat_sharded: the step's jax Mesh
    param_specs: Any = None       # flat_sharded: PartitionSpec pytree
    sketch_dtype: str | None = None  # flat family: 'bfloat16' halves sketch
    #   memory; contractions still accumulate in f32
    refine: int = 1               # residual sweeps on the stabilized apply:
    #   0 = literal two-C-pass apply; each sweep adds 4 C-passes and drives
    #   the f32 cancellation error (~eps·λmax/ρ) down to roundoff
    stabilized: bool = True       # False = the literal Eq. 6 apply (the
    #   paper-faithful 'nystrom_eq6' benchmark variant); True = the
    #   whitened-Woodbury apply (backward-stable; see NystromIHVP)

    def _build_backend(self):
        from repro.core.backend import get_backend
        if not isinstance(self.backend, str):
            if (self.sketch_dtype is not None or self.mesh is not None
                    or self.param_specs is not None):
                raise ValueError(
                    'backend is a pre-built instance: set sketch_dtype / '
                    'mesh / param_specs on the instance itself — the config '
                    'fields would be silently ignored')
            return self.backend            # pre-built instance passes through
        kwargs = {}
        if self.sketch_dtype is not None:
            if self.backend == 'tree':
                raise ValueError(
                    "sketch_dtype has no effect on backend='tree' (it never "
                    'builds a fused buffer); pick a flat-family backend')
            kwargs['sketch_dtype'] = jnp.dtype(self.sketch_dtype).type
        if self.backend == 'flat_sharded':
            kwargs.update(mesh=self.mesh, specs=self.param_specs)
        elif self.mesh is not None or self.param_specs is not None:
            raise ValueError(
                "mesh/param_specs are only consumed by backend='flat_sharded'")
        return get_backend(self.backend, **kwargs) if kwargs else self.backend

    def build(self):
        """Construct the configured solver via the ``SOLVERS`` registry.

        Each registry entry records which config fields its solver consumes;
        a field set to a non-default value that the chosen solver ignores is
        an error here — matching the backend-field strictness — instead of a
        silently dead knob:

        >>> HypergradConfig(solver='cg', alpha=0.5).build()
        Traceback (most recent call last):
            ...
        ValueError: HypergradConfig.alpha=0.5 is not consumed by \
solver='cg' (it consumes: k, rho) — it would be silently ignored
        """
        from repro.core.solvers import SOLVERS
        spec = SOLVERS.get(self.solver)
        if spec is None:
            raise ValueError(f'unknown solver {self.solver!r}; registered: '
                             f'{sorted(SOLVERS)}')
        consumed = set(spec.fields) | set(_TRAINER_FIELDS)
        if spec.builds_backend:
            consumed |= set(_BACKEND_FIELDS)
        for f in dataclasses.fields(self):
            if f.name in consumed:
                continue
            if getattr(self, f.name) != f.default:
                raise ValueError(
                    f'HypergradConfig.{f.name}={getattr(self, f.name)!r} is '
                    f'not consumed by solver={self.solver!r} (it consumes: '
                    f'{", ".join(sorted(spec.fields))}) — it would be '
                    'silently ignored')
        kwargs = {kw: getattr(self, name) for name, kw in spec.fields.items()}
        if spec.builds_backend:
            kwargs['backend'] = self._build_backend()
        return spec.cls(**kwargs)

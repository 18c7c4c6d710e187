"""Activation-sharding context: explicit with_sharding_constraint hints.

GSPMD's sharding propagation is free to replicate the batch axis of
activations when FSDP-sharded weights pull the contraction dims (measured:
217 GB/chip of temps on yi-9b/train before constraints, 13× over budget —
see EXPERIMENTS.md §Dry-run). Production JAX trainers pin activation layouts
explicitly; model code here calls ``constrain(x, 'batch', None, 'model')``
with *logical* entries that resolve against the ambient mesh:

  'batch' → the (pod, data) axes     'model' → the model axis
  None    → unsharded

Entries whose dim does not divide the mesh axis are dropped automatically,
so one call site is valid for every (arch × mesh) combination. Outside an
``activation_mesh`` context (CPU tests, single-host examples) ``constrain``
is the identity.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map_unchecked(f, mesh: Mesh, in_specs, out_specs):
    """``shard_map`` with the static replication checker off.

    Needed whenever an out_spec *claims* replication the checker cannot
    prove — e.g. un-fusing a flat buffer back into leaves that are
    replicated along some mesh axes (the per-device segments really are
    identical there, but only by a value-level argument: they were computed
    from replicated inputs and psum'd reductions). The collective structure
    is unchanged; only the static proof obligation is waived.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_MESH: Mesh | None = None


@contextlib.contextmanager
def activation_mesh(mesh: Mesh):
    """Activate during tracing (jit/lower) of distributed step functions."""
    global _MESH
    prev, _MESH = _MESH, mesh
    try:
        yield
    finally:
        _MESH = prev


def current_mesh() -> Mesh | None:
    return _MESH


def constrain(x: jax.Array, *entries):
    """with_sharding_constraint with logical entries (see module doc)."""
    mesh = _MESH
    if mesh is None:
        return x
    assert len(entries) == x.ndim, (entries, x.shape)
    resolved = []
    for dim, e in zip(x.shape, entries):
        if e is None:
            resolved.append(None)
            continue
        axes = (tuple(a for a in ('pod', 'data') if a in mesh.axis_names)
                if e == 'batch' else (e,))
        axes = tuple(a for a in axes if a in mesh.axis_names)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if size > 1 and dim % size == 0:
            resolved.append(axes if len(axes) > 1 else axes[0])
        else:
            resolved.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))

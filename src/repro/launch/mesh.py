"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — device count is locked at first
jax init, and only launch/dryrun.py (which sets XLA_FLAGS before any import)
may see the 512-placeholder topology.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: GSPMD propagates shardings from the
    parameter specs and the model's ``constrain`` hints, as this code base
    is written for (JAX's default is Explicit axes, which reject the
    unconstrained entries that differentiating a constraint produces)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 chips per pod ('data','model'); two pods add a leading 'pod' axis
    (cross-pod traffic = batch-gradient all-reduce over DCN only)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    data = data or (n // model)
    return _mesh((data, model), ("data", "model"))

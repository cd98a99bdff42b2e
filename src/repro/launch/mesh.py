"""Production mesh factory.

A FUNCTION (not a module constant) so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS *before* first jax init.
Axes are ``Auto``: the models place arrays with ``with_sharding_constraint``
(``distributed.sharding.constrain``), which explicit axes reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh for tests (requires >= pod*data*model local devices)."""
    if pod > 1:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

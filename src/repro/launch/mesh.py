"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax


def mesh_axis_types_kwargs(n_axes: int) -> dict:
    """``axis_types`` kwarg for ``jax.make_mesh``: every axis ``Auto`` —
    the sharding-in-types default the repo's logical-axis rules assume."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **mesh_axis_types_kwargs(len(axes)))


def make_dev_mesh(model: int = 1, data: int = 1):
    """Small mesh for CPU multi-device tests (subprocess sets device count)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         **mesh_axis_types_kwargs(2))

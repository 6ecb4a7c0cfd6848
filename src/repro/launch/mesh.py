"""Production mesh definitions.

A function (never a module-level constant) so importing this module never
touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_auto(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis stated as ``AxisType.Auto`` (the
    compiler propagates shardings; ``jax.make_mesh`` alone defaults to
    explicit axes).  ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def data_axes_of(mesh) -> tuple:
    """Axes usable for batch/data parallelism on this mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))

"""Production mesh definitions.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state.  The dry-run launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import; everything else (tests, benches, examples) sees the real
single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """Auto axes: shardings propagate through GSPMD, so model code stays
    free of per-op ``out_sharding`` arguments."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods for the multi-pod dry run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over local devices (tests / CPU examples)."""
    return _make_mesh((data, model), ("data", "model"))


def mesh_context(mesh):
    """Context manager activating ``mesh`` (``jax.set_mesh``), against
    which with_sharding_constraint(PartitionSpec) resolves."""
    return jax.set_mesh(mesh)


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def n_chips(mesh) -> int:
    return int(mesh.devices.size)

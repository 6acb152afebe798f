"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benches see 1 device.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names, for CPU tests."""
    return _make_mesh((1, 1), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple:
    """Axes used for data parallelism (pod outermost when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)

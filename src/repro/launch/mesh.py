"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips (data, model);
multi-pod: 2×16×16 = 512 chips (pod, data, model).

``make_mesh_compat`` builds every mesh in src/ and tests/ with ``Auto``
axis types, so sharding is propagated by the compiler (GSPMD) rather than
made explicit in the program.
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """jax.make_mesh with every axis of type ``Auto``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_mesh_from_shape(mesh_shape: dict[str, int]):
    """Arbitrary (possibly degraded) mesh, e.g. after elastic rescale."""
    names = tuple(n for n in ("pod", "data", "model") if n in mesh_shape)
    shape = tuple(mesh_shape[n] for n in names)
    return make_mesh_compat(shape, names)


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def chips(mesh) -> int:
    return int(mesh.devices.size)

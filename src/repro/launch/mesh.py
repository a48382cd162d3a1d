"""Production mesh definitions.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax initialization).  Every axis is
``AxisType.Auto``: the code leaves sharding propagation to the compiler.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (elastic serving re-meshes at varying DP degrees)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes that carry the batch: ('pod', 'data') when 'pod' exists."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


__all__ = ["make_production_mesh", "make_mesh", "data_axes", "model_axis_size"]

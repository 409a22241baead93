"""Production meshes (single-pod 16x16 and 2-pod 2x16x16).

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with the Auto axis type on every axis (the
    sharding rules and the DART heap rely on propagation, which the
    default Explicit axes turn off)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def mesh_axis_names(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def dp_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The data-parallel axes present in this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)

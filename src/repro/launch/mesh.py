"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run launcher sets XLA_FLAGS for 512 host devices *before*
any jax import; everything else sees the real device count.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_axes(mesh) -> dict:
    return dict(mesh.shape)


def dp_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)

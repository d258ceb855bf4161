"""Production meshes (assignment spec).

Defined as functions — importing this module never touches jax device state.
Single pod: (data=16, model=16) = 256 chips; multi-pod adds a leading
pod axis: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests/examples)."""
    return _mesh(tuple(shape), tuple(axes))

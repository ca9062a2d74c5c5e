"""Device meshes.

Every mesh in the repo is built by ``make_mesh`` with *Auto* axes: the
sharding helpers (``with_sharding_constraint`` under ``use_mesh``) and the
``shard_map`` executor are written for compiler-propagated shardings, not
for the explicit-sharding types ``jax.make_mesh`` defaults to.

Functions, not module constants: importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """Mesh of the given shape and axis names, every axis Auto.

    ``devices`` defaults to ``jax.devices()``; pass a list to build the mesh
    over other devices (e.g. a described topology's, for compile-only
    checks).
    """
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


"""Mesh construction and ambient-mesh installation.

* ``make_mesh(shape, axes)`` — the one way this repo builds a Mesh: every axis
  is ``AxisType.Auto``, so GSPMD decides placement.
* ``set_mesh(mesh)`` — context manager installing ``mesh`` as the ambient
  mesh (bare-``PartitionSpec`` sharding constraints resolve against it).
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None) -> jax.sharding.Mesh:
    """Build a Mesh with all-auto axis types."""
    shape = tuple(shape)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} / axis names {axes} length mismatch")
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types, devices=devices)


def set_mesh(mesh: jax.sharding.Mesh):
    """Context manager installing `mesh` as the ambient mesh.

    Usage: ``with compat.set_mesh(mesh): ...`` — inside the scope,
    ``compat.current_mesh()`` returns it and bare-PartitionSpec
    ``with_sharding_constraint`` resolves against it.
    """
    return jax.set_mesh(mesh)

"""Ambient-mesh lookup and shard_map.

* ``current_mesh()`` — the mesh installed by ``compat.set_mesh`` (or any mesh
  context manager), or None when there is none.
* ``shard_map(f, mesh=..., in_specs=..., out_specs=..., axis_names=...,
  check_vma=...)`` — ``jax.shard_map``; ``axis_names`` (the *manual* axes,
  default all) must name axes of ``mesh``.
"""
from __future__ import annotations

from typing import Callable, Set

import jax


def current_mesh():
    """The ambient (context-installed) mesh, or None if none is active.

    Never raises on empty/absent meshes — callers treat None as "no mesh":
    sharding constraints become no-ops.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def current_mesh_axis_sizes() -> dict | None:
    """{axis_name: size} of the ambient mesh, or None outside any mesh."""
    mesh = current_mesh()
    if mesh is None:
        return None
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def shard_map(
    f: Callable,
    *,
    mesh,
    in_specs,
    out_specs,
    axis_names: Set[str] | None = None,
    check_vma: bool = False,
) -> Callable:
    """``jax.shard_map`` with the manual axes (default: all) checked against
    the mesh."""
    names = frozenset(mesh.axis_names if axis_names is None else axis_names)
    unknown = names - frozenset(mesh.axis_names)
    if unknown:
        raise ValueError(f"axis_names {sorted(unknown)} not in mesh axes {mesh.axis_names}")
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=set(names),
        check_vma=check_vma,
    )

"""Thin forwards over the mesh and cost-analysis surface of the installed JAX.

* ``make_mesh(shape, axes)``    — Mesh with all-auto axis types (GSPMD places)
* ``set_mesh(mesh)``            — ambient-mesh context manager
* ``current_mesh()``            — the ambient mesh, or None outside any mesh
* ``current_mesh_axis_sizes()`` — {axis: size} of the ambient mesh
* ``shard_map(...)``            — ``jax.shard_map`` with the manual axes checked
* ``normalized_cost_analysis(c)`` — flat cost-metric dict of a compiled program
"""
from repro.compat.mesh import make_mesh, set_mesh
from repro.compat.sharding import current_mesh, current_mesh_axis_sizes, shard_map
from repro.compat.xla import normalized_cost_analysis

__all__ = [
    "make_mesh",
    "set_mesh",
    "current_mesh",
    "current_mesh_axis_sizes",
    "shard_map",
    "normalized_cost_analysis",
]

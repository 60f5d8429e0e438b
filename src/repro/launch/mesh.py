"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax device query.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_auto(shape, axes):
    """Mesh with all axes in Auto (collective) mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for in-container multi-device tests (8 host devices)."""
    return make_mesh_auto((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes that carry data parallelism ('pod' folds into data)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name) -> int:
    return mesh.shape[name]

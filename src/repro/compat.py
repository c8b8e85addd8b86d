"""Mesh helpers shared by every ``shard_map`` in the repo.

``jax.make_mesh`` types its axes ``Explicit``: each array built on such a
mesh carries its sharding in its type, and eager indexing, reshapes and
``with_sharding_constraint`` then refuse work that GSPMD would lay out
itself. The engine places and indexes its own arrays, so it runs every
mesh it is given with ``Auto`` axes over the same devices.
"""
from __future__ import annotations

import jax


def auto_mesh(mesh):
    """``mesh`` with every axis ``Auto`` (same devices, same axis names)."""
    if mesh is None or not mesh.explicit_axes:
        return mesh
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names)


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` over ``auto_mesh(mesh)``; ``check`` is check_vma."""
    return jax.shard_map(f, mesh=auto_mesh(mesh), in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)

"""Mesh construction and shard_map entry points.

The repo's meshes are always fully "auto" (GSPMD derives the
collectives), so every mesh is built with ``AxisType.Auto`` axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

shard_map = jax.shard_map


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``shard_map`` with the varying-manual-axes check off.

    The kernel wrappers run Pallas calls inside the mapped body; the
    checker has no rule for them, so checking must be disabled."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)

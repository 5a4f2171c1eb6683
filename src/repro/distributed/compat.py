"""The two mesh primitives the distributed runtime builds on, spelled once.

``shard_map`` maps the engine's ``check`` flag onto ``jax.shard_map``'s
``check_vma``; ``make_mesh`` builds meshes with Auto axis types, the
sharding mode every mesh in this repo is written for.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off unless asked for;
    usable as a decorator through ``functools.partial``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


__all__ = ["shard_map", "make_mesh"]

"""The mesh helpers every distributed module imports: ``shard_map`` and a
``make_mesh`` whose axes are all Auto."""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types (opting out of explicit
    sharding on every axis)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


__all__ = ["make_mesh", "shard_map"]

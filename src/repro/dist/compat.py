"""The one spelling of mesh construction and shard_map the repo uses.

Everything in ``repro`` that builds a mesh or enters a shard_map region
goes through these two functions, so the axis types and the replication
check are decided in one place.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f, mesh, *, in_specs, out_specs):
    """``jax.shard_map`` without replication checking: the MoE and
    ring-attention bodies compute routing redundantly per rank, which the
    checker cannot verify."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

"""Mesh and shard_map construction in one place.

Every mesh in the repo is built with Auto axis types and every
``shard_map`` goes through :func:`shard_map`, whose ``check_vma=None``
keeps JAX's default (the planner turns the check off for programs that
may hold a ``pallas_call``, which has no replication rule).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax

AxisType = jax.sharding.AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices: Optional[Sequence[Any]] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    """``jax.shard_map``; ``check_vma=None`` leaves JAX's default."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


__all__ = ["AxisType", "make_mesh", "shard_map"]

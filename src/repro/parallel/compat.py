"""Mesh and manual-partitioning entry points for the installed jax (0.9).

Two spellings live here so every call site agrees on them:

* :func:`make_mesh` — ``jax.make_mesh`` with **Auto** axis types.  Since
  jax 0.5 ``make_mesh`` defaults to Explicit axes, and
  :meth:`~repro.parallel.mesh_rules.MeshRules.constrain` (a
  ``with_sharding_constraint``) may only name Auto axes.
* :func:`shard_map` — ``jax.shard_map`` with this repo's keyword set.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "shard_map"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """A device mesh whose axes are all Auto (sharding by constraint)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)

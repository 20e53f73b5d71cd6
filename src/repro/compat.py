"""Thin wrappers over the jax sharding calls the model and join code make.

Call sites import ``shard_map`` / ``sharding_constraint`` from here so one
module owns the spelling of these APIs.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec


def sharding_constraint(x, spec: PartitionSpec):
    """``jax.lax.with_sharding_constraint`` of ``x`` to ``spec``."""
    return jax.lax.with_sharding_constraint(x, spec)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` lists the manual axes (all of the
    mesh's when None)."""
    kwargs = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)

"""Thin wrappers over jax sharding APIs the embedding step uses."""
from __future__ import annotations

import jax


def shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: the episode
    step's tuple-of-subparts carry defeats the checker."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_flat_index(axis_names, sizes):
    """Row-major flat index of this device across ``axis_names``, for use
    inside shard_map. Mesh extents are passed statically (python ints)."""
    idx = jax.lax.axis_index(axis_names[0])
    for name, n in zip(axis_names[1:], sizes[1:]):
        idx = idx * n + jax.lax.axis_index(name)
    return idx

"""Tables made on the device from a run's seed, one jitted call each.

Row block ``b`` (of ``block_rows(rows)`` rows) of table ``t`` is
``U(-0.5/d, 0.5/d)`` drawn from ``fold_in(fold_in(key, t), b)``; rows past
the real node count are zero, and values are cast to the served dtype. A
block depends only on (seed, t, b), so the same draw made again inside a
reduction gives a table's starting value without keeping a copy: that is
how the change of the parameters is measured (``change_norm_fn``). Both
walk the table a block at a time, so neither needs more than a block of
scratch beside the table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MAX_BLOCK_ROWS = 1 << 17


def block_rows(rows: int) -> int:
    """The largest divisor of ``rows`` that is at most MAX_BLOCK_ROWS."""
    for b in range(min(rows, MAX_BLOCK_ROWS), 0, -1):
        if rows % b == 0:
            return b
    return 1


def _block(key, t: int, b, rows_b: int, num_nodes: int, dim: int, dtype):
    """Row block ``b`` of table ``t``."""
    x = jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(key, t), b), (rows_b, dim),
        jnp.float32, -0.5 / dim, 0.5 / dim)
    row = b * rows_b + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < num_nodes, x, 0.0).astype(dtype)


@functools.lru_cache(maxsize=None)
def init_fn(t: int, rows: int, num_nodes: int, dim: int, dtype: str,
            sharding=None):
    """``fn(seed) -> table t`` of ``rows`` rows; seed is an int32 scalar."""
    rb = block_rows(rows)
    dt = jnp.dtype(dtype)

    def fn(seed):
        key = jax.random.PRNGKey(seed)

        def body(b, table):
            return jax.lax.dynamic_update_slice_in_dim(
                table, _block(key, t, b, rb, num_nodes, dim, dt), b * rb, 0)
        return jax.lax.fori_loop(0, rows // rb, body,
                                 jnp.zeros((rows, dim), dt))
    return jax.jit(fn, out_shardings=sharding)


def make(tables: int, *args, sharding=None, seed) -> tuple:
    """The first ``tables`` tables, each made and finished in turn."""
    out = []
    for t in range(tables):
        out.append(init_fn(t, *args, sharding=sharding)(seed))
        out[-1].block_until_ready()
    return tuple(out)


@functools.lru_cache(maxsize=None)
def change_norm_fn(t: int, rows: int, num_nodes: int, dim: int, dtype: str):
    """``fn(table, seed) -> ||table - start||`` in f32, for table ``t``
    started from ``seed`` in ``dtype``."""
    rb = block_rows(rows)

    def fn(table, seed):
        key = jax.random.PRNGKey(seed)

        def body(b, acc):
            start = _block(key, t, b, rb, num_nodes, dim,
                           jnp.dtype(dtype)).astype(jnp.float32)
            part = jax.lax.dynamic_slice_in_dim(table, b * rb, rb)
            diff = part.astype(jnp.float32) - start
            return acc + jnp.sum(diff * diff)
        return jnp.sqrt(jax.lax.fori_loop(0, rows // rb, body,
                                          jnp.float32(0)))
    return jax.jit(fn)

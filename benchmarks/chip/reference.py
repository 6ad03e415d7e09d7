"""Plain references that decide ``correct``. They import nothing of the
program under test and take nothing it made: tables come from
``tables.py`` and the run's seed, samples and queries from ``traffic.py``.

SGNS training (``sgns_episode``). One episode is sequential minibatch SGD
over the episode's pairs, in the order the hybrid schedule defines: for
each ring round, each device, each of the ``k`` sub-parts of the vertex
shard the device holds in that round, the pairs (u, v) whose context row v
the device owns and whose vertex row u lies in that sub-part, in episode
order, at most ``block_cap`` of them, in minibatches of ``minibatch``. A
minibatch shares ``negatives`` context rows drawn from the device's pool
by ``randint(split(key))`` of a key chain that starts at
``fold_in(PRNGKey(seed), device)`` and advances once per minibatch slot of
the ``block_cap`` slots, used or not. Its loss is
``sum_b m_b [softplus(-<v_b, c_b>) + sum_s softplus(<v_b, n_s>)]``; the
gradients are taken at the minibatch's starting rows, summed per row over
duplicates, and applied as ``row + cast(-lr * grad)`` in the table dtype.
The episode's loss is the summed loss over the number of samples.

Top-k retrieval (``topk_exact``): scores ``q . row`` in f32 at full
precision over every real row, best first, ties to the smaller row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH,
              "default": jax.lax.Precision.DEFAULT}


# ------------------------------------------------------------- SGNS order
def schedule(pairs: np.ndarray, *, num_nodes: int, chips: int,
             subparts: int, minibatch: int, block_cap: int,
             half_batch: bool = False):
    """Minibatches of one episode in schedule order.

    Returns u, v (M, minibatch) int32 global rows, mask (M, minibatch) f32,
    dev (M,) int32 and slot (M,) int32 (the minibatch's position in its
    device's key chain), with M fixed by the episode size so that every
    episode of a mix has one shape; unused minibatches are all masked.
    ``half_batch`` masks the second half of every minibatch (a fault).
    """
    P, k, mb = chips, subparts, minibatch
    rows_shard = -(-num_nodes // P)
    rows_sub = -(-rows_shard // k)
    rows_pad = rows_sub * k
    u = pairs[:, 0].astype(np.int64)
    v = pairs[:, 1].astype(np.int64)
    dev = v // rows_pad
    vs = u // rows_pad
    rnd = (dev - vs) % P
    sub = (u % rows_pad) // rows_sub
    cell = (rnd * P + dev) * k + sub            # round-major schedule order
    order = np.argsort(cell, kind="stable")
    cs = cell[order]
    starts = np.searchsorted(cs, np.arange(P * P * k + 1))
    nmb = block_cap // mb
    M = pairs.shape[0] // mb + P * P * k
    U = np.zeros((M, mb), np.int32)
    V = np.zeros((M, mb), np.int32)
    W = np.zeros((M, mb), np.float32)
    D = np.zeros(M, np.int32)
    S = np.zeros(M, np.int32)
    m = 0
    for c in range(P * P * k):
        sel = order[starts[c]:starts[c + 1]][:block_cap]
        r, rest = divmod(c, P * k)
        d, j = divmod(rest, k)
        for i in range(-(-sel.size // mb)):
            s = sel[i * mb:(i + 1) * mb]
            U[m, :s.size] = u[s]
            V[m, :s.size] = v[s]
            W[m, :s.size] = 1.0
            D[m] = d
            S[m] = (r * k + j) * nmb + i
            m += 1
    if half_batch:
        W[:, mb // 2:] = 0.0
    return U, V, W, D, S


@functools.lru_cache(maxsize=None)
def _key_chain_fn(n: int):
    def fn(seed, dev):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), dev)

        def body(key, _):
            key, kneg = jax.random.split(key)
            return key, kneg
        return jax.lax.scan(body, key, None, length=n)[1]
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _episode_fn(negatives: int, pool_n: int, rows_pad: int, dtype: str,
                precision: str):
    dt = jnp.dtype(dtype)
    f32 = jnp.float32
    mm = functools.partial(jnp.matmul, precision=PRECISIONS[precision])

    def fn(vert, ctx, pool, U, V, W, D, K, lr):
        def body(carry, x):
            vert, ctx, loss = carry
            u, v, m, d, kneg = x
            pidx = jax.random.randint(kneg, (negatives,), 0, pool_n)
            neg = pool[d, pidx] + d * rows_pad
            vr = vert[u].astype(f32)
            cr = ctx[v].astype(f32)
            nr = ctx[neg].astype(f32)
            pos = jnp.sum(vr * cr, axis=-1)
            sc = mm(vr, nr.T)
            g_pos = (jax.nn.sigmoid(pos) - 1.0) * m
            g_neg = jax.nn.sigmoid(sc) * m[:, None]
            dv = g_pos[:, None] * cr + mm(g_neg, nr)
            dc = g_pos[:, None] * vr
            dn = mm(g_neg.T, vr)
            loss = loss + jnp.sum(m * jax.nn.softplus(-pos)) + jnp.sum(
                m[:, None] * jax.nn.softplus(sc))
            # per-row sums over duplicates, then one cast update per row
            tv = mm((u[:, None] == u[None, :]).astype(f32), dv)
            ic = jnp.concatenate([v, neg])
            tc = mm((ic[:, None] == ic[None, :]).astype(f32),
                    jnp.concatenate([dc, dn]))

            def apply(rows, g):
                new = rows + (-lr * g).astype(dt).astype(f32)
                return new.astype(dt).astype(vert.dtype)
            vert = vert.at[u].set(apply(vr, tv))
            ctx = ctx.at[ic].set(apply(jnp.concatenate([cr, nr]), tc))
            return (vert, ctx, loss), None
        (vert, ctx, loss), _ = jax.lax.scan(
            body, (vert, ctx, jnp.float32(0.0)), (U, V, W, D, K))
        return vert, ctx, loss
    return jax.jit(fn, donate_argnums=(0, 1))


def sgns_episode(vert, ctx, pool, sched, *, seed: int, lr: float,
                 negatives: int, block_cap: int, minibatch: int,
                 subparts: int, rows_pad: int, chips: int,
                 dtype: str | None = None, precision: str = "highest"):
    """One episode on (vert, ctx) (donated). Returns (vert, ctx, loss).

    ``dtype`` (default: the tables') is the type every stored value is
    rounded to; a type narrower than bfloat16 is kept in f32 tables."""
    U, V, W, D, S = sched
    chain = -(-block_cap // minibatch) * subparts * chips
    keys = jnp.stack([_key_chain_fn(chain)(jnp.int32(seed), jnp.int32(d))
                      for d in range(chips)])
    K = keys[jnp.asarray(D), jnp.asarray(S)]
    fn = _episode_fn(negatives, pool.shape[1], rows_pad,
                     dtype or str(jnp.dtype(vert.dtype)), precision)
    vert, ctx, loss = fn(vert, ctx, jnp.asarray(pool), jnp.asarray(U),
                         jnp.asarray(V), jnp.asarray(W), jnp.asarray(D), K,
                         jnp.float32(lr))
    return vert, ctx, float(loss) / max(float(W.sum()), 1.0)


# ------------------------------------------------------------------ top-k
@functools.lru_cache(maxsize=None)
def _block_topk_fn(k: int, precision: str, dtype: str):
    dt = jnp.dtype(dtype)

    def fn(q, rows, base, valid):
        s = jnp.matmul(q.astype(dt), rows.astype(dt).T,
                       precision=PRECISIONS[precision],
                       preferred_element_type=jnp.float32)
        ids = base + jnp.arange(rows.shape[0], dtype=jnp.int32)
        s = jnp.where(ids[None, :] < valid, s, -jnp.inf)
        v, i = jax.lax.top_k(s, k)
        return v, ids[i]
    return jax.jit(fn)


def topk_exact(table, queries, k: int, valid: int, *, block: int = 1 << 19,
               precision: str = "highest", dtype: str = "float32"):
    """(vals, ids) of the k best real rows per query, best first, ties to
    the smaller row. ``precision``/``dtype`` below f32 make the control."""
    q = jnp.asarray(queries, jnp.float32)
    fn = _block_topk_fn(k, precision, dtype)
    vs, ids = [], []
    n = table.shape[0]
    for lo in range(0, n, block):
        rows = jax.lax.dynamic_slice_in_dim(table, lo, min(block, n - lo))
        v, i = fn(q, rows, jnp.int32(lo), jnp.int32(valid))
        vs.append(np.asarray(v))
        ids.append(np.asarray(i))
    v = np.concatenate(vs, axis=1)
    i = np.concatenate(ids, axis=1)
    order = np.lexsort((i, -v), axis=1)[:, :k]
    return (np.take_along_axis(v, order, axis=1),
            np.take_along_axis(i, order, axis=1))


@functools.lru_cache(maxsize=None)
def _score_fn():
    def fn(table, q, ids):
        rows = table[ids].astype(jnp.float32)             # (Q, k, d)
        return jnp.einsum("qd,qkd->qk", q, rows,
                          precision=jax.lax.Precision.HIGHEST)
    return jax.jit(fn)


def scores(table, queries, ids) -> np.ndarray:
    """Exact f32 scores of given (Q, k) ids."""
    ids = np.clip(np.asarray(ids), 0, table.shape[0] - 1)
    return np.asarray(_score_fn()(table, jnp.asarray(queries, jnp.float32),
                                  jnp.asarray(ids)))

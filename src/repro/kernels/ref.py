"""Pure-jnp oracles for the Pallas kernels.

These are the numerical ground truth: every Pallas kernel in this package has
a matching function here, and tests assert allclose between the two across a
shape/dtype sweep. They are also the CPU execution path for real training
runs in this container (Pallas interpret mode is Python-slow).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sgns_grads_ref(v: jax.Array, c: jax.Array, n: jax.Array, mask: jax.Array):
    """Shared-negative SGNS loss + grads for one minibatch.

    Args:
      v:    (B, d) gathered vertex rows (centers).
      c:    (B, d) gathered context rows (positives).
      n:    (S, d) gathered shared negative context rows.
      mask: (B,) float {0,1} — padding mask.

    Returns:
      (loss, dv, dc, dn): scalar summed loss, (B,d), (B,d), (S,d) grads of
      the summed loss w.r.t. v, c, n.

    Math: loss = Σ_b m_b [ softplus(-⟨v_b,c_b⟩) + Σ_s softplus(⟨v_b,n_s⟩) ].
    Matmuls run at full f32 precision (the TPU's default rounds f32 operands
    to bf16), like the kernels they check.
    """
    f32 = jnp.float32
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    v32, c32, n32 = v.astype(f32), c.astype(f32), n.astype(f32)
    m = mask.astype(f32)
    pos = jnp.sum(v32 * c32, axis=-1)                 # (B,)
    neg = mm(v32, n32.T)                              # (B, S)
    g_pos = (jax.nn.sigmoid(pos) - 1.0) * m           # dL/dpos
    g_neg = jax.nn.sigmoid(neg) * m[:, None]          # dL/dneg
    dv = g_pos[:, None] * c32 + mm(g_neg, n32)        # (B, d)
    dc = g_pos[:, None] * v32                         # (B, d)
    dn = mm(g_neg.T, v32)                             # (S, d)
    loss = jnp.sum(m * jax.nn.softplus(-pos)) + jnp.sum(
        m[:, None] * jax.nn.softplus(neg)
    )
    return loss, dv.astype(v.dtype), dc.astype(c.dtype), dn.astype(n.dtype)


def gather_rows_ref(table: jax.Array, idx: jax.Array) -> jax.Array:
    """(N, d) table, (B,) int32 -> (B, d)."""
    return jnp.take(table, idx, axis=0)


def scatter_add_rows_ref(table: jax.Array, idx: jax.Array, upd: jax.Array) -> jax.Array:
    """(N, d) table += updates at rows idx (duplicates accumulate)."""
    return table.at[idx].add(upd.astype(table.dtype))


def sgns_step_ref(vert: jax.Array, ctx: jax.Array, idx_v: jax.Array,
                  idx_c: jax.Array, idx_n: jax.Array, mask: jax.Array,
                  lr: jax.Array):
    """One full SGNS SGD minibatch against local shards (oracle for the fused op).

    vert: (Nv, d) local vertex sub-shard;  ctx: (Nc, d) local context shard.
    Returns (vert', ctx', loss).
    """
    v = gather_rows_ref(vert, idx_v)
    c = gather_rows_ref(ctx, idx_c)
    n = gather_rows_ref(ctx, idx_n)
    loss, dv, dc, dn = sgns_grads_ref(v, c, n, mask)
    vert = scatter_add_rows_ref(vert, idx_v, -lr * dv)
    # ONE combined scatter for both context updates (exactly equivalent:
    # scatter-add commutes). Two chained scatters defeat XLA's while-carry
    # in-place aliasing and force full-table copies every minibatch —
    # EXPERIMENTS.md §Perf hillclimb A.
    idx_cn = jnp.concatenate([idx_c, idx_n])
    upd_cn = jnp.concatenate([-lr * dc, -lr * dn])
    ctx = scatter_add_rows_ref(ctx, idx_cn, upd_cn)
    return vert, ctx, loss


def topk_mips_ref(table, queries, k: int):
    """Numpy oracle for exact-MIPS top-k retrieval (embed_serve.topk).

    table: (N, d); queries: (Q, d). Scores are the f32 inner products
    queries @ table.T (matching the kernels, which cast to f32 before the
    MXU dot); ties break toward the smaller row index — `kind="stable"` on
    the negated scores is exactly that rule.

    Returns (vals (Q, k) f32, idx (Q, k) int32). Numpy (not jnp) on
    purpose: this is the serving subsystem's ground truth, so it must not
    share an execution path with anything it validates.
    """
    t = np.asarray(table).astype(np.float32)
    q = np.asarray(queries).astype(np.float32)
    scores = q @ t.T                                  # (Q, N) f32
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, order, axis=1)
    return vals, order.astype(np.int32)

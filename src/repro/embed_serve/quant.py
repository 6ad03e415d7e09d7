"""Two-tier quantized retrieval: int8 first-pass scan + exact f32 rescore.

The exact MIPS scan is bandwidth-bound — every table byte is read once per
query block, so scan bytes *are* the latency roofline. This module trades
arithmetic for bandwidth the way GraphVite trades capacity for compact
on-GPU tables: a symmetric per-row int8 copy of each shard is scanned
first (4x fewer bytes than f32), keeping an over-fetched top-``m``
candidate set per query (``m = ceil(k * overfetch)``), and only the ``m``
survivors' full-precision rows are gathered back and re-scored exactly.

Tier one (:func:`repro.embed_serve.topk.topk_mips_quant`) is approximate
by at most the quantization error, which is bounded per row (see
:func:`quantize_rows`); tier two (:func:`rescore_exact`) re-ranks the
survivors with the same f32 scores and smaller-index tie rule as the full
exact scan, so whenever the candidate set contains the true top-k — the
overfetch margin's job — the final (Q, k) result equals
``kernels.ref.topk_mips_ref`` exactly. That containment is not proven a
priori on arbitrary data; it is *gated*: the CLI's ``--check-recall`` and
``bench_serve``'s recall assertion compare against the numpy oracle every
run, so a too-thin margin fails loudly instead of serving quietly wrong.
(Concretely observed: cosine serving over a barely-trained, near-collinear
table compresses the score range until the rank-m boundary sits inside the
quantization error — the gate fails at the default margin, and a wider
``--overfetch`` restores exactness. Size the margin per workload.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.embed_serve import topk as tk
from repro.kernels import sgns as _k

INT8_QMAX = 127          # symmetric: values in [-127, 127]; -128 unused so
                         # the range (and the error bound) is sign-balanced
DEFAULT_OVERFETCH = 4.0  # m = ceil(k * overfetch) tier-one survivors


def quantize_rows(table):
    """Symmetric per-row int8 quantization of a (N, d) table.

    Returns ``(q (N, d) int8, scale (N,) f32)`` with
    ``scale_r = max|row_r| / 127`` (1.0 for an all-zero row, which
    round-trips exactly) and ``q = round(row / scale_r)``.

    Round-trip bound (documented and property-tested): no value clips —
    ``|x| <= 127 * scale_r`` by construction — so the only error is the
    rounding, ``|scale_r * q - x| <= scale_r / 2 = max|row_r| / 254``
    elementwise. A quantized MIPS score against query ``u`` is therefore
    off by at most ``||u||_1 * scale_r / 2`` for row r.

    bf16 tables are quantized through their f32 values (bitwise-stable:
    bf16 -> f32 is exact), so serving's quant tier sees the same numbers
    the exact tier scores.
    """
    x = np.asarray(jnp.asarray(table).astype(jnp.float32))
    amax = np.max(np.abs(x), axis=1)
    scale = np.where(amax > 0, amax / INT8_QMAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(x / scale[:, None]), -INT8_QMAX, INT8_QMAX)
    return q.astype(np.int8), scale


def dequantize_rows(q, scale) -> np.ndarray:
    """(N, d) int8 + (N,) f32 scales -> the (N, d) f32 reconstruction."""
    return np.asarray(q, np.float32) * np.asarray(scale, np.float32)[:, None]


def overfetch_m(k: int, overfetch: float, n_rows: int) -> int:
    """Tier-one candidate count: ceil(k * overfetch), at least k, clamped
    to the shard's rows (a shard can't yield more candidates than rows —
    and at m == n_rows the two-tier scan degenerates to exhaustive-exact,
    so small/degraded shards are automatically safe)."""
    return max(1, min(max(k, math.ceil(k * overfetch)), n_rows))


@functools.partial(jax.jit, static_argnames=("k", "gather", "interpret"))
def rescore_exact(table, queries, cand_idx, *, k: int, gather: str = "xla",
                  interpret: bool = False):
    """Tier two: gather the surviving rows, re-score in f32, re-rank.

    table: the (N, d) full-precision shard (f32/bf16); cand_idx: (Q, m)
    shard-local ids from the int8 first pass (sentinel slots from short
    shards allowed — they gather row 0 but score -inf and keep losing).
    ``gather="pallas"`` routes the (Q*m,) flat gather through the
    training-side blocked-DMA ``kernels.sgns.gather_rows``; ``"xla"`` is
    the plain ``jnp.take`` CPU path. Selection is the shared
    :func:`topk.select_topk`, so the tie rule cannot diverge from the
    exact scan's.

    Returns ((Q, k) f32, (Q, k) i32) — the exact top-k *of the candidate
    set* under the oracle's total order.
    """
    Q, m = cand_idx.shape
    d = table.shape[1]
    idx = cand_idx.astype(jnp.int32)
    safe = jnp.where(idx == tk.IDX_SENTINEL, 0, idx).reshape(-1)
    if gather == "pallas":
        rows = _k.gather_rows(table, safe, interpret=interpret)
    else:
        rows = jnp.take(table, safe, axis=0)
    rows = rows.reshape(Q, m, d).astype(jnp.float32)
    scores = jnp.einsum("qd,qmd->qm", queries.astype(jnp.float32), rows,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jnp.where(idx == tk.IDX_SENTINEL, tk.NEG_INF, scores)
    return tk.select_topk(scores, idx, k)


def topk_mips_quant_rescored(table, qtable, scales, queries, *, k: int,
                             overfetch: float = DEFAULT_OVERFETCH,
                             valid: int | None = None,
                             block_q: int = tk.DEFAULT_BLOCK_Q,
                             block_n: int | None = None,
                             impl: str = "pallas",
                             interpret: bool = False):
    """The full two-tier shard scan: int8 top-m, exact rescore to top-k.

    table and (qtable, scales) must cover the same rows in the same order
    (``quantize_rows(table)``); `valid` masks padded tail rows in both
    tiers. impl: "pallas" streams int8 tiles through the double-buffered
    DMA kernel and gathers survivors with the blocked-DMA gather; "xla" is
    the plain-jnp CPU path. Output layout matches :func:`topk.topk_mips`.
    """
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown quant impl {impl!r}")
    n_rows = valid if valid is not None else qtable.shape[0]
    m = overfetch_m(k, overfetch, n_rows)
    if impl == "pallas":
        _, ci = tk.topk_mips_quant(qtable, scales, queries, m=m,
                                   valid=valid, block_q=block_q,
                                   block_n=block_n, interpret=interpret)
        return rescore_exact(table, queries, ci, k=k, gather="pallas",
                             interpret=interpret)
    _, ci = tk.topk_mips_quant_xla(qtable, scales, queries, m=m,
                                   valid=valid)
    return rescore_exact(table, queries, ci, k=k, gather="xla")

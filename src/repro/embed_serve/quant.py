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
QUANT_BLOCK_ROWS = 1 << 16  # rows per step of the device quantizer


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

    This numpy version is the plain reference; the store quantizes its
    shards on the device with :func:`quantize_device`, which gives the
    same bits.
    """
    x = np.asarray(jnp.asarray(table).astype(jnp.float32))
    amax = np.max(np.abs(x), axis=1)
    scale = np.where(amax > 0, amax / INT8_QMAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(x / scale[:, None]), -INT8_QMAX, INT8_QMAX)
    return q.astype(np.int8), scale


@functools.partial(jax.jit, static_argnames=("block_rows",))
def quantize_device(table, *, block_rows: int = QUANT_BLOCK_ROWS):
    """:func:`quantize_rows` on the device, in fixed blocks of rows.

    table: a device-resident (N, d) shard in any served dtype. Returns
    ``(q (N, d) int8, scale (N,) f32)`` on the shard's device, bitwise
    what :func:`quantize_rows` (the plain reference) gives: the same
    symmetric per-row rule, round half to even, clip at +-127, with both
    quotients correctly rounded f32 (:func:`_div_exact`). One
    ``fori_loop`` step quantizes ``block_rows`` rows into the outputs in
    place, so the peak temporary is one block's f32 copy, not the shard's.
    The last block ends at row N (it overlaps the one before when N is not
    a multiple; those rows are written twice, alike).
    """
    n, d = table.shape
    b = min(block_rows, n)
    f32 = jnp.float32

    def block(j, carry):
        q8, sc = carry
        lo = jnp.minimum(j * b, n - b)
        x = jax.lax.dynamic_slice_in_dim(table, lo, b).astype(f32)
        amax = jnp.max(jnp.abs(x), axis=1)
        scale = jnp.where(amax > 0,
                          _div_exact(amax, jnp.full_like(amax, INT8_QMAX)),
                          f32(1.0))
        q = jax.lax.round(_div_exact(x, jnp.broadcast_to(scale[:, None],
                                                         x.shape)),
                          jax.lax.RoundingMethod.TO_NEAREST_EVEN)
        q = jnp.clip(q, -INT8_QMAX, INT8_QMAX).astype(jnp.int8)
        return (jax.lax.dynamic_update_slice_in_dim(q8, q, lo, 0),
                jax.lax.dynamic_update_slice_in_dim(sc, scale, lo, 0))

    init = (jnp.zeros((n, d), jnp.int8), jnp.zeros((n,), f32))
    return jax.lax.fori_loop(0, -(-n // b), block, init)


def _div_exact(a, b):
    """``a / b`` in f32, correctly rounded (to nearest, ties to even), as
    numpy divides: a TPU v5e's f32 division is not (5,793,316 of 2^24
    random quotients differed from numpy's in the last bit), and XLA turns
    a division by a constant into a multiplication by its rounded
    reciprocal. The 24-bit mantissas are divided in int32 arithmetic, one
    quotient bit a step, with the remainder as the sticky bit.

    b > 0. Where a is zero or subnormal, or the quotient is not a normal
    f32, the result is the plain ``a / b``.
    """
    i32 = jnp.int32
    ab = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bb = jax.lax.bitcast_convert_type(b, jnp.uint32)
    ea = ((ab >> 23) & 0xFF).astype(i32)
    eb = ((bb >> 23) & 0xFF).astype(i32)
    ma = ((ab & 0x7FFFFF) | 0x800000).astype(i32)
    mb = ((bb & 0x7FFFFF) | 0x800000).astype(i32)
    low = ma < mb                 # scale ma into [mb, 2 mb): quotient in [1, 2)
    rem = jnp.where(low, ma << 1, ma)
    e = ea - eb + 127 - low.astype(i32)
    q = jnp.zeros_like(rem)
    for _ in range(25):           # the leading bit, 23 fraction bits, guard
        bit = rem >= mb
        q = (q << 1) | bit.astype(i32)
        rem = jnp.where(bit, rem - mb, rem) << 1
    guard, q = q & 1, q >> 1
    up = (guard == 1) & ((rem != 0) | ((q & 1) == 1))   # sticky, or a tie
    q = q + up.astype(i32)
    carry = q >> 24               # rounded up to 2.0
    q, e = q >> carry, e + carry
    bits = ((ab & jnp.uint32(0x80000000)) | (e.astype(jnp.uint32) << 23)
            | (q.astype(jnp.uint32) & 0x7FFFFF))
    exact = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where((ea > 0) & (e > 0) & (e < 255), exact, a / b)


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


def topk_mips_quant_rescored_counted(table, qtable, scales, queries, *,
                                    k: int,
                                    overfetch: float = DEFAULT_OVERFETCH,
                                    valid: int | None = None,
                                    block_q: int = tk.DEFAULT_BLOCK_Q,
                                    block_n: int | None = None,
                                    impl: str = "pallas",
                                    interpret: bool = False):
    """:func:`topk_mips_quant_rescored` and the first pass's counts, as
    the store calls it: ((Q, k) f32, (Q, k) i32, (4,) i32 [row-group
    merges run, (row group, tile) pairs scored, query blocks, zero query
    blocks] of the Pallas int8 pass, None on the "xla" path). The two
    tiers stay two jitted programs (``topk_mips_quant_counted``, then
    :func:`rescore_exact`)."""
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown quant impl {impl!r}")
    n_rows = valid if valid is not None else qtable.shape[0]
    m = overfetch_m(k, overfetch, n_rows)
    if impl == "pallas":
        _, ci, groups = tk.topk_mips_quant_counted(
            qtable, scales, queries, m=m, valid=valid, block_q=block_q,
            block_n=block_n, interpret=interpret)
        v, i = rescore_exact(table, queries, ci, k=k, gather="pallas",
                             interpret=interpret)
        return v, i, groups
    _, ci = tk.topk_mips_quant_xla(qtable, scales, queries, m=m,
                                   valid=valid)
    v, i = rescore_exact(table, queries, ci, k=k, gather="xla")
    return v, i, None


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
    v, i, _ = topk_mips_quant_rescored_counted(
        table, qtable, scales, queries, k=k, overfetch=overfetch,
        valid=valid, block_q=block_q, block_n=block_n, impl=impl,
        interpret=interpret)
    return v, i

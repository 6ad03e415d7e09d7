"""Pallas TPU kernels for the SGNS hot loop (the paper's CUDA kernel, §II-C).

The paper's performance model says node-embedding training is O(1) arithmetic
intensity and therefore memory-bound: the hot loop is gather rows → tiny
dot-products → scatter rows. The TPU-native rethink (DESIGN.md §6):

* **Shared-negative batching** (Ji et al. [19], adopted by the paper's lineage)
  turns the per-edge level-1 dot products into level-3 ``(B,d) @ (d,S)``
  matmuls — exactly the shape the 128×128 MXU wants.
* The whole fwd+bwd for a (Bt, d) tile lives in **VMEM**: one HBM round-trip
  per row, honoring the memory-bound analysis.
* Row gathers use **scalar-prefetched indices** so the index-dependent DMA
  address is known before the block runs (TPU has no hardware gather from
  HBM; scalar prefetch + per-row BlockSpec index_map is the idiom).

Kernels:
  * :func:`sgns_grads`        — dense tile kernel: loss + dv/dc/dn grads (MXU).
  * :func:`sgns_fused_grads`  — DMA-gather + grads in one launch (no apply).
  * :func:`sgns_fused_update` — the paper's full fused hot loop: pipelined
    double-buffered gather → grads → **in-kernel SGD apply** straight back to
    the HBM-resident tables (aliased outputs). One HBM round-trip per row.
    Duplicate scatter targets combine via an O(B²) equality-matrix matmul
    (small B, the reference) or an O(B·d) sort-based segment sum (large B).
  * :func:`gather_rows`       — multi-row blocks, overlapped async row copies.
  * :func:`scatter_add_rows`  — multi-row blocks; per-block duplicate flags —
    only a block with an internal collision serializes its RMW.
  * ``*_rowwise``             — the original one-row-per-grid-step layouts,
    kept as the interpret-mode reference implementations.

All are validated against ``ref.py`` in interpret mode on the CPU;
``tests/test_tpu_compile.py`` compiles the fused update for a described TPU
v5e (the other kernels move single rows and compile for f32 tables only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.launch import roofline


# --------------------------------------------------------------------------
# shared tile math: the SGNS fwd+bwd every kernel in this file runs on the
# MXU. One definition so a formula fix can't silently diverge the kernels.
# --------------------------------------------------------------------------
def _dot(a, b, dims):
    """f32 MXU matmul at full f32 precision (the TPU's default rounds f32
    operands to bf16), so kernels agree with the f32 oracles on the chip."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _tile_grads(v, c, n, m):
    """v, c: (Bt, d); n: (S, d); m: (Bt, 1) — all f32.
    Returns (dv, dc, dn_tile, loss_tile) in f32."""
    pos = jnp.sum(v * c, axis=-1, keepdims=True)               # (Bt, 1)
    neg = _dot(v, n, ((1,), (1,)))                             # (Bt, S) MXU
    g_pos = (jax.nn.sigmoid(pos) - 1.0) * m                    # (Bt, 1)
    g_neg = jax.nn.sigmoid(neg) * m                            # (Bt, S)
    dv = g_pos * c + _dot(g_neg, n, ((1,), (0,)))
    dc = g_pos * v
    dn_tile = _dot(g_neg, v, ((0,), (0,)))                     # (S, d)
    loss_tile = (jnp.sum(m * jax.nn.softplus(-pos))
                 + jnp.sum(m * jax.nn.softplus(neg)))
    return dv, dc, dn_tile, loss_tile


# --------------------------------------------------------------------------
# dense SGNS grads tile kernel
# --------------------------------------------------------------------------
def _sgns_grads_kernel(v_ref, c_ref, n_ref, mask_ref,
                       dv_ref, dc_ref, dn_ref, loss_ref):
    i = pl.program_id(0)
    f32 = jnp.float32
    dv, dc, dn_tile, loss_tile = _tile_grads(
        v_ref[...].astype(f32), c_ref[...].astype(f32),
        n_ref[...].astype(f32), mask_ref[...].astype(f32))
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)

    # dn and loss accumulate across the B grid (sequential on TPU).
    @pl.when(i == 0)
    def _init():
        dn_ref[...] = jnp.zeros_like(dn_ref)
        loss_ref[...] = jnp.zeros_like(loss_ref)

    dn_ref[...] += dn_tile.astype(dn_ref.dtype)
    loss_ref[...] += loss_tile.astype(loss_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sgns_grads(v, c, n, mask, *, block_b: int = 256, interpret: bool = False):
    """Pallas version of :func:`repro.kernels.ref.sgns_grads_ref`.

    v, c: (B, d); n: (S, d); mask: (B,). B must be a multiple of block_b
    (ops.py pads). d, S should be multiples of 128 / 8 for MXU alignment on
    real hardware; interpret mode accepts anything.
    """
    B, d = v.shape
    S = n.shape[0]
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)
    mask2 = mask.reshape(B, 1)
    out_shape = (
        jax.ShapeDtypeStruct((B, d), v.dtype),      # dv
        jax.ShapeDtypeStruct((B, d), c.dtype),      # dc
        jax.ShapeDtypeStruct((S, d), jnp.float32),  # dn (accumulated)
        jax.ShapeDtypeStruct((1, 1), jnp.float32),  # loss
    )
    loss_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    dv, dc, dn, loss = pl.pallas_call(
        _sgns_grads_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),   # v
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),   # c
            pl.BlockSpec((S, d), lambda i: (0, 0)),         # n (resident)
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),   # mask
        ],
        out_specs=(
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((S, d), lambda i: (0, 0)),
            loss_spec,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(v, c, n, mask2)
    return loss[0, 0], dv, dc, dn.astype(n.dtype)


# --------------------------------------------------------------------------
# FUSED kernel: DMA-gather + grads in one pallas_call (the paper's fused
# CUDA hot loop, TPU-native: per-row HBM->VMEM async copies from scalar-
# prefetched indices feed the same MXU tile math as `_sgns_grads_kernel`,
# so gathered rows never round-trip through HBM between gather and compute).
# --------------------------------------------------------------------------
def _sgns_fused_kernel(iv_ref, ic_ref, in_ref, vert_ref, ctx_ref, mask_ref,
                       dv_ref, dc_ref, dn_ref, loss_ref,
                       v_s, c_s, n_s, sem):
    i = pl.program_id(0)
    Bt = v_s.shape[0]
    S = n_s.shape[0]

    @pl.when(i == 0)
    def _load_negatives():           # shared negatives persist across tiles
        for s in range(S):
            cp = pltpu.make_async_copy(ctx_ref.at[in_ref[s]], n_s.at[s], sem)
            cp.start()
            cp.wait()

    for j in range(Bt):              # gather this tile's rows into VMEM
        cp = pltpu.make_async_copy(vert_ref.at[iv_ref[i * Bt + j]],
                                   v_s.at[j], sem)
        cp.start()
        cp.wait()
        cp = pltpu.make_async_copy(ctx_ref.at[ic_ref[i * Bt + j]],
                                   c_s.at[j], sem)
        cp.start()
        cp.wait()

    f32 = jnp.float32
    dv, dc, dn_tile, loss_tile = _tile_grads(
        v_s[...].astype(f32), c_s[...].astype(f32), n_s[...].astype(f32),
        mask_ref[...].astype(f32))
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dn_ref[...] = jnp.zeros_like(dn_ref)
        loss_ref[...] = jnp.zeros_like(loss_ref)

    dn_ref[...] += dn_tile.astype(dn_ref.dtype)
    loss_ref[...] += loss_tile.astype(loss_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sgns_fused_grads(vert, ctx, idx_v, idx_c, idx_n, mask, *,
                     block_b: int = 256, interpret: bool = False):
    """Fused gather+grads: rows are DMA'd from the (HBM-resident) tables by
    index inside the kernel. Returns (loss, dv, dc, dn) like sgns_grads.

    vert: (Nv, d); ctx: (Nc, d); idx_v/idx_c: (B,); idx_n: (S,); mask: (B,).
    """
    B = idx_v.shape[0]
    d = vert.shape[1]
    S = idx_n.shape[0]
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    mask2 = mask.reshape(B, 1)
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # vert (HBM)
            pl.BlockSpec(memory_space=pl.ANY),              # ctx (HBM)
            pl.BlockSpec((bb, 1), lambda i, *_: (i, 0)),    # mask tile
        ],
        out_specs=(
            pl.BlockSpec((bb, d), lambda i, *_: (i, 0)),    # dv
            pl.BlockSpec((bb, d), lambda i, *_: (i, 0)),    # dc
            pl.BlockSpec((S, d), lambda i, *_: (0, 0)),     # dn (accum)
            pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),     # loss
        ),
        scratch_shapes=[
            pltpu.VMEM((bb, d), vert.dtype),
            pltpu.VMEM((bb, d), ctx.dtype),
            pltpu.VMEM((S, d), ctx.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    dv, dc, dn, loss = pl.pallas_call(
        _sgns_fused_kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, d), vert.dtype),
            jax.ShapeDtypeStruct((B, d), ctx.dtype),
            jax.ShapeDtypeStruct((S, d), f32),
            jax.ShapeDtypeStruct((1, 1), f32),
        ),
        interpret=interpret,
    )(idx_v.astype(jnp.int32), idx_c.astype(jnp.int32),
      idx_n.astype(jnp.int32), vert, ctx, mask2)
    return loss[0, 0], dv, dc, dn.astype(ctx.dtype)


# --------------------------------------------------------------------------
# FULLY-FUSED pipelined update kernel (the tentpole): double-buffered DMA
# gather → MXU tile grads → in-kernel SGD apply to the aliased HBM tables.
#
# Pipeline (grid step = one (bb, d) tile, sequential on TPU):
#   step i:  start tile i+1's row gathers   (rotating sem slot (i+1) % 2)
#            wait  tile i's   row gathers   (sem slot i % 2 — started at i-1)
#            tile math on the MXU           (overlaps tile i+1's copies)
#   last step: duplicate-combine + write-back (see below).
#
# Scatter-accumulate semantics without read-modify-write: all B rows were
# gathered *pre-update*, so the final value of table row r is
#   orig[r] - lr * Σ_{positions p with idx[p]==r} grad[p].
# Every position then writes the SAME final value for its row, so the
# write-back is pure pipelined DMA with no RAW hazards — duplicate writes
# race benignly (identical bytes). ctx duplicates may span idx_c and idx_n;
# the combine runs over the concatenated (idx_c ++ idx_n) index space, which
# is also what lets ops.sgns_step drop its concatenate round-trip through
# HBM. Padded rows (mask 0, index 0) fold in for free: their grads are zero,
# and the combine makes them write row 0's correct final value.
#
# bf16 tables move as 32-bit pair words. Their HBM layout packs rows 2p and
# 2p+1 into one 32-bit word per lane, and the TPU compiler refuses a one-row
# DMA or a dynamic one-row VMEM access of such a table. So the kernel views
# a bf16 table as (N/2, d) uint32 words (a free reshape to (N/2, 2, d) plus
# an in-kernel ref bitcast), gathers the word row of each index's pair,
# unpacks its half to f32 for the tile math, and writes whole pair words
# back. Every writer of a pair must then carry both rows' final values: the
# combine also sums the grads of each position's partner row (row ^ 1), so
# all writers of a pair still emit identical bytes. A pair word is 4 bytes
# per lane, so a bf16 gather moves as many bytes as an f32 one.
#
# Two duplicate-combine strategies (`combine=`):
#   * "eq"     — (B, B) equality-matrix matmuls (MXU-friendly). O(B²) VMEM:
#                the reference path, caps B per launch at ~2k rows (f32).
#   * "segsum" — sort-based segment-sum: the host argsorts the index vectors
#                once (XLA), the kernel runs a forward segment-prefix pass
#                and a backward run-total broadcast over the sorted runs —
#                O(B·d) memory and work, so B ≫ 2k fits in one launch. The
#                sorted order also means the write-back touches each table
#                row's duplicates consecutively.
# --------------------------------------------------------------------------
_NWRITE = 4   # write-back semaphore ring depth (max outstanding row writes)

# Largest B given the equality-matrix combine, by a direct
# sgns_fused_update call and by ops.plan_fused_update. Past it the (B, B)
# matrices may still fit VMEM, but the TPU compiler unrolls them into vector
# code whose compile time grows as B² (2 s at B=256, 21 s at 1024, 314 s at
# 3072 for a described v5e), so segsum takes over.
EQ_COMBINE_MAX_ROWS = 512

_HI16 = 0xFFFF0000


def compiler_params():
    """The scoped VMEM the planners size fused-update launches for."""
    return pltpu.CompilerParams(vmem_limit_bytes=roofline.VMEM_LIMIT_BYTES)


def _word_of(packed: bool):
    """Table row index → index of the word row a DMA moves."""
    return (lambda r: r >> 1) if packed else (lambda r: r)


def _unpack(w, odd):
    """f32 values of one bf16 half of (R, d) uint32 pair words: the high
    half (row 2p+1) where `odd`, else the low half (row 2p)."""
    bits = jnp.where(odd, w & jnp.uint32(_HI16), w << 16)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _pack(even, odd):
    """Pair words from the f32 values of rows 2p and 2p+1, each rounded
    to bf16."""
    def bits(x):
        return jax.lax.bitcast_convert_type(
            x.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32)
    return bits(odd) | (bits(even) >> 16)


def _to_words(t):
    """(N, d) bf16 → (N/2, d) uint32 pair words, row 2p in the low half —
    the TPU's own packing, done by XLA for interpret mode."""
    n, d = t.shape
    return jax.lax.bitcast_convert_type(
        t.reshape(n // 2, 2, d).swapaxes(1, 2), jnp.uint32)


def _from_words(w, dtype):
    m, d = w.shape
    return jax.lax.bitcast_convert_type(w, dtype).swapaxes(1, 2).reshape(
        2 * m, d)


def _word_ref(ref):
    """The (rows, d) units the kernel moves: the table itself (f32, or the
    interpret-mode uint32 words), or the uint32 pair words of a bf16 table
    handed over as (N/2, 2, d)."""
    if len(ref.shape) == 3:
        w = ref.bitcast(jnp.uint32)
        return w.reshape(w.shape[0], w.shape[2])
    return ref


def _sgd_rows(w, odd, own, par, lr):
    """Words to write back for positions whose pre-update words are w.

    Unpacked (par None): w - lr * own, the update cast to the table dtype
    first like the ref's scatter-add of a cast update. Packed: both bf16
    halves of each pair word — the position's own row (half `odd`, grads
    `own`) and its partner row (grads `par`; zero leaves it unchanged)."""
    if par is None:
        return w + (-lr * own).astype(w.dtype)

    def upd(x, g):
        return x + (-lr * g).astype(jnp.bfloat16).astype(jnp.float32)
    mine = upd(_unpack(w, odd), own)
    other = upd(_unpack(w, jnp.logical_not(odd)), par)
    return _pack(jnp.where(odd, other, mine), jnp.where(odd, mine, other))


def _fused_main_body(i, iv_ref, ic_ref, in_ref, vw, cw, mask_ref, odd_refs,
                     loss_ref, v_s, c_s, n_s, dv_s, dc_s, dn_s, gsem, nsem):
    """Shared per-grid-step body of both fused-update kernels: the double-
    buffered row-gather pipeline + MXU tile grads + loss/dn accumulation.
    odd_refs is None (unpacked) or the (bb,1)/(bb,1)/(S,1) half selectors
    of the v/c tiles and the negatives (packed)."""
    T = pl.num_programs(0)
    bb = mask_ref.shape[0]
    S = n_s.shape[0]
    f32 = jnp.float32
    word = _word_of(odd_refs is not None)

    def tile_copies(t, op):
        """start/wait the 2*bb row DMAs of tile t on sem slot t % 2."""
        def body(j, _):
            r = t * bb + j
            getattr(pltpu.make_async_copy(
                vw.at[word(iv_ref[r])], v_s.at[r], gsem.at[t % 2]), op)()
            getattr(pltpu.make_async_copy(
                cw.at[word(ic_ref[r])], c_s.at[r], gsem.at[t % 2]), op)()
            return 0
        jax.lax.fori_loop(0, bb, body, 0)

    def neg_copy(s):
        return pltpu.make_async_copy(cw.at[word(in_ref[s])], n_s.at[s], nsem)

    @pl.when(i == 0)
    def _prologue():
        # shared negatives: start first so they overlap tile 0's gathers
        def nstart(s, _):
            neg_copy(s).start()
            return 0
        jax.lax.fori_loop(0, S, nstart, 0)
        tile_copies(0, "start")

        def nwait(s, _):
            neg_copy(s).wait()
            return 0
        jax.lax.fori_loop(0, S, nwait, 0)

    @pl.when(i + 1 < T)
    def _prefetch_next():          # double buffering: next tile's DMAs fly
        tile_copies(i + 1, "start")   # while this tile computes

    tile_copies(i, "wait")

    def rows(x, k):
        return (x.astype(f32) if odd_refs is None
                else _unpack(x, odd_refs[k][...] == 1))

    dv, dc, dn_tile, loss_tile = _tile_grads(
        rows(v_s[pl.ds(i * bb, bb), :], 0),
        rows(c_s[pl.ds(i * bb, bb), :], 1),
        rows(n_s[...], 2), mask_ref[...].astype(f32))
    dv_s[pl.ds(i * bb, bb), :] = dv
    dc_s[pl.ds(i * bb, bb), :] = dc

    @pl.when(i == 0)
    def _init():
        dn_s[...] = jnp.zeros_like(dn_s)
        loss_ref[...] = jnp.zeros_like(loss_ref)

    dn_s[...] += dn_tile
    loss_ref[...] += loss_tile


def _write_rows(src, idx_sref, tbl_out, count, wsem, word):
    """Pipelined row write-back: semaphore ring, _NWRITE in flight."""
    def copy(p):
        return pltpu.make_async_copy(src.at[p], tbl_out.at[word(idx_sref[p])],
                                     wsem.at[p % _NWRITE])

    def body(p, _):
        @pl.when(p >= _NWRITE)
        def _retire():
            copy(p - _NWRITE).wait()
        copy(p).start()
        return 0
    jax.lax.fori_loop(0, count, body, 0)
    for p in range(max(0, count - _NWRITE), count):   # drain
        copy(p).wait()


def _write_rows_unique(src, upos_ref, idx_sref, tbl_out, count, wsem, word):
    """Deduplicated pipelined write-back: one row DMA per *unique* scatter
    target instead of one per position.

    upos_ref[j] is the sorted position holding the j-th run's final bytes
    (host plan: _unique_write_plan); count — the number of runs — is a
    traced SMEM scalar, so both loops are dynamic-bound fori_loops (the
    static-drain idiom of _write_rows needs a python range). On skewed
    batches hub rows collapse many positions into one DMA; the written
    bytes are identical because every position of a run emits the same
    final row, so this also retires the old benign write race.
    """
    def copy(p):
        u = upos_ref[p]
        return pltpu.make_async_copy(src.at[u], tbl_out.at[word(idx_sref[u])],
                                     wsem.at[p % _NWRITE])

    def body(p, _):
        @pl.when(p >= _NWRITE)
        def _retire():
            copy(p - _NWRITE).wait()
        copy(p).start()
        return 0
    jax.lax.fori_loop(0, count, body, 0)

    def drain(p, _):
        copy(p).wait()
        return 0
    jax.lax.fori_loop(jnp.maximum(count - _NWRITE, 0), count, drain, 0)


def _sgns_update_kernel(iv_ref, ic_ref, in_ref,               # scalar prefetch
                        vert_hbm, ctx_hbm, ivv_ref, icv_ref, inv_ref,
                        mask_ref, lr_ref, *rest, packed: bool):
    odd_refs = rest[:3] if packed else None
    (vert_out, ctx_out, loss_ref, v_s, c_s, n_s, dv_s, dc_s, dn_s,
     gsem, nsem, wsem) = rest[3:] if packed else rest
    i = pl.program_id(0)
    T = pl.num_programs(0)
    B = v_s.shape[0]
    f32 = jnp.float32
    word = _word_of(packed)
    _fused_main_body(i, iv_ref, ic_ref, in_ref, _word_ref(vert_hbm),
                     _word_ref(ctx_hbm), mask_ref, odd_refs, loss_ref,
                     v_s, c_s, n_s, dv_s, dc_s, dn_s, gsem, nsem)

    @pl.when(i == T - 1)
    def _apply():
        lr = lr_ref[0, 0]
        iv = ivv_ref[...]                                    # (B, 1) i32
        ic = icv_ref[...]
        inn = inv_ref[...]                                   # (S, 1) i32

        def esum(keys, idx, grads):
            """Σ of grads over positions whose row equals each key."""
            eq = (keys == idx.reshape(1, -1)).astype(f32)
            return _dot(eq, grads, ((1,), (0,)))

        # duplicate-combine: position-level grad sums per table row; ctx
        # rows sum over both the positive-context and the negative grads
        def vsum(keys):
            return esum(keys, iv, dv_s[...])

        def csum(keys):
            return esum(keys, ic, dc_s[...]) + esum(keys, inn, dn_s[...])

        def apply(buf, keys, total):
            if packed:
                buf[...] = _sgd_rows(buf[...], (keys & 1) == 1, total(keys),
                                     total(keys ^ 1), lr)
            else:
                buf[...] = _sgd_rows(buf[...], None, total(keys), None, lr)
        apply(v_s, iv, vsum)
        apply(c_s, ic, csum)
        apply(n_s, inn, csum)

        _write_rows(v_s, iv_ref, _word_ref(vert_out), B, wsem, word)
        _write_rows(c_s, ic_ref, _word_ref(ctx_out), B, wsem, word)
        _write_rows(n_s, in_ref, _word_ref(ctx_out), n_s.shape[0], wsem,
                    word)


def _sgns_update_kernel_segsum(iv_ref, ic_ref, in_ref,        # scalar prefetch
                               pv_ref, ivs_ref, vflag_ref,
                               pc_ref, icns_ref, cflag_ref,
                               uv_ref, nv_ref, uc_ref, nc_ref,
                               vert_hbm, ctx_hbm, mask_ref, lr_ref,
                               *rest, packed: bool):
    """Fused update with the sort-based segment-sum duplicate-combine.

    The host argsorted the index vectors: pv/pc map sorted position → batch
    position (ctx positions p ≥ B address idx_n's grads dn_s[p - B]); ivs/
    icns are the sorted indices (the write-back targets); vflag/cflag pack
    run boundaries (bit 0 = first of its run, bit 1 = last). The combine is
    two O(B) passes per side instead of an O(B²) equality matmul:

      forward:  acc resets at each run start; ps[j] = prefix sum of the
                run's grads up to sorted position j.
      backward: the run total (ps at the run's last position) propagates
                back over the run; every position emits its row's final
                value orig - lr·total into fv/fc.

    All positions of a run emit identical bytes; the write-back issues ONE
    DMA per run (uv/uc list each run's last sorted position, nv/nc count
    the runs) instead of one per position — on skewed batches the hub rows
    that dominate collapse to single writes.

    Packed (bf16) tables run over pair words: a run is a row pair, the
    forward pass keeps one accumulator per half (ps/ps2), and every position
    emits the pair word with both rows' finals.
    """
    odd_refs = rest[:3] if packed else None
    rest = rest[3:] if packed else rest
    vert_out, ctx_out, loss_ref, v_s, c_s, n_s, dv_s, dc_s, dn_s = rest[:9]
    fv_s, fc_s, ps_s = rest[9:12]
    ps2_s = rest[12] if packed else None
    gsem, nsem, wsem = rest[-3:]
    i = pl.program_id(0)
    T = pl.num_programs(0)
    B, d = v_s.shape
    S = n_s.shape[0]
    L = B + S
    f32 = jnp.float32
    word = _word_of(packed)
    _fused_main_body(i, iv_ref, ic_ref, in_ref, _word_ref(vert_hbm),
                     _word_ref(ctx_hbm), mask_ref, odd_refs, loss_ref,
                     v_s, c_s, n_s, dv_s, dc_s, dn_s, gsem, nsem)

    @pl.when(i == T - 1)
    def _apply():
        lr = lr_ref[0, 0]

        def combine(count, perm_ref, sidx_ref, flag_ref, grad_row, orig_row,
                    out_buf):
            zero = jnp.zeros((1, d), f32)

            def fwd(j, acc):
                g = grad_row(perm_ref[j])
                start = (flag_ref[j] & 1) == 1
                if not packed:
                    acc = jnp.where(start, g, acc + g)
                    ps_s[pl.ds(j, 1), :] = acc
                    return acc
                odd = (sidx_ref[j] & 1) == 1
                acc_e = (jnp.where(start, zero, acc[0])
                         + jnp.where(odd, zero, g))
                acc_o = (jnp.where(start, zero, acc[1])
                         + jnp.where(odd, g, zero))
                ps_s[pl.ds(j, 1), :] = acc_e
                ps2_s[pl.ds(j, 1), :] = acc_o
                return acc_e, acc_o
            jax.lax.fori_loop(0, count, fwd, (zero, zero) if packed else zero)

            def bwd(t, tot):
                j = count - 1 - t
                last = (flag_ref[j] & 2) == 2
                w = orig_row(perm_ref[j])
                if not packed:
                    tot = jnp.where(last, ps_s[pl.ds(j, 1), :], tot)
                    # same op structure as the eq path's in-place SGD
                    out_buf[pl.ds(j, 1), :] = _sgd_rows(w, None, tot, None,
                                                        lr)
                    return tot
                te = jnp.where(last, ps_s[pl.ds(j, 1), :], tot[0])
                to = jnp.where(last, ps2_s[pl.ds(j, 1), :], tot[1])
                out_buf[pl.ds(j, 1), :] = _sgd_rows(w, False, te, to, lr)
                return te, to
            jax.lax.fori_loop(0, count, bwd, (zero, zero) if packed else zero)

        combine(B, pv_ref, ivs_ref, vflag_ref,
                lambda p: dv_s[pl.ds(p, 1), :],
                lambda p: v_s[pl.ds(p, 1), :], fv_s)

        # ctx side runs over the concatenated (idx_c ++ idx_n) position
        # space: p < B is a positive-context grad, p >= B a shared-negative
        # grad — this is exactly the cross-coupling the eq path's ctx sums
        # over both index vectors provide
        def c_grad(p):
            pc = jnp.minimum(p, B - 1)
            pn = jnp.maximum(p - B, 0)
            return jnp.where(p < B, dc_s[pl.ds(pc, 1), :],
                             dn_s[pl.ds(pn, 1), :])

        def c_orig(p):
            pc = jnp.minimum(p, B - 1)
            pn = jnp.maximum(p - B, 0)
            return jnp.where(p < B, c_s[pl.ds(pc, 1), :],
                             n_s[pl.ds(pn, 1), :])

        combine(L, pc_ref, icns_ref, cflag_ref, c_grad, c_orig, fc_s)

        _write_rows_unique(fv_s, uv_ref, ivs_ref, _word_ref(vert_out),
                           nv_ref[0], wsem, word)
        _write_rows_unique(fc_s, uc_ref, icns_ref, _word_ref(ctx_out),
                           nc_ref[0], wsem, word)


def _run_flags(sorted_idx):
    """Bit 0: first position of its equal-index run; bit 1: last."""
    brk = sorted_idx[1:] != sorted_idx[:-1]
    one = jnp.ones((1,), bool)
    start = jnp.concatenate([one, brk])
    end = jnp.concatenate([brk, one])
    return start.astype(jnp.int32) | (end.astype(jnp.int32) << 1)


def _unique_write_plan(sorted_idx):
    """Write-back dedup plan for a sorted scatter-index vector.

    Returns (upos, n): upos[j] is the sorted position whose buffer row
    holds run j's final bytes (the run's last position — every position of
    a run emits identical bytes, see the segsum kernel), n (shape (1,)) is
    the run count. upos entries past n are zero padding the kernel's
    dynamic-bound write loop never reads.
    """
    L = sorted_idx.shape[0]
    ar = jnp.arange(L, dtype=jnp.int32)
    start = jnp.concatenate([jnp.ones((1,), bool),
                             sorted_idx[1:] != sorted_idx[:-1]])
    rank = jnp.cumsum(start.astype(jnp.int32)) - 1
    upos = jnp.zeros((L,), jnp.int32).at[rank].max(ar)
    return upos, (rank[-1] + 1).reshape(1)


def _kernel_tables(vert, ctx, packed: bool, interpret: bool):
    """The table operands as the kernel takes them (see _word_ref)."""
    if not packed:
        return vert, ctx
    if interpret:
        return _to_words(vert), _to_words(ctx)
    return (vert.reshape(vert.shape[0] // 2, 2, -1),
            ctx.reshape(ctx.shape[0] // 2, 2, -1))


def _user_table(t, dtype, packed: bool, interpret: bool):
    if not packed:
        return t
    if interpret:
        return _from_words(t, dtype)
    return t.reshape(2 * t.shape[0], -1)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "combine", "interpret"))
def sgns_fused_update(vert, ctx, idx_v, idx_c, idx_n, mask, lr, *,
                      block_b: int = 256, combine: str | None = None,
                      interpret: bool = False):
    """One fully-fused SGNS SGD minibatch: gather + grads + apply in a single
    pallas_call with the tables aliased input→output.

    vert: (Nv, d); ctx: (Nc, d) (same dtype, f32 or bf16); idx_v/idx_c:
    (B,); idx_n: (S,); mask: (B,); lr: scalar. B must be a multiple of
    min(block_b, B) — ops.sgns_step pads. Returns (vert', ctx', loss).
    bf16 tables with an odd row count pay a one-row pad copy per call (the
    pair words need even rows); the trainers' tables have even row counts.

    ``combine`` selects the duplicate-combine strategy: ``"eq"`` (equality-
    matrix matmuls, O(B²) VMEM — the small-B reference), ``"segsum"``
    (sort-based segment sum, O(B·d) — scales to B ≫ 2k), or ``None`` to pick
    by B. ops.plan_fused_update makes the production choice from the full
    VMEM model.
    """
    B = idx_v.shape[0]
    d = vert.shape[1]
    S = idx_n.shape[0]
    assert vert.dtype == ctx.dtype, (vert.dtype, ctx.dtype)
    assert vert.dtype in (jnp.float32, jnp.bfloat16), vert.dtype
    packed = vert.dtype == jnp.bfloat16     # moved as uint32 pair words
    if packed and (vert.shape[0] % 2 or ctx.shape[0] % 2):
        nv, nc = vert.shape[0], ctx.shape[0]
        v2, c2, loss = sgns_fused_update(
            jnp.pad(vert, ((0, nv % 2), (0, 0))),
            jnp.pad(ctx, ((0, nc % 2), (0, 0))), idx_v, idx_c, idx_n, mask,
            lr, block_b=block_b, combine=combine, interpret=interpret)
        return v2[:nv], c2[:nc], loss
    if combine is None:
        combine = "eq" if B <= EQ_COMBINE_MAX_ROWS else "segsum"
    assert combine in ("eq", "segsum"), combine
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    f32 = jnp.float32
    iv32 = idx_v.astype(jnp.int32)
    ic32 = idx_c.astype(jnp.int32)
    in32 = idx_n.astype(jnp.int32)
    vk, ck = _kernel_tables(vert, ctx, packed, interpret)
    wdt = jnp.uint32 if packed else vert.dtype     # the units the DMAs move
    out_shape = (
        jax.ShapeDtypeStruct(vk.shape, vk.dtype),
        jax.ShapeDtypeStruct(ck.shape, ck.dtype),
        jax.ShapeDtypeStruct((1, 1), f32),
    )
    table_scratch = [
        pltpu.VMEM((B, d), wdt),                         # v_s
        pltpu.VMEM((B, d), wdt),                         # c_s
        pltpu.VMEM((S, d), wdt),                         # n_s
        pltpu.VMEM((B, d), f32),                         # dv_s
        pltpu.VMEM((B, d), f32),                         # dc_s
        pltpu.VMEM((S, d), f32),                         # dn_s
    ]
    sems = [
        pltpu.SemaphoreType.DMA((2,)),                   # gather (rotating)
        pltpu.SemaphoreType.DMA,                         # negatives
        pltpu.SemaphoreType.DMA((_NWRITE,)),             # write-back ring
    ]
    # packed: which half of its pair word each v/c/n row is
    odd_specs, odd_ops = [], []
    if packed:
        odd_specs = [pl.BlockSpec((bb, 1), lambda i, *_: (i, 0)),
                     pl.BlockSpec((bb, 1), lambda i, *_: (i, 0)),
                     pl.BlockSpec((S, 1), lambda i, *_: (0, 0))]
        odd_ops = [(iv32 & 1).reshape(B, 1), (ic32 & 1).reshape(B, 1),
                   (in32 & 1).reshape(S, 1)]
    lr2 = jnp.asarray(lr, f32).reshape(1, 1)
    if combine == "eq":
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B // bb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),           # vert (HBM)
                pl.BlockSpec(memory_space=pl.ANY),           # ctx (HBM)
                pl.BlockSpec((B, 1), lambda i, *_: (0, 0)),  # idx_v as vector
                pl.BlockSpec((B, 1), lambda i, *_: (0, 0)),  # idx_c as vector
                pl.BlockSpec((S, 1), lambda i, *_: (0, 0)),  # idx_n as vector
                pl.BlockSpec((bb, 1), lambda i, *_: (i, 0)),  # mask tile
                pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),  # lr
            ] + odd_specs,
            out_specs=(
                pl.BlockSpec(memory_space=pl.ANY),           # vert' (aliased)
                pl.BlockSpec(memory_space=pl.ANY),           # ctx'  (aliased)
                pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),  # loss (accum)
            ),
            scratch_shapes=table_scratch + sems,
        )
        vert2, ctx2, loss = pl.pallas_call(
            functools.partial(_sgns_update_kernel, packed=packed),
            grid_spec=grid_spec,
            out_shape=out_shape,
            # operands 0..2 are the scalar-prefetch index vectors.
            input_output_aliases={3: 0, 4: 1},
            compiler_params=compiler_params(),
            interpret=interpret,
        )(iv32, ic32, in32, vk, ck,
          iv32.reshape(B, 1), ic32.reshape(B, 1), in32.reshape(S, 1),
          mask.reshape(B, 1), lr2, *odd_ops)
        return (_user_table(vert2, vert.dtype, packed, interpret),
                _user_table(ctx2, ctx.dtype, packed, interpret), loss[0, 0])

    # segsum: sort each scatter index space once on the XLA side; the kernel
    # combines duplicates over the sorted runs in O(B·d). Packed tables
    # combine over row pairs: runs and write targets are pair indices (the
    # row sort keeps each pair's rows adjacent)
    L = B + S
    perm_v = jnp.argsort(iv32).astype(jnp.int32)          # stable
    ivs = jnp.take(iv32, perm_v)
    icn = jnp.concatenate([ic32, in32])
    perm_c = jnp.argsort(icn).astype(jnp.int32)
    icns = jnp.take(icn, perm_c)
    shift = 1 if packed else 0
    upos_v, nuniq_v = _unique_write_plan(ivs >> shift)
    upos_c, nuniq_c = _unique_write_plan(icns >> shift)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=13,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),               # vert (HBM)
            pl.BlockSpec(memory_space=pl.ANY),               # ctx (HBM)
            pl.BlockSpec((bb, 1), lambda i, *_: (i, 0)),     # mask tile
            pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),      # lr
        ] + odd_specs,
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),               # vert' (aliased)
            pl.BlockSpec(memory_space=pl.ANY),               # ctx'  (aliased)
            pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),      # loss (accum)
        ),
        scratch_shapes=table_scratch + [
            pltpu.VMEM((B, d), wdt),                         # fv_s (finals)
            pltpu.VMEM((L, d), wdt),                         # fc_s (finals)
            pltpu.VMEM((L, d), f32),                         # ps_s (prefixes)
        ] + ([pltpu.VMEM((L, d), f32)] if packed else [])    # ps2_s (odd half)
          + sems,
    )
    vert2, ctx2, loss = pl.pallas_call(
        functools.partial(_sgns_update_kernel_segsum, packed=packed),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operands 0..12 are the scalar-prefetch index/permutation/dedup
        # vectors.
        input_output_aliases={13: 0, 14: 1},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(iv32, ic32, in32,
      perm_v, ivs, _run_flags(ivs >> shift), perm_c, icns,
      _run_flags(icns >> shift), upos_v, nuniq_v, upos_c, nuniq_c,
      vk, ck, mask.reshape(B, 1), lr2, *odd_ops)
    return (_user_table(vert2, vert.dtype, packed, interpret),
            _user_table(ctx2, ctx.dtype, packed, interpret), loss[0, 0])


# --------------------------------------------------------------------------
# row gather: multi-row blocks, overlapped async copies (all of a block's
# row DMAs are in flight before the first wait)
# --------------------------------------------------------------------------
def _gather_block_kernel(idx_ref, table_ref, out_ref, sem, *, valid: int):
    i = pl.program_id(0)
    rb = out_ref.shape[0]
    # padded tail rows (global index >= valid) are discarded by the caller's
    # out[:B] slice — skip their DMAs entirely

    def start(j, _):
        @pl.when(i * rb + j < valid)
        def _():
            pltpu.make_async_copy(table_ref.at[idx_ref[i * rb + j]],
                                  out_ref.at[j], sem.at[j]).start()
        return 0
    jax.lax.fori_loop(0, rb, start, 0)

    def wait(j, _):
        @pl.when(i * rb + j < valid)
        def _():
            pltpu.make_async_copy(table_ref.at[idx_ref[i * rb + j]],
                                  out_ref.at[j], sem.at[j]).wait()
        return 0
    jax.lax.fori_loop(0, rb, wait, 0)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def gather_rows(table, idx, *, rows_per_block: int = 8,
                interpret: bool = False):
    """(N, d) table, (B,) int32 → (B, d). One grid step per `rows_per_block`
    rows; each block's HBM→VMEM row copies are all started before any wait,
    so the DMAs overlap each other (and the previous block's writeout)."""
    B = idx.shape[0]
    N, d = table.shape
    rb = min(rows_per_block, B)
    Bp = -(-B // rb) * rb
    idx_p = jnp.pad(idx.astype(jnp.int32), (0, Bp - B))  # pad rows: no DMA
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bp // rb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],    # table (HBM)
        out_specs=pl.BlockSpec((rb, d), lambda i, idx: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((rb,))],
    )
    out = pl.pallas_call(
        functools.partial(_gather_block_kernel, valid=B),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, d), table.dtype),
        interpret=interpret,
    )(idx_p, table)
    return out[:B]


def _gather_rowwise_kernel(idx_ref, table_ref, out_ref):
    del idx_ref  # consumed by the index_map
    out_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_rowwise(table, idx, *, interpret: bool = False):
    """Original one-row-per-grid-step gather, kept as the interpret-mode
    reference for :func:`gather_rows`."""
    B = idx.shape[0]
    N, d = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, d), lambda i, idx: (idx[i], 0))],
        out_specs=pl.BlockSpec((1, d), lambda i, idx: (i, 0)),
    )
    return pl.pallas_call(
        _gather_rowwise_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, d), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), table)


# --------------------------------------------------------------------------
# row scatter-add: multi-row blocks with PER-BLOCK duplicate flags. A block
# whose own indices are duplicate-free runs the overlapped path (reads all
# overlap, the adds vectorize, the writes all overlap); only blocks with an
# internal collision fall back to serialized per-row read-modify-write (the
# only order that accumulates correctly). Duplicates *across* blocks are
# safe on the overlapped path: the grid is sequential and every block's
# writes are waited before its step ends, so a later block's read of the
# same row sees the earlier block's write.
# --------------------------------------------------------------------------
def _scatter_add_block_kernel(idx_ref, dup_ref, table_ref, upd_ref, out_ref,
                              row_s, sem, *, valid: int):
    del table_ref  # aliased: current rows are read through out_ref
    i = pl.program_id(0)
    rb = upd_ref.shape[0]
    # padded tail rows (global index >= valid) do no DMA at all, so padding
    # neither races real row updates nor forces the serialized path

    @pl.when(dup_ref[i] == 0)
    def _overlapped():
        def rstart(j, _):
            @pl.when(i * rb + j < valid)
            def _():
                pltpu.make_async_copy(out_ref.at[idx_ref[i * rb + j]],
                                      row_s.at[j], sem.at[j]).start()
            return 0
        jax.lax.fori_loop(0, rb, rstart, 0)
        def rwait(j, _):
            @pl.when(i * rb + j < valid)
            def _():
                pltpu.make_async_copy(out_ref.at[idx_ref[i * rb + j]],
                                      row_s.at[j], sem.at[j]).wait()
            return 0
        jax.lax.fori_loop(0, rb, rwait, 0)
        row_s[...] = row_s[...] + upd_ref[...].astype(row_s.dtype)
        def wstart(j, _):
            @pl.when(i * rb + j < valid)
            def _():
                pltpu.make_async_copy(row_s.at[j],
                                      out_ref.at[idx_ref[i * rb + j]],
                                      sem.at[j]).start()
            return 0
        jax.lax.fori_loop(0, rb, wstart, 0)
        def wwait(j, _):
            @pl.when(i * rb + j < valid)
            def _():
                pltpu.make_async_copy(row_s.at[j],
                                      out_ref.at[idx_ref[i * rb + j]],
                                      sem.at[j]).wait()
            return 0
        jax.lax.fori_loop(0, rb, wwait, 0)

    @pl.when(dup_ref[i] != 0)
    def _serialized():
        def body(j, _):
            @pl.when(i * rb + j < valid)
            def _():
                r = idx_ref[i * rb + j]
                cp = pltpu.make_async_copy(out_ref.at[r], row_s.at[0],
                                           sem.at[0])
                cp.start()
                cp.wait()
                row_s[0, :] = row_s[0, :] + upd_ref[j, :].astype(row_s.dtype)
                cp = pltpu.make_async_copy(row_s.at[0], out_ref.at[r],
                                           sem.at[0])
                cp.start()
                cp.wait()
            return 0
        jax.lax.fori_loop(0, rb, body, 0)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def scatter_add_rows(table, idx, upd, *, rows_per_block: int = 8,
                     interpret: bool = False):
    """table[idx[i]] += upd[i] (duplicates accumulate), `rows_per_block` rows
    per grid step. A host-side per-block duplicate check (sorted-adjacent
    compare within each block) selects the overlapped fast path or the
    serialized RMW path block by block, so one colliding block no longer
    serializes the whole scatter."""
    B = idx.shape[0]
    N, d = table.shape
    rb = min(rows_per_block, B)
    Bp = -(-B // rb) * rb
    idx32 = idx.astype(jnp.int32)
    idx_p = jnp.pad(idx32, (0, Bp - B))   # pad rows are skipped in-kernel
    upd_p = _pad_rows(upd, Bp)
    # per-block duplicate flags over the REAL indices (padded tail positions
    # get unique negative sentinels so they can't fake a collision with a
    # real index; the kernel skips them regardless)
    sentinels = -1 - jnp.arange(Bp - B, dtype=jnp.int32)
    srt = jnp.sort(jnp.concatenate([idx32, sentinels]).reshape(Bp // rb, rb),
                   axis=1)
    dup = jnp.any(srt[:, 1:] == srt[:, :-1], axis=1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bp // rb,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # table: alias
            pl.BlockSpec((rb, d), lambda i, *_: (i, 0)),     # upd block
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((rb, d), table.dtype),
            pltpu.SemaphoreType.DMA((rb,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_scatter_add_block_kernel, valid=B),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, d), table.dtype),
        # operands 0/1 are the scalar-prefetch idx/dup; operand 2 is `table`.
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx_p, dup, table, upd_p)


def _pad_rows(x, n_rows):
    pad = n_rows - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad), (0, 0)))


def _scatter_add_rowwise_kernel(idx_ref, table_ref, upd_ref, out_ref):
    del idx_ref, table_ref  # table is aliased to out; its rows arrive in out_ref
    out_ref[...] += upd_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_add_rows_rowwise(table, idx, upd, *, interpret: bool = False):
    """Original one-row-per-grid-step scatter-add (aliased input→output;
    sequential grid ⇒ duplicates accumulate), kept as the interpret-mode
    reference for :func:`scatter_add_rows`."""
    B = idx.shape[0]
    N, d = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),           # table: alias only
            pl.BlockSpec((1, d), lambda i, idx: (i, 0)),    # upd row
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, idx: (idx[i], 0)),
    )
    return pl.pallas_call(
        _scatter_add_rowwise_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, d), table.dtype),
        # operand 0 is the scalar-prefetch idx; operand 1 is `table`.
        input_output_aliases={1: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), table, upd)

"""Pallas blocked MIPS top-k kernels for sharded embedding retrieval.

The serving hot loop is the training hot loop run backwards: instead of
gathering a minibatch of rows by index, a query batch scans every table row
once — a (Q, d) x (d, N) matmul that is O(1) arithmetic intensity per table
byte, so (like training) the kernel's job is to touch each HBM row exactly
once and keep the MXU fed while the next tile's DMA is in flight.

Kernels (all validated against :func:`repro.kernels.ref.topk_mips_ref`):

  * :func:`topk_mips`          — the production kernel: the table stays in
    HBM; (bn, d) row tiles are double-buffered into VMEM by explicit DMA
    (tile t+1's copy flies while tile t is scored on the MXU), and the
    call's query blocks, resident in VMEM, each fold every tile into a
    running (bq, k) top-k held in the revisited output block. One HBM
    read per table row per call. :func:`topk_mips_counted` is the same
    scan returning its counts too (the store's Pallas path).
  * :func:`topk_mips_quant`    — the int8 first pass of the two-tier
    quantized scan (``embed_serve.quant``): the same double-buffered
    tile-DMA skeleton streaming (bn, d) *int8* tiles (4x less DMA traffic
    than f32), per-row scales riding a pipelined (1, bn) block, keeping an
    over-fetched running top-``m`` candidate set per query row. Its
    output is approximate by the quantization error — survivors are
    re-scored exactly by ``quant.rescore_exact``.
    :func:`topk_mips_quant_counted` returns the merge gate's counts too.
  * :func:`topk_mips_quant_xla` — plain-jnp quantized first pass; the CPU
    serving path for the quant tier and the kernel's cross-check (int8
    scores are exact integers in f32, so the two agree bitwise).
  * :func:`topk_mips_rowwise`  — one table row per grid step through a
    BlockSpec-pipelined (1, d) block; the interpret-mode reference, in the
    spirit of ``kernels.sgns.gather_rows_rowwise``.
  * :func:`topk_mips_xla`      — plain-jnp scores + the same selection
    network; the CPU/XLA serving path and the shard-level oracle.
  * :func:`merge_topk`         — the small jitted cross-shard reduce: P
    per-shard (Q, k) results (global ids) → the global (Q, k).

Launch geometry: ``block_n=None`` (the default everywhere) sizes the scan
tile with :func:`choose_block_n` — the serving mirror of
``kernels.ops.choose_block_b``, fitting the (2*bn, d) double-buffer
scratch plus the merge working set against ``roofline.VMEM_BUDGET_BYTES``.

Merge gate: selecting a query row's top-k from k + bn candidates costs
far more than scoring the tile, and after the first few hundred tiles
almost no tile holds a score above a row's running k-th best. So a
scored tile is merged only into rows it can change: a row opens when its
best score in the tile is strictly above its running k-th value. When no
row opens, the step ends after the gate's two reductions; when the open
rows lie in one 8-row group, that group alone is merged; when they span
two or more groups, the whole query block is merged once. An 8-row merge
is bound by the latency of its k selection passes, not by their width,
so on a v5e it costs about half of a 128-row merge (k = 10 and 100): two
group merges cost about as much as one block merge. The strict ``>`` is
exact: tiles arrive in ascending row order, so a later score equal to
the k-th value has a larger index and loses the tie. The gate adapts
through the data: a row order with rising scores merges the block on
every tile, at the cost of the ungated kernel plus the gate (about
0.35 µs a step on a v5e, 5% of the ungated step at k = 10).

Zero blocks: serving pads each call to a fixed batch with zero rows, so
a call's last query blocks often hold no query at all. A zero row's
scores are all +-0; once the tiles holding its first k rows are merged,
its k-th value is 0 and no later tile can open it. So a query block of
zero rows alone is scored on those tiles only, and costs nothing after
them; the launcher flags each block's zeros from the queries it is
given.

Exactness: scores are f32 (tables cast up before the dot, like the SGNS
kernels), selection is exact MIPS with ties broken toward the smaller row
index — the same total order as the numpy oracle's stable argsort.
Sentinels: invalid positions (padded table rows, masked candidates) carry
(-inf, int32 max), so they lose every comparison and a shard with fewer
than k valid rows degrades gracefully in the cross-shard merge.

Interpret mode on CPU; TPU is the compilation target
(``tests/test_tpu_compile.py`` compiles the scans for a described v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.launch import roofline

NEG_INF = float("-inf")
IDX_SENTINEL = jnp.iinfo(jnp.int32).max
DEFAULT_BLOCK_Q = 128   # query rows scored and merged as one block
                        # (topk_mips default)
DEFAULT_PLAN_K = 128    # running-candidate allowance choose_block_n assumes
                        # when the query-time k is not yet known (store load)
# every score is an f32 product at full precision (the TPU's default rounds
# f32 matmul operands to bf16), so rankings match the numpy oracle's
_F32 = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# VMEM-aware tile planner (the serving mirror of kernels.ops.choose_block_b:
# all decisions from static shape/dtype info, nothing at run time)
# --------------------------------------------------------------------------
def _lanes(n: int) -> int:
    """Columns a VMEM buffer of n columns occupies (128-lane tiles)."""
    return -(-n // 128) * 128


def topk_scan_vmem_bytes(bn: int, d: int, dtype, *, k: int = DEFAULT_PLAN_K,
                         block_q: int = DEFAULT_BLOCK_Q,
                         rows: int | None = None,
                         hot_rows: int = 0) -> int:
    """Modeled VMEM working set of one topk_mips/topk_mips_quant launch.

    Mirrors the scratch_shapes, the pipelined blocks and the compute
    temporaries, with every column count padded to whole 128-lane tiles:
    the (2*bn, d) double-buffer tile slots (table dtype), the f32 cast of
    the scored tile, the double-buffered (rows, d) resident queries, the
    int8 scan's double-buffered (1, bn) scale block (8 sublanes), the
    (block_q, bn) score/iota matrices, the (block_q, k + bn) candidates
    the selection loop carries (vals/idx plus the per-pass masked copies
    — 4 f32-width copies), and the double-buffered (rows, k) value/id
    output blocks. rows (default block_q) is the query rows the launch
    keeps resident: whole query blocks, scored one at a time.

    hot_rows models a co-resident hot-tier scan tile: the tiered store
    runs an exact-f32 scan over min(hot_rows, bn) rows alongside the quant
    scan of the cold remainder, so that tile's bytes come out of the same
    budget. 0 (default) is the untiered model.
    """
    rows = block_q if rows is None else rows
    item = jnp.dtype(dtype).itemsize
    total = 2 * bn * d * item                     # double-buffered tile slots
    total += bn * d * 4                           # f32 cast of the scored tile
    total += 2 * rows * _lanes(d) * 4             # resident queries
    if item == 1:
        total += 2 * 8 * _lanes(bn) * 4           # int8 row-scale block
    total += block_q * _lanes(bn) * 4 * 2         # (bq, bn) scores + iota
    total += block_q * _lanes(k + bn) * 4 * 4     # selection candidates
    total += 2 * 2 * rows * _lanes(k) * 4         # running (rows, k) outputs
    total += min(hot_rows, bn) * d * 4            # exact hot-tier scan tile
    return total


def choose_block_n(d: int, dtype, *, k: int = DEFAULT_PLAN_K,
                   block_q: int = DEFAULT_BLOCK_Q,
                   vmem_budget: int = roofline.VMEM_BUDGET_BYTES,
                   hot_rows: int = 0) -> int:
    """Scan-tile rows from (d, dtype, k, block_q, VMEM budget).

    Largest power-of-two tile (cap 512 — past that the merge cost per tile
    grows without more DMA overlap to win; written for the ungated merge,
    which ran on every tile) whose modeled working set fits
    the planner budget, which sits below the scoped VMEM limit every scan
    launch requests (``roofline.VMEM_LIMIT_BYTES``; the gap is headroom
    for compiler temporaries); floor 8 (f32 sublane).
    """
    bn = 512
    while bn > 8 and topk_scan_vmem_bytes(
            bn, d, dtype, k=k, block_q=block_q,
            hot_rows=hot_rows) > vmem_budget:
        bn //= 2
    return bn


def select_topk(vals: jax.Array, idx: jax.Array, k: int):
    """Exact top-k over (Q, M) candidate (value, index) pairs.

    k VPU-shaped passes of one ``fori_loop`` (code size independent of k,
    so k=100 or an over-fetched m=400 compiles as fast as k=10): each pass
    selects the row-wise max value, and among equal values the smallest
    index, writes it to output column j, then masks the taken slot to the
    (-inf, sentinel) pair. Shared by the kernels' per-tile merge (M = k +
    tile rows) and the cross-shard reduce (M = shards * k) so the tie rule
    cannot diverge between the two levels.

    Returns ((Q, k) f32, (Q, k) i32).
    """
    vals = vals.astype(jnp.float32)
    idx = idx.astype(jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (vals.shape[0], k), 1)

    def take_one(j, carry):
        vals, idx, out_v, out_i = carry
        v = jnp.max(vals, axis=1, keepdims=True)
        is_max = vals == v
        i = jnp.min(jnp.where(is_max, idx, IDX_SENTINEL), axis=1,
                    keepdims=True)
        taken = is_max & (idx == i)
        return (jnp.where(taken, NEG_INF, vals),
                jnp.where(taken, IDX_SENTINEL, idx),
                jnp.where(col == j, v, out_v),
                jnp.where(col == j, i, out_i))

    init = (vals, idx, jnp.full(col.shape, NEG_INF, jnp.float32),
            jnp.full(col.shape, IDX_SENTINEL, jnp.int32))
    _, _, out_v, out_i = jax.lax.fori_loop(0, k, take_one, init)
    return out_v, out_i


def _merge_into(out_v_ref, out_i_ref, scores, gidx, k: int):
    """Fold a scored tile into the running top-k held in the output refs."""
    cand_v = jnp.concatenate([out_v_ref[...], scores], axis=1)
    cand_i = jnp.concatenate([out_i_ref[...], gidx], axis=1)
    nv, ni = select_topk(cand_v, cand_i, k)
    out_v_ref[...] = nv
    out_i_ref[...] = ni


# --------------------------------------------------------------------------
# production kernel: HBM-resident table, double-buffered (bn, d) tile DMA.
# One body serves both tiers — the exact f32/bf16 scan and the int8
# first pass (quant=True adds the pipelined (1, bn) row-scale block), so
# the prefetch/semaphore/padding logic cannot drift between them.
# --------------------------------------------------------------------------
GROUP_ROWS = 8   # query rows of the smallest merge: an f32 sublane group


def _topk_scan_kernel(live_ref, *refs, k: int, bn: int, bq: int,
                      valid: int, quant: bool):
    """One table tile's step of the scan, for every resident query block.

    The queries of the launch's row group stay resident, and so do their
    running (rows, k) lists; each step waits for its tile's DMA, starts
    the next one, and then scores the tile against each query block in
    turn. ``live_ref`` holds one flag a query block (1 if any entry is
    non-zero): a block of all-zero rows is scored only on the tiles that
    hold its first k rows, which give it its exact answer (every score
    is +-0, ties go to the smaller id); a later tile's +-0 scores equal
    its running k-th value and could not open the gate below.

    A scored block's tile lands in the (bq, bn) ``scores_s`` scratch and
    is merged only where it can change a running top-k: a row opens when
    its best score in the tile is strictly above its running k-th value.
    No open row: the block's step ends. Open rows in one GROUP_ROWS-row
    group: the exact ``select_topk`` merge runs on that group's rows
    alone. Open rows in two or more groups: it runs once on the whole
    block, which costs about as much as two group merges. ``counts_ref``
    counts, in lane 0, the row groups merged (a block merge counts every
    group of the block) and, in lane 1, the (row group, tile) pairs
    scored.

    The strict ``>`` is exact: tiles arrive in ascending row order (the
    table axis is the sequential inner grid axis), so a later score equal
    to a row's k-th value has a larger index and loses the tie under the
    oracle's total order; a merge it would have run changes nothing, and
    so does a block merge on the rows that did not open. Until k valid
    rows have been seen the k-th value is -inf, so every finite score
    opens. Padded rows (global index >= valid) score -inf and so never
    open a row, and carry the IDX_SENTINEL id into a merge.
    """
    tbl_hbm, scale_ref, *refs = refs if quant else (refs[0], None,
                                                     *refs[1:])
    (q_ref, out_v_ref, out_i_ref, counts_ref, tile_s, scores_s, sem,
     *cast_s) = refs           # cast_s: the f32 tile, for non-f32 tables
    g = pl.program_id(0)
    t = pl.program_id(1)
    T = pl.num_programs(1)
    f32 = jnp.float32
    nb = q_ref.shape[0] // bq
    lane = jax.lax.broadcasted_iota(jnp.int32, counts_ref.shape, 1)

    def tile_copy(tt, op):
        """start/wait tile tt's contiguous-row DMA on buffer slot tt % 2."""
        getattr(pltpu.make_async_copy(
            tbl_hbm.at[pl.ds(tt * bn, bn)],
            tile_s.at[pl.ds((tt % 2) * bn, bn)],
            sem.at[tt % 2]), op)()

    @pl.when(t == 0)
    def _prologue():           # new query row group: restart the pipeline
        tile_copy(0, "start")
        out_v_ref[...] = jnp.full_like(out_v_ref, NEG_INF)
        out_i_ref[...] = jnp.full_like(out_i_ref, IDX_SENTINEL)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    @pl.when(t + 1 < T)
    def _prefetch_next():      # double buffering: next tile's DMA flies
        tile_copy(t + 1, "start")   # while this tile is scored on the MXU

    tile_copy(t, "wait")

    tile = tile_s.at[pl.ds((t % 2) * bn, bn)]
    if cast_s:                 # int8 / bf16 tiles: cast once, not per block
        cast_s[0][...] = tile[...].astype(f32)
        tile = cast_s[0]

    def scan_block(b: int):
        """Score the tile against query block b and merge where it opens."""
        block = slice(b * bq, (b + 1) * bq)
        scores = jax.lax.dot_general(q_ref[block, :].astype(f32),
                                     tile[...].astype(f32),
                                     (((1,), (1,)), ((), ())),
                                     precision=_F32,
                                     preferred_element_type=f32)
        if scale_ref is not None:  # int8 tiles: back to embedding units
            scores = scores * scale_ref[...]
        scores_s[...] = scores

        @pl.when((t + 1) * bn > valid)
        def _demote_padding():     # only tiles that reach past the valid rows
            col = t * bn + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores_s[...] = jnp.where(col >= valid, NEG_INF, scores_s[...])

        def merge(lo, n: int):
            """Merge the tile into the running lists of the block's n rows
            from row lo; padded rows carry the IDX_SENTINEL id."""
            col = t * bn + jax.lax.broadcasted_iota(jnp.int32, (n, bn), 1)
            rows = pl.ds(pl.multiple_of(b * bq + lo, GROUP_ROWS), n)
            _merge_into(out_v_ref.at[rows], out_i_ref.at[rows],
                        scores_s[pl.ds(lo, n), :],
                        jnp.where(col >= valid, IDX_SENTINEL, col), k)

        # a row opens where its best score in the tile beats its running
        # k-th value (the minimum of its descending running list)
        best = jnp.max(scores_s[...], axis=1, keepdims=True)
        kth = jnp.min(out_v_ref[block, :], axis=1, keepdims=True)
        group = (jax.lax.broadcasted_iota(jnp.int32, best.shape, 0)
                 // GROUP_ROWS)
        lo = jnp.min(jnp.where(best > kth, group, bq))   # bq: no row opens
        hi = jnp.max(jnp.where(best > kth, group, -1))
        counts_ref[...] += jnp.where(lane == 1, bq // GROUP_ROWS, 0)

        @pl.when(lo == hi)         # one group opens: merge its rows alone
        def _merge_group():
            merge(pl.multiple_of(lo * GROUP_ROWS, GROUP_ROWS), GROUP_ROWS)
            counts_ref[...] += jnp.where(lane == 0, 1, 0)

        @pl.when(lo < hi)          # two or more: one merge of the whole block
        def _merge_block():
            merge(0, bq)
            counts_ref[...] += jnp.where(lane == 0, bq // GROUP_ROWS, 0)

    for b in range(nb):        # a zero block stops after its first k rows
        pl.when((live_ref[g * nb + b] != 0) | (t * bn < k))(
            functools.partial(scan_block, b))


MAX_RESIDENT_BLOCKS = 8   # query blocks one launch keeps resident: each is
                          # its own copy of the block body in the kernel


def resident_query_blocks(blocks: int, bq: int, bn: int, d: int, dtype, *,
                          k: int,
                          vmem_budget: int = roofline.VMEM_BUDGET_BYTES
                          ) -> int:
    """Query blocks of bq rows one scan launch keeps resident at tile bn.

    The most blocks, up to ``blocks`` and MAX_RESIDENT_BLOCKS, whose
    queries and running lists fit the planner budget beside the tile
    (:func:`topk_scan_vmem_bytes`); floor 1. A launch with more blocks
    runs its row groups as an outer grid axis, each scanning the table.
    """
    nb = min(blocks, MAX_RESIDENT_BLOCKS)
    while nb > 1 and topk_scan_vmem_bytes(
            bn, d, dtype, k=k, block_q=bq, rows=nb * bq) > vmem_budget:
        nb -= 1
    return nb


def _launch_topk_scan(table, scales, queries, *, k: int, valid: int,
                      bq: int, bn: int, interpret: bool):
    """Pad to tile multiples and launch :func:`_topk_scan_kernel`.

    scales=None is the exact scan; a (1, N) f32 scales row makes it the
    int8 first pass. bq is rounded up to whole GROUP_ROWS groups (padded
    query rows are zero). The grid is (row groups, table tiles): at most
    :func:`resident_query_blocks` blocks a row group, one row group for
    the store's calls, so each table tile is read once a call. Returns
    the unpadded ((Q, k) f32, (Q, k) i32) and the scan's (4,) i32 counts:
    row-group merges run, (row group, tile) pairs scored, query blocks,
    and query blocks of zero rows alone."""
    N, d = table.shape
    Q = queries.shape[0]
    quant = scales is not None
    if N % bn:
        pad = (-N) % bn
        table = jnp.pad(table, ((0, pad), (0, 0)))
        if quant:
            scales = jnp.pad(scales, ((0, 0), (0, pad)),
                             constant_values=1.0)
        N = table.shape[0]
    bq = -(-bq // GROUP_ROWS) * GROUP_ROWS
    blocks = -(-Q // bq)
    nb = resident_query_blocks(blocks, bq, bn, d, table.dtype, k=k)
    n_groups = -(-blocks // nb)
    nb = -(-blocks // n_groups)       # spread the blocks evenly
    rows = nb * bq
    qp = jnp.pad(queries, ((0, n_groups * rows - Q), (0, 0)))
    live = jnp.any(qp.reshape(n_groups * nb, bq * d) != 0,
                   axis=1).astype(jnp.int32)   # NaN counts as non-zero
    grid = (n_groups, N // bn)        # table tiles innermost (sequential)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]       # table (HBM)
    operands = [table]
    if quant:
        in_specs.append(pl.BlockSpec((1, bn), lambda g, t, _: (0, t)))
        operands.append(scales)                             # row scales
    in_specs.append(pl.BlockSpec((rows, d), lambda g, t, _: (g, 0)))
    operands.append(qp)                                     # resident queries
    scratch = [
        pltpu.VMEM((2 * bn, d), table.dtype),               # tile slots
        pltpu.VMEM((bq, bn), jnp.float32),                  # tile scores
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if table.dtype != jnp.float32:
        scratch.append(pltpu.VMEM((bn, d), jnp.float32))    # f32 tile
    out_v, out_i, counts = pl.pallas_call(
        functools.partial(_topk_scan_kernel, k=k, bn=bn, bq=bq,
                          valid=valid, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                          # live flags
            grid=grid,
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((rows, k), lambda g, t, _: (g, 0)),  # top-k
                pl.BlockSpec((rows, k), lambda g, t, _: (g, 0)),
                pl.BlockSpec((8, 128), lambda g, t, _: (g, 0)),   # counts
            ),
            scratch_shapes=scratch,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_groups * rows, k), jnp.float32),
            jax.ShapeDtypeStruct((n_groups * rows, k), jnp.int32),
            jax.ShapeDtypeStruct((n_groups * 8, 128), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=roofline.VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="topk_mips_quant" if quant else "topk_mips",
    )(live, *operands)
    counts = jnp.stack([jnp.sum(counts[::8, 0]), jnp.sum(counts[::8, 1]),
                        jnp.int32(live.shape[0]),
                        jnp.int32(live.shape[0]) - jnp.sum(live)])
    return out_v[:Q], out_i[:Q], counts


@functools.partial(jax.jit, static_argnames=("k", "valid", "block_q",
                                             "block_n", "interpret"))
def topk_mips_counted(table, queries, *, k: int, valid: int | None = None,
                      block_q: int = DEFAULT_BLOCK_Q,
                      block_n: int | None = None, interpret: bool = False):
    """:func:`topk_mips` and the merge gate's counts, as the store calls it.

    Returns ((Q, k) f32 scores, (Q, k) i32 shard-local row ids, (4,) i32
    [row-group merges run, (row group, tile) pairs scored, query blocks,
    query blocks of zero rows alone]).
    """
    N, d = table.shape
    valid = N if valid is None else valid
    assert 0 < valid <= N, (valid, N)
    bq = min(block_q, queries.shape[0])
    planned = choose_block_n(d, table.dtype, k=k, block_q=bq)
    bn = planned if block_n is None else min(block_n, planned)
    return _launch_topk_scan(table, None, queries, k=k, valid=valid,
                             bq=bq, bn=min(bn, N), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "valid", "block_q",
                                             "block_n", "interpret"))
def topk_mips(table, queries, *, k: int, valid: int | None = None,
              block_q: int = DEFAULT_BLOCK_Q, block_n: int | None = None,
              interpret: bool = False):
    """Exact-MIPS top-k of `queries` against one table shard.

    table: (N, d) HBM-resident shard (bf16 or f32 — scored in f32);
    queries: (Q, d). `valid` masks padded tail rows (row >= valid scores
    -inf and can never be returned); rows are padded here to a block_n
    multiple if the caller didn't (the store pre-pads at load so serving
    never re-materializes the table). block_n=None sizes the scan tile
    with :func:`choose_block_n` against the VMEM budget; an explicit
    block_n is capped (not pinned) by the k-aware plan — the running
    (bq, k) list is this kernel's own working set, and the store passes
    its load-time tile (planned at ``DEFAULT_PLAN_K``) for every
    query-time k.

    Returns ((Q, k) f32 scores, (Q, k) i32 shard-local row ids), both
    sorted by the oracle's total order (descending score, ascending index
    on ties). If valid < k the tail entries are (-inf, int32 max).
    """
    v, i, _ = topk_mips_counted(table, queries, k=k, valid=valid,
                                block_q=block_q, block_n=block_n,
                                interpret=interpret)
    return v, i


# --------------------------------------------------------------------------
# quantized first pass: int8 tiles through the same double-buffered DMA
# skeleton, over-fetched running top-m (the two-tier scan's tier one)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("m", "valid", "block_q",
                                             "block_n", "interpret"))
def topk_mips_quant_counted(qtable, scales, queries, *, m: int,
                            valid: int | None = None,
                            block_q: int = DEFAULT_BLOCK_Q,
                            block_n: int | None = None,
                            interpret: bool = False):
    """:func:`topk_mips_quant` and the merge gate's counts, as the store
    calls it.

    Returns ((Q, m) f32 approx scores, (Q, m) i32 shard-local row ids,
    (4,) i32 [row-group merges run, (row group, tile) pairs scored, query
    blocks, query blocks of zero rows alone]).
    """
    N, d = qtable.shape
    valid = N if valid is None else valid
    assert 0 < valid <= N, (valid, N)
    assert qtable.dtype == jnp.int8, qtable.dtype
    bq = min(block_q, queries.shape[0])
    planned = choose_block_n(d, qtable.dtype, k=m, block_q=bq)
    bn = planned if block_n is None else min(block_n, planned)
    return _launch_topk_scan(
        qtable, scales.astype(jnp.float32).reshape(1, N), queries, k=m,
        valid=valid, bq=bq, bn=min(bn, N), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("m", "valid", "block_q",
                                             "block_n", "interpret"))
def topk_mips_quant(qtable, scales, queries, *, m: int,
                    valid: int | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_n: int | None = None, interpret: bool = False):
    """Int8 first-pass scan: approximate top-``m`` candidates per query.

    qtable: (N, d) int8 shard (``quant.quantize_rows``); scales: (N,) f32
    per-row dequantization scales; queries: (Q, d). The tile-DMA skeleton
    is :func:`topk_mips`'s (shared ``_topk_scan_kernel`` body), but the
    streamed tiles are int8 — 4x less HBM traffic per scan — with the
    per-row scales riding a BlockSpec-pipelined (1, bn) block. Scores are
    (q @ tile.T) * scale in f32; the dominant error is the quantization
    itself (bounded per row — see ``quant.quantize_rows``), which the
    exact second tier absorbs.

    Like the exact kernel, an explicit block_n is capped (not pinned) by
    the ``m``-aware :func:`choose_block_n` plan: the over-fetched (bq, m)
    candidate list is this kernel's own working set — a caller passing a
    tile planned for plain top-k (the store's load-time
    ``DEFAULT_PLAN_K`` plan) must not silently bust the VMEM budget when
    ``m = k * overfetch`` runs far past that allowance.

    Returns ((Q, m) f32 approx scores, (Q, m) i32 shard-local row ids) —
    feed the ids to ``quant.rescore_exact`` for the exact second tier.
    """
    v, i, _ = topk_mips_quant_counted(qtable, scales, queries, m=m,
                                      valid=valid, block_q=block_q,
                                      block_n=block_n, interpret=interpret)
    return v, i


def _masked_select(scores, valid: int, k: int):
    """Demote rows >= valid to sentinels and run the selection network —
    the shared tail of the jnp scan paths."""
    gidx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    invalid = gidx >= valid
    return select_topk(jnp.where(invalid, NEG_INF, scores),
                       jnp.where(invalid, IDX_SENTINEL, gidx), k)


@functools.partial(jax.jit, static_argnames=("m", "valid"))
def topk_mips_quant_xla(qtable, scales, queries, *, m: int,
                        valid: int | None = None):
    """Plain-jnp int8 first pass: the CPU serving path for the quant tier
    and the cross-check for :func:`topk_mips_quant` (bitwise identical on
    integer queries, where every f32 dot is exact; on continuous data an
    accumulation-order ulp flip at the m-boundary is possible — and
    harmless, since tier two rescores exactly)."""
    N = qtable.shape[0]
    f32 = jnp.float32
    scores = jnp.matmul(queries.astype(f32), qtable.astype(f32).T,
                        precision=_F32) * scales.astype(f32).reshape(1, N)
    return _masked_select(scores, N if valid is None else valid, m)


# --------------------------------------------------------------------------
# rowwise reference: one table row per grid step, BlockSpec-pipelined
# --------------------------------------------------------------------------
def _topk_rowwise_kernel(row_ref, q_ref, out_v_ref, out_i_ref, *, k: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_v_ref[...] = jnp.full_like(out_v_ref, NEG_INF)
        out_i_ref[...] = jnp.full_like(out_i_ref, IDX_SENTINEL)

    f32 = jnp.float32
    score = jax.lax.dot_general(q_ref[...].astype(f32),
                                row_ref[...].astype(f32),
                                (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=f32)   # (Q, 1)
    gidx = jnp.full_like(score, t, dtype=jnp.int32)
    _merge_into(out_v_ref, out_i_ref, score, gidx, k)


@functools.partial(jax.jit, static_argnames=("k", "valid", "interpret"))
def topk_mips_rowwise(table, queries, *, k: int, valid: int | None = None,
                      interpret: bool = False):
    """One-row-per-grid-step top-k, kept as the interpret-mode reference for
    :func:`topk_mips` (grid covers only the valid rows, so padding needs no
    masking here)."""
    N, d = table.shape
    Q = queries.shape[0]
    valid = N if valid is None else valid
    assert 0 < valid <= N, (valid, N)   # grid=(0,) would return garbage
    return pl.pallas_call(
        functools.partial(_topk_rowwise_kernel, k=k),
        grid=(valid,),
        in_specs=[
            pl.BlockSpec((1, d), lambda t: (t, 0)),         # table row
            pl.BlockSpec((Q, d), lambda t: (0, 0)),         # queries resident
        ],
        out_specs=(
            pl.BlockSpec((Q, k), lambda t: (0, 0)),
            pl.BlockSpec((Q, k), lambda t: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ),
        interpret=interpret,
        name="topk_mips_rowwise",
    )(table, queries)


# --------------------------------------------------------------------------
# XLA paths: the CPU serving path and the cross-shard merge
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k", "valid"))
def topk_mips_xla(table, queries, *, k: int, valid: int | None = None):
    """Plain-jnp shard top-k: full (Q, N) scores + the shared selection
    network. The serving path on CPU (Pallas interpret mode is Python-slow)
    and the jnp-level oracle for the kernels."""
    N = table.shape[0]
    f32 = jnp.float32
    scores = jnp.matmul(queries.astype(f32), table.astype(f32).T,
                        precision=_F32)
    return _masked_select(scores, N if valid is None else valid, k)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(vals, idx, *, k: int):
    """Cross-shard reduce: (P, Q, kk) per-shard results (ids already global)
    → the global (Q, k). Each shard's list is exact for its rows, so the
    global top-k is the top-k of the P*kk candidates — one selection pass,
    same tie rule."""
    P, Q, kk = vals.shape
    return select_topk(jnp.swapaxes(vals, 0, 1).reshape(Q, P * kk),
                       jnp.swapaxes(idx, 0, 1).reshape(Q, P * kk), k)

"""Row orders and query layouts that drive the top-k scan's merge gate
and its zero-block skip (``topk._topk_scan_kernel``) to each of their
edges, shared by the exact and the int8 scan's tests.

Every case is integer data at d=16, scanned in block_n=64 tiles unless it
says otherwise, so every f32 score is exact (or, on the int8 pass, one
rounding of an exact integer) and the kernels must agree bitwise with the
jnp paths and the numpy oracle. ``case(name, kk)`` builds the (N, d) f32
table, the (Q, d) queries, ``valid``, ``block_q`` and ``block_n`` for a
running list of kk entries (k, or the int8 pass's m); ``merges`` gives
the row-group merges the gate must run where the row order fixes them,
else None.
"""
import numpy as np

from repro.kernels import ref

D = 16
BLOCK_N = 64
IDX_SENTINEL = np.iinfo(np.int32).max
CASES = ["rising", "falling", "tie_at_kth", "tail_valid", "valid_below_k",
         "zero_queries", "q_not_multiple_of_8", "one_group_opens",
         "zero_block_after_live", "zero_block_between_live",
         "all_zero_queries", "zero_block_k_above_block_n",
         "zero_block_valid_below_k", "zero_query_in_live_block"]


def _positive(q, seed):
    return np.random.default_rng(seed).integers(1, 5, size=(q, D)).astype(
        np.float32)


def _ints(n, seed):
    return np.random.default_rng(seed).integers(-4, 5, size=(n, D)).astype(
        np.float32)


def _levels(lev):
    """Constant rows: positive queries all rank the rows by lev."""
    return np.repeat(np.asarray(lev, np.float32)[:, None], D, axis=1)


def case(name: str, kk: int) -> dict:
    n = 4 * BLOCK_N
    c = {"valid": n, "block_q": 8, "block_n": BLOCK_N, "kk": kk,
         "merges": None}
    if name == "rising":           # every tile beats every row's k-th
        c |= {"table": _levels(1 + np.arange(n)), "queries": _positive(16, 1),
              "merges": 2 * 4}     # 2 row groups x 4 tiles
    elif name == "falling":        # only tile 0 holds any row's top-kk
        c |= {"table": _levels(n - np.arange(n)), "queries": _positive(16, 2),
              "merges": 2}         # one merge per row group
    elif name == "tie_at_kth":
        # tile 0 ends its top-kk at level 5; tile 1 is all 5s (equal to the
        # k-th value, larger ids: no merge); tile 2 holds one 6 (merges)
        lev = np.full(n, 5)
        lev[:kk - 1] = 10
        lev[2 * BLOCK_N + 7] = 6
        c |= {"table": _levels(lev), "queries": _positive(16, 3),
              "merges": 2 * 2}     # tiles 0 and 2, both row groups
    elif name == "tail_valid":     # valid ends inside the last tile
        tbl = _ints(n, 4)
        tbl[230:] = 4.0            # padding that would win if unmasked
        c |= {"table": tbl, "queries": _ints(16, 5), "valid": 230}
    elif name == "valid_below_k":  # fewer valid rows than kk, and tile 1
        tbl = _ints(2 * BLOCK_N, 6)           # is padding alone
        tbl[6:] = 4.0
        c |= {"table": tbl, "queries": _ints(8, 7), "valid": 6}
    elif name == "zero_queries":   # a zero query, and Q=10 pads the second
        q = _ints(10, 9)                      # 8-row block with zero rows
        q[3] = 0.0
        c |= {"table": _ints(n, 8), "queries": q}
    elif name == "q_not_multiple_of_8":       # bq = Q = 5, rounded up to 8
        c |= {"table": _ints(n, 10), "queries": _ints(5, 11), "block_q": 128}
    elif name == "one_group_opens":
        # one 16-row block: group A's queries read dims 0..7 only, group
        # B's dims 8..15. Tile 0 ends both groups' top-kk at level 5 and
        # tile 1 ties it; tile 2 holds a row that beats it for A alone (A
        # merges alone), tile 3 one that beats it for both (block merge)
        tbl = _levels(np.full(n, 5))
        tbl[:kk - 1] = 10.0
        tbl[2 * BLOCK_N + 7, :D // 2] = 6.0
        tbl[2 * BLOCK_N + 7, D // 2:] = 0.0
        tbl[3 * BLOCK_N + 9] = 7.0
        q = _positive(16, 12)
        q[:8, D // 2:] = 0.0
        q[8:, :D // 2] = 0.0
        c |= {"table": tbl, "queries": q, "block_q": 16,
              "merges": 2 + 1 + 2}   # tiles 0, 2 (A alone) and 3
    elif name == "zero_block_after_live":
        # falling rows: the live group merges tile 0 alone; the zero group
        # (a zero block) is scored on tile 0 alone, which holds its kk rows
        q = np.zeros((16, D), np.float32)
        q[:8] = _positive(8, 13)
        c |= {"table": _levels(n - np.arange(n)), "queries": q,
              "merges": 2}
    elif name == "zero_block_between_live":
        q = _ints(24, 15)
        q[8:16] = 0.0
        c |= {"table": _ints(n, 14), "queries": q}
    elif name == "all_zero_queries":  # every block is zero: each group
        c |= {"table": _ints(n, 16),  # merges tile 0 and nothing after
              "queries": np.zeros((16, D), np.float32), "merges": 2}
    elif name == "zero_block_k_above_block_n":
        # 8-row tiles: the zero block's first kk rows span ceil(kk / 8)
        # tiles, and it merges each of them (its k-th is -inf until then)
        q = np.zeros((16, D), np.float32)
        q[:8] = _positive(8, 18)
        tbl = _levels(64 - np.arange(64))
        c |= {"table": tbl, "queries": q, "block_n": 8, "valid": 64,
              "merges": 2 * -(-kk // 8)}   # the live group alike
    elif name == "zero_block_valid_below_k":
        # fewer valid rows than kk: the zero rows' answer is the 6 valid
        # ids, then sentinels; tile 1 is padding alone
        tbl = _ints(2 * BLOCK_N, 19)
        tbl[6:] = 4.0
        q = np.zeros((16, D), np.float32)
        q[:8] = _ints(8, 20)
        c |= {"table": tbl, "queries": q, "valid": 6}
    elif name == "zero_query_in_live_block":
        q = _ints(16, 22)          # both blocks live; rows 0 and 12 zero
        q[[0, 12]] = 0.0
        c |= {"table": _ints(n, 21), "queries": q}
    else:
        raise KeyError(name)
    return c


def live_blocks(c: dict) -> np.ndarray:
    """Per query block of case c (queries padded with zero rows to whole
    blocks): True where any entry is non-zero."""
    q = c["queries"]
    bq = -(-min(c["block_q"], q.shape[0]) // 8) * 8
    qp = np.zeros((-(-q.shape[0] // bq) * bq, D), np.float32)
    qp[:q.shape[0]] = q
    return np.any(qp.reshape(-1, bq * D) != 0, axis=1)


def tile_groups(c: dict) -> int:
    """(row group, tile) pairs the scan scores for case c: a live block
    every tile, a zero block the tiles holding its first kk rows."""
    q = c["queries"]
    bq = -(-min(c["block_q"], q.shape[0]) // 8) * 8
    tiles = -(-c["table"].shape[0] // c["block_n"])
    first = min(tiles, -(-c["kk"] // c["block_n"]))
    live = live_blocks(c)
    return (bq // 8) * int(np.sum(np.where(live, tiles, first)))


def check_merges(c: dict, groups) -> None:
    """The scan's (4,) counts for case c: the query blocks and the zero
    ones among them, the (row group, tile) pairs scored, every row group
    merging its first tile and no pair twice, and exactly ``merges``
    where the row order fixes them."""
    merged, scored, blocks, zero = (int(x) for x in np.asarray(groups))
    live = live_blocks(c)
    assert (blocks, zero) == (live.size, int(np.sum(~live)))
    assert scored == tile_groups(c)
    q = c["queries"]
    row_groups = live.size * (-(-min(c["block_q"], q.shape[0]) // 8))
    assert row_groups <= merged <= scored
    if c["merges"] is not None:
        assert merged == c["merges"]


def check_oracle(c: dict, vals, ids, table=None) -> None:
    """(vals, ids) are bitwise the numpy oracle's top-kk over the valid
    rows of ``table`` (default: the case's), and sentinels past them."""
    t = c["table"] if table is None else table
    rv, ri = ref.topk_mips_ref(np.asarray(t, np.float32)[:c["valid"]],
                               c["queries"], c["kk"])
    n = ri.shape[1]
    vals, ids = np.asarray(vals), np.asarray(ids)
    np.testing.assert_array_equal(ids[:, :n], ri)
    np.testing.assert_array_equal(vals[:, :n], rv)
    assert np.all(ids[:, n:] == IDX_SENTINEL)
    assert np.all(vals[:, n:] == -np.inf)

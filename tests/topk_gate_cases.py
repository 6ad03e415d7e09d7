"""Row orders that drive the top-k scan's merge gate
(``topk._topk_scan_kernel``) to each of its edges, shared by the exact and
the int8 scan's tests.

Every case is integer data at d=16, scanned in block_n=64 tiles, so every
f32 score is exact (or, on the int8 pass, one rounding of an exact integer)
and the kernels must agree bitwise with the jnp paths. ``case(name, kk)``
builds the (N, d) f32 table, the (Q, d) queries, ``valid`` and ``block_q``
for a running list of kk entries (k, or the int8 pass's m); ``merges``
gives the row-group merges the gate must run where the row order fixes
them, else None.
"""
import numpy as np

D = 16
BLOCK_N = 64
CASES = ["rising", "falling", "tie_at_kth", "tail_valid", "valid_below_k",
         "zero_queries", "q_not_multiple_of_8", "one_group_opens"]


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
    c = {"valid": n, "block_q": 8, "merges": None}
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
    else:
        raise KeyError(name)
    return c


def tile_groups(c: dict) -> int:
    """Row groups x tiles the scan visits for case c."""
    q = c["queries"].shape[0]
    bq = -(-min(c["block_q"], q) // 8) * 8
    return -(-q // bq) * (bq // 8) * (c["table"].shape[0] // BLOCK_N)


def check_merges(c: dict, groups) -> None:
    """The gate's (2,) counts for case c: every row group merges its first
    tile, never more than every tile, and exactly ``merges`` where the row
    order fixes them."""
    merged, visited = (int(x) for x in np.asarray(groups))
    assert visited == tile_groups(c)
    assert visited // (c["table"].shape[0] // BLOCK_N) <= merged <= visited
    if c["merges"] is not None:
        assert merged == c["merges"]

"""Retrieval-serving subsystem: Pallas top-k vs the numpy oracle, the
device-sharded store (checkpoint round-trip, shard placement, cross-shard
merge), and the micro-batching frontend under concurrent load.

Exactness strategy: tables/queries are small random INTEGERS cast to the
embedding dtype — every value is exactly representable in bf16 and every
f32 dot product is exact, so kernel and numpy oracle scores are bitwise
identical regardless of accumulation order, and the (frequent) score ties
genuinely exercise the smaller-index tie rule."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HybridConfig, HybridEmbeddingTrainer
from repro.core.partition import build_episode_blocks
from repro.embed_serve import (MicroBatcher, ShardedEmbeddingStore,
                               merge_topk, topk_mips, topk_mips_rowwise,
                               topk_mips_xla)
from repro.embed_serve import topk as tk
from repro.kernels import ref
from repro.train.checkpoint import load_arrays, save_checkpoint

import topk_gate_cases as gate


def _int_table(n, d, seed=0, dtype=jnp.float32, lo=-4, hi=5):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(lo, hi, size=(n, d)),
                       dtype=jnp.float32).astype(dtype)


# --------------------------------------------------------------------- topk
@pytest.mark.parametrize("k,dtype,N,Q", [
    (1, jnp.float32, 230, 17),
    (10, jnp.float32, 230, 17),
    (10, jnp.bfloat16, 230, 17),
    (100, jnp.float32, 130, 5),   # k > block_n fraction, odd N
])
def test_topk_mips_matches_oracle(k, dtype, N, Q):
    tbl = _int_table(N, 32, seed=1, dtype=dtype)
    q = _int_table(Q, 32, seed=2)
    rv, ri = ref.topk_mips_ref(np.asarray(tbl), np.asarray(q), k)
    v, i = topk_mips(tbl, q, k=k, valid=N, block_q=8, block_n=64,
                     interpret=True)
    np.testing.assert_array_equal(np.asarray(i), ri)
    np.testing.assert_array_equal(np.asarray(v), rv)


def test_topk_mips_heavy_ties():
    """Only 6 distinct rows -> ties everywhere; the smaller index must win
    at every rank, in-tile, across tiles, and across the k boundary."""
    rng = np.random.default_rng(3)
    base = np.asarray(_int_table(6, 16, seed=4))
    tbl = jnp.asarray(base[rng.integers(0, 6, size=200)])
    q = _int_table(9, 16, seed=5)
    rv, ri = ref.topk_mips_ref(np.asarray(tbl), np.asarray(q), 25)
    v, i = topk_mips(tbl, q, k=25, valid=200, block_q=4, block_n=32,
                     interpret=True)
    np.testing.assert_array_equal(np.asarray(i), ri)
    np.testing.assert_array_equal(np.asarray(v), rv)


def test_topk_mips_padded_shard_masked():
    """Rows >= valid (the store's block_n padding) can never be returned,
    even when their zero rows would out-score real (negative) rows."""
    tbl = jnp.asarray(np.full((64, 8), -2.0, np.float32))  # pad rows are 0
    q = jnp.asarray(np.ones((3, 8), np.float32))
    v, i = topk_mips(tbl, q, k=5, valid=40, block_q=4, block_n=16,
                     interpret=True)
    assert int(np.asarray(i).max()) < 40
    rv, ri = ref.topk_mips_ref(np.asarray(tbl)[:40], np.asarray(q), 5)
    np.testing.assert_array_equal(np.asarray(i), ri)


@pytest.mark.parametrize("name", gate.CASES)
def test_topk_mips_merge_gate_is_exact(name):
    """The merge gate returns bitwise what the jnp path and the numpy
    oracle do on row orders that merge every tile, only the first, tie at
    the k-th value across a tile boundary, end valid rows inside a tile or
    below k, pad the query block, and open one row group of a block
    alone, and on calls whose query blocks hold zero rows alone (after,
    between and without live blocks, past one tile, below k valid rows)
    or a zero row among live ones; its counts match the row order."""
    c = gate.case(name, 10)
    kw = dict(k=10, valid=c["valid"], block_q=c["block_q"],
              block_n=c["block_n"], interpret=True)
    tbl, q = jnp.asarray(c["table"]), jnp.asarray(c["queries"])
    v, i, groups = tk.topk_mips_counted(tbl, q, **kw)
    xv, xi = topk_mips_xla(tbl, q, k=10, valid=c["valid"])
    np.testing.assert_array_equal(np.asarray(i), np.asarray(xi))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(xv))
    gate.check_oracle(c, v, i)
    gate.check_merges(c, groups)
    pv, pi = topk_mips(tbl, q, **kw)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(xi))
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(xv))


def test_topk_mips_row_groups_past_residency():
    """More query blocks than one launch keeps resident run as row groups
    of an outer grid axis, each scanning the table: 9 blocks of 8 rows
    over MAX_RESIDENT_BLOCKS = 8 become 2 groups of 5 (one block of
    padding); block 4 and the padding are zero rows. The answer is the
    jnp path's and the oracle's, bitwise."""
    assert tk.MAX_RESIDENT_BLOCKS == 8
    tbl = _int_table(256, 16, seed=30)
    q = np.array(_int_table(72, 16, seed=31))
    q[32:40] = 0.0
    v, i, groups = tk.topk_mips_counted(tbl, jnp.asarray(q), k=10,
                                        block_q=8, block_n=64,
                                        interpret=True)
    xv, xi = topk_mips_xla(tbl, jnp.asarray(q), k=10)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(xi))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(xv))
    rv, ri = ref.topk_mips_ref(np.asarray(tbl), q, 10)
    np.testing.assert_array_equal(np.asarray(i), ri)
    np.testing.assert_array_equal(np.asarray(v), rv)
    merged, scored, blocks, zero = (int(x) for x in np.asarray(groups))
    assert (blocks, zero) == (10, 2)
    assert scored == 8 * 4 + 2 * 1       # live blocks 4 tiles, zero ones 1
    assert 10 <= merged <= scored


def test_resident_query_blocks_fit_the_budget():
    """The planner keeps every block of a serving call resident at the
    cells' shapes, caps the count at MAX_RESIDENT_BLOCKS, and drops to
    one block where the budget holds no more."""
    for dtype, k in ((jnp.float32, 10), (jnp.float32, 100), (jnp.int8, 40),
                     (jnp.int8, 400)):
        assert tk.resident_query_blocks(2, 128, 512, 128, dtype, k=k) == 2
        nb = tk.resident_query_blocks(64, 128, 512, 128, dtype, k=k)
        assert nb == tk.MAX_RESIDENT_BLOCKS
        assert (tk.topk_scan_vmem_bytes(512, 128, dtype, k=k, rows=nb * 128)
                <= tk.roofline.VMEM_BUDGET_BYTES)
    one = tk.topk_scan_vmem_bytes(512, 128, jnp.float32, k=10, rows=128)
    assert tk.resident_query_blocks(2, 128, 512, 128, jnp.float32, k=10,
                                    vmem_budget=one) == 1


@pytest.mark.parametrize("impl,merged,visited", [("pallas", 2, 8),
                                                 ("xla", 0, 0)])
def test_store_counts_scan_merges(impl, merged, visited):
    """The store adds the Pallas scan's counts to the obs counters
    serve.topk.merge_groups / tile_groups / query_blocks / zero_blocks;
    other paths add nothing. 16 positive queries are one live block of
    two row groups, each scored on the table's 4 tiles and merged on the
    first alone."""
    from repro import obs
    c = gate.case("falling", 10)
    store = ShardedEmbeddingStore.from_array(c["table"],
                                             block_n=gate.BLOCK_N)
    reg = obs.enable()
    try:
        store.topk(c["queries"], 10, impl=impl)
        counters = reg.snapshot()["counters"]
    finally:
        obs.disable()
    assert counters.get("serve.topk.merge_groups", 0) == merged
    assert counters.get("serve.topk.tile_groups", 0) == visited
    assert counters.get("serve.topk.query_blocks", 0) == (impl == "pallas")
    assert counters.get("serve.topk.zero_blocks", 0) == 0


@pytest.mark.parametrize("impl,scan", [("pallas", "serve.topk"),
                                       ("quant_pallas", "serve.quant")])
def test_store_counts_zero_blocks_of_a_padded_call(impl, scan):
    """A 256-row call with 5 real rows, as the batcher pads it: on each of
    two shards (2 tiles of 64 rows) the second 128-row query block is zero
    rows alone and is scored on the tile holding its first k (or m) rows
    only, so a shard scores 16 row groups x 2 tiles + 16 x 1; falling
    rows merge each block's 16 groups once, on tile 0. The answer is the
    oracle's, for the real rows and the zero ones."""
    from repro import obs
    c = gate.case("falling", 10)
    dev = jax.devices()[0]
    store = ShardedEmbeddingStore.from_array(
        c["table"], devices=[dev, dev], block_n=gate.BLOCK_N,
        quant="int8" if impl.startswith("quant") else None)
    q = np.zeros((256, gate.D), np.float32)
    q[:5] = c["queries"][:5]
    reg = obs.enable()
    try:
        v, i = store.topk(q, 10, impl=impl)
        counters = reg.snapshot()["counters"]
    finally:
        obs.disable()
    assert counters[f"{scan}.query_blocks"] == 2 * 2
    assert counters[f"{scan}.zero_blocks"] == 2 * 1
    assert counters[f"{scan}.tile_groups"] == 2 * (16 * 2 + 16 * 1)
    assert counters[f"{scan}.merge_groups"] == 2 * (16 + 16)
    rv, ri = ref.topk_mips_ref(c["table"], q, 10)
    np.testing.assert_array_equal(i, ri)
    np.testing.assert_array_equal(v, rv)


@pytest.mark.parametrize("fn", [topk_mips_rowwise, topk_mips_xla],
                         ids=["rowwise", "xla"])
def test_topk_reference_paths_match_oracle(fn):
    tbl = _int_table(57, 24, seed=6)
    q = _int_table(11, 24, seed=7)
    rv, ri = ref.topk_mips_ref(np.asarray(tbl), np.asarray(q), 8)
    kw = {"interpret": True} if fn is topk_mips_rowwise else {}
    v, i = fn(tbl, q, k=8, valid=57, **kw)
    np.testing.assert_array_equal(np.asarray(i), ri)
    np.testing.assert_array_equal(np.asarray(v), rv)


def test_merge_topk_equals_global_oracle():
    """Per-shard exact top-k lists + the cross-shard reduce == top-k over
    the whole table (3 uneven shards, sentinel-padded short shard)."""
    N, d, Q, k = 150, 16, 7, 12
    tbl = np.asarray(_int_table(N, d, seed=8))
    q = np.asarray(_int_table(Q, d, seed=9))
    bounds = [(0, 64), (64, 128), (128, 150)]   # last shard < k rows? no: 22
    per_v, per_i = [], []
    for lo, hi in bounds:
        v, i = ref.topk_mips_ref(tbl[lo:hi], q, k)   # local top-k...
        per_v.append(v)
        per_i.append(i + lo)                         # ...with global ids
    gv, gi = merge_topk(jnp.asarray(np.stack(per_v)),
                        jnp.asarray(np.stack(per_i)), k=k)
    rv, ri = ref.topk_mips_ref(tbl, q, k)
    np.testing.assert_array_equal(np.asarray(gi), ri)
    np.testing.assert_array_equal(np.asarray(gv), rv)


# -------------------------------------------------------------------- store
@pytest.mark.parametrize("impl", ["xla", "pallas", "rowwise"])
def test_store_multi_shard_query(impl):
    """Two shards (same device twice on this container): shard fan-out +
    global-id merge equal the oracle over the unsharded table."""
    dev = jax.devices()[0]
    tbl = np.asarray(_int_table(143, 16, seed=10))
    store = ShardedEmbeddingStore.from_array(tbl, devices=[dev, dev],
                                             block_n=32)
    q = np.asarray(_int_table(6, 16, seed=11))
    rv, ri = store.oracle_topk(q, 9)
    v, i = store.topk(q, 9, impl=impl)
    np.testing.assert_array_equal(i, ri)
    np.testing.assert_array_equal(v, rv)


@pytest.mark.parametrize("impl", ["xla", "pallas", "rowwise"])
def test_store_empty_tail_shards(impl):
    """num_nodes < (P-1) * rows leaves trailing shards with zero valid
    rows (block assignment); they must be skipped, not scanned."""
    dev = jax.devices()[0]
    tbl = np.asarray(_int_table(9, 8, seed=30))
    store = ShardedEmbeddingStore.from_array(tbl, devices=[dev] * 4,
                                             block_n=16)
    assert store.valid == (3, 3, 3, 0)
    q = np.asarray(_int_table(4, 8, seed=31))
    rv, ri = store.oracle_topk(q, 5)
    v, i = store.topk(q, 5, impl=impl)
    np.testing.assert_array_equal(i, ri)
    np.testing.assert_array_equal(v, rv)


def test_recall_at_k_tie_tolerance():
    from repro.embed_serve import recall_at_k

    oracle_ids = np.array([[4, 7]])
    oracle_vals = np.array([[2.0, 1.0]])
    # plain set recall: one hit of two
    assert recall_at_k(np.array([[4, 9]]), oracle_ids) == 0.5
    # id 9 scored at the k-th boundary (an ulp-flipped exact tie): counts
    got_vals = np.array([[2.0, 1.0]])
    assert recall_at_k(np.array([[4, 9]]), oracle_ids, got_vals=got_vals,
                       oracle_vals=oracle_vals) == 1.0
    # a genuinely wrong id (score below the boundary) still misses
    got_vals = np.array([[2.0, 0.5]])
    assert recall_at_k(np.array([[4, 9]]), oracle_ids, got_vals=got_vals,
                       oracle_vals=oracle_vals) == 0.5
    # a kernel repeating its rank-1 id cannot double-count its way to 1.0
    got_vals = np.array([[2.0, 2.0]])
    assert recall_at_k(np.array([[4, 4]]), oracle_ids, got_vals=got_vals,
                       oracle_vals=oracle_vals) == 0.5


def test_store_k_clamped_and_cosine():
    tbl = np.asarray(_int_table(12, 8, seed=12, lo=1, hi=5))  # nonzero rows
    store = ShardedEmbeddingStore.from_array(tbl, normalize=True)
    v, i = store.topk(np.asarray(_int_table(2, 8, seed=13)), 50)
    assert v.shape == (2, 12)                  # k clamped to num_nodes
    assert sorted(i[0].tolist()) == list(range(12))
    norms = np.linalg.norm(store.host_table.astype(np.float32), axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-2)


@pytest.fixture(scope="module")
def trained_ckpt(tmp_path_factory):
    """A few real training steps -> checkpoint (bf16 default dtype)."""
    nodes, d = 300, 16
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = HybridConfig(dim=d, minibatch=32, negatives=4, subparts=1,
                       neg_pool=256, impl="ref", seed=3)   # dtype: bf16
    tr = HybridEmbeddingTrainer(nodes, mesh, cfg)
    tr.init_embeddings()
    pairs = np.random.default_rng(4).integers(0, nodes, size=(512, 2))
    eb = build_episode_blocks(pairs, tr.part, pad_multiple=cfg.minibatch)
    tr.train_episode(eb)
    V, C = tr.embeddings(), tr.context_embeddings()
    path = str(tmp_path_factory.mktemp("ckpt") / "embeddings.npz")
    save_checkpoint(path, {"vertex": V, "context": C}, step=7)
    return path, V, C


def test_store_checkpoint_roundtrip_bitwise(trained_ckpt):
    """Train a few steps -> save -> reload via the store: tables must come
    back BITWISE (bf16 included — the npz void-dtype fix), and the
    NodePartition row layout must land each shard's rows on its device."""
    path, V, C = trained_ckpt
    assert V.dtype == np.asarray(jnp.zeros(0, jnp.bfloat16)).dtype

    arrays, step = load_arrays(path)
    assert step == 7 and arrays["vertex"].dtype == V.dtype

    dev = jax.devices()[0]
    for table, ref_arr in (("vertex", V), ("context", C)):
        store = ShardedEmbeddingStore.load(path, table=table,
                                           devices=[dev, dev], block_n=64)
        assert store.step == 7
        # bitwise: the served host table and the device shards
        np.testing.assert_array_equal(
            store.host_table.view(np.uint16), ref_arr.view(np.uint16))
        rows = store.part.padded_rows_per_shard
        padded = store.part.pad_table(ref_arr)
        for s, shard in enumerate(store.shards):
            assert shard.devices() == {store.devices[s]}
            got = np.asarray(shard)[:rows]        # drop block_n pad rows
            np.testing.assert_array_equal(
                got.view(np.uint16),
                padded[s * rows:(s + 1) * rows].view(np.uint16))


def test_store_query_from_trained_checkpoint(trained_ckpt):
    """The acceptance path: real (non-integer) trained embeddings, Pallas
    kernel vs numpy oracle at k in {1, 10, 100}."""
    path, _, _ = trained_ckpt
    store = ShardedEmbeddingStore.load(path, block_n=64)
    rng = np.random.default_rng(5)
    q = store.host_table[rng.integers(0, store.num_nodes, 8)].astype(
        np.float32)
    for k in (1, 10, 100):
        rv, ri = store.oracle_topk(q, k)
        v, i = store.topk(q, k, impl="pallas")
        np.testing.assert_array_equal(i, ri)


# ------------------------------------------------------------------ batcher
def test_batcher_concurrent_correctness():
    """Seeded load test: concurrent submitters each get exactly their own
    query's oracle row back, and coalescing actually happened."""
    tbl = np.asarray(_int_table(120, 16, seed=20))
    store = ShardedEmbeddingStore.from_array(tbl, block_n=32)
    pool = np.asarray(_int_table(40, 16, seed=21))
    rv, ri = store.oracle_topk(pool, 6)

    batcher = MicroBatcher(lambda q: store.topk(q, 6, impl="xla"),
                           dim=16, max_batch=16, window_ms=5.0,
                           pad_multiple=8)
    errors = []

    def client(seed):
        rng = np.random.default_rng(seed)
        for _ in range(12):
            j = int(rng.integers(0, 40))
            fut = batcher.submit(pool[j])
            time.sleep(float(rng.uniform(0, 0.002)))
            vals, ids = fut.result(timeout=60)
            if not (np.array_equal(ids, ri[j])
                    and np.array_equal(vals, rv[j])):
                errors.append((seed, j))

    threads = [threading.Thread(target=client, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batcher.close()
    assert not errors
    st = batcher.stats
    assert st.requests == 6 * 12
    assert st.batches < st.requests          # coalescing happened
    assert st.mean_batch > 1.0


def test_batcher_close_serves_backlog_and_rejects_new():
    tbl = np.asarray(_int_table(30, 8, seed=22))
    store = ShardedEmbeddingStore.from_array(tbl, block_n=16)
    batcher = MicroBatcher(lambda q: store.topk(q, 3, impl="xla"),
                           dim=8, max_batch=4, window_ms=50.0)
    futs = [batcher.submit(tbl[i]) for i in range(10)]
    batcher.close()                           # must drain, not drop
    for f in futs:
        vals, ids = f.result(timeout=10)
        assert ids.shape == (3,)
    with pytest.raises(RuntimeError):
        batcher.submit(tbl[0])


def test_batcher_fixed_batch_shape():
    """fixed_batch pads every backend call to exactly max_batch rows (one
    compiled shape), and per-request results are still correct."""
    tbl = np.asarray(_int_table(50, 8, seed=23))
    store = ShardedEmbeddingStore.from_array(tbl, block_n=16)
    seen = []

    def serve_fn(q):
        seen.append(q.shape)
        return store.topk(q, 4, impl="xla")

    batcher = MicroBatcher(serve_fn, dim=8, max_batch=16, window_ms=5.0,
                           fixed_batch=True)
    futs = [batcher.submit(tbl[i]) for i in range(11)]
    rv, ri = store.oracle_topk(tbl[:11], 4)
    for j, f in enumerate(futs):
        vals, ids = f.result(timeout=30)
        np.testing.assert_array_equal(ids, ri[j])
    batcher.close()
    assert all(s == (16, 8) for s in seen)


def test_batcher_propagates_backend_errors():
    def boom(q):
        raise ValueError("backend down")

    batcher = MicroBatcher(boom, dim=4, max_batch=4, window_ms=1.0)
    fut = batcher.submit(np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="backend down"):
        fut.result(timeout=10)
    with pytest.raises(ValueError):           # shape validation
        batcher.submit(np.zeros(3, np.float32))
    batcher.close()

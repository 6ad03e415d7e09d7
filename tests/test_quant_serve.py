"""Two-tier quantized retrieval (embed_serve.quant + topk_mips_quant) and
the VMEM-aware scan-tile planner.

Correctness strategy mirrors test_embed_serve.py: integer tables make every
f32 dot exact, so the int8 first pass is bitwise deterministic across the
Pallas kernel and the jnp path, and the rescored result must equal the
numpy oracle EXACTLY (recall 1.0 is asserted as array equality, which is
stronger). Continuous (trained-like) tables are covered via the seeded
normal tables the bench uses, gated through ``recall_at_k == 1.0`` at the
default overfetch — the acceptance criterion."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.embed_serve import (ShardedEmbeddingStore, overfetch_m,
                               recall_at_k, rescore_exact)
from repro.embed_serve import quant as qz
from repro.embed_serve import topk as tk
from repro.kernels import ref
from repro.launch import roofline

import topk_gate_cases as gate


def _int_table(n, d, seed=0, dtype=jnp.float32, lo=-4, hi=5):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(lo, hi, size=(n, d)),
                       dtype=jnp.float32).astype(dtype)


# -------------------------------------------------------- quantization
@pytest.mark.parametrize("seed,scale_mag", [(0, 1.0), (1, 1e-3), (2, 1e3)])
def test_quantize_roundtrip_bound(seed, scale_mag):
    """Property-style: for random rows at several magnitudes, the int8
    round-trip error is <= max|row| / 254 per element (the documented
    bound), values stay in the symmetric [-127, 127] range, and all-zero
    rows reconstruct exactly."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(0, scale_mag, size=(64, 48))
         * rng.uniform(0.01, 1, size=(64, 1))).astype(np.float32)
    x[7] = 0.0                                # all-zero row
    q, scale = qz.quantize_rows(x)
    assert q.dtype == np.int8
    assert int(np.abs(q).max()) <= qz.INT8_QMAX          # -128 never used
    deq = qz.dequantize_rows(q, scale)
    amax = np.max(np.abs(x), axis=1, keepdims=True)
    bound = amax / (2 * qz.INT8_QMAX) * (1 + 1e-6) + 1e-12
    assert np.all(np.abs(deq - x) <= bound)
    np.testing.assert_array_equal(deq[7], 0.0)           # zero row exact
    np.testing.assert_array_equal(q[7], 0)
    assert scale[7] == 1.0                               # and scale benign


def test_quantize_bf16_matches_f32_view():
    """bf16 tables quantize through their exact f32 values — the quant
    tier sees the same numbers the exact tier scores."""
    tbl = _int_table(33, 16, seed=3, dtype=jnp.bfloat16)
    q16, s16 = qz.quantize_rows(tbl)
    q32, s32 = qz.quantize_rows(np.asarray(tbl.astype(jnp.float32)))
    np.testing.assert_array_equal(q16, q32)
    np.testing.assert_array_equal(s16, s32)


def test_overfetch_m_clamps():
    assert qz.overfetch_m(10, 4.0, 10_000) == 40
    assert qz.overfetch_m(10, 4.0, 25) == 25      # shard smaller than m
    assert qz.overfetch_m(10, 1.0, 10_000) == 10  # never below k
    assert qz.overfetch_m(3, 2.5, 10_000) == 8    # ceil
    assert qz.overfetch_m(10, 4.0, 4) == 4        # degraded shard


# ------------------------------------------------- first-pass kernel
@pytest.mark.parametrize("dtype,N,Q,m", [
    (jnp.float32, 230, 17, 25),
    (jnp.bfloat16, 230, 17, 25),
    (jnp.float32, 130, 5, 40),        # odd N, m a big fraction of N
])
def test_topk_quant_kernel_matches_xla(dtype, N, Q, m):
    """Integer tables: the Pallas int8 first pass and the jnp path agree
    bitwise (same scores, same candidate ids, same tie-breaks)."""
    tbl = _int_table(N, 32, seed=1, dtype=dtype)
    q8, sc = qz.quantize_rows(tbl)
    q = _int_table(Q, 32, seed=2)
    kv, ki = tk.topk_mips_quant(jnp.asarray(q8), jnp.asarray(sc), q, m=m,
                                valid=N, block_q=8, block_n=64,
                                interpret=True)
    xv, xi = tk.topk_mips_quant_xla(jnp.asarray(q8), jnp.asarray(sc), q,
                                    m=m, valid=N)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(xi))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(xv))


@pytest.mark.parametrize("name", gate.CASES)
def test_topk_quant_merge_gate_is_exact(name):
    """The int8 pass shares the exact scan's gated body: at m=40 it returns
    bitwise what the jnp path does on the gate's edge cases and on calls
    with query blocks of zero rows alone; with unit scales, bitwise the
    numpy oracle's top-m of the int8 rows; and its counts match the row
    order."""
    m = 40
    c = gate.case(name, m)
    q8, sc = qz.quantize_rows(c["table"])
    q8, sc, q = jnp.asarray(q8), jnp.asarray(sc), jnp.asarray(c["queries"])
    kw = dict(m=m, valid=c["valid"], block_q=c["block_q"],
              block_n=c["block_n"], interpret=True)
    kv, ki = tk.topk_mips_quant(q8, sc, q, **kw)
    xv, xi = tk.topk_mips_quant_xla(q8, sc, q, m=m, valid=c["valid"])
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(xi))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(xv))
    _, _, groups = tk._launch_topk_scan(
        q8, sc.reshape(1, -1), q, k=m, valid=c["valid"],
        bq=min(c["block_q"], q.shape[0]), bn=c["block_n"], interpret=True)
    gate.check_merges(c, groups)
    uv, ui = tk.topk_mips_quant(q8, jnp.ones_like(sc), q, **kw)
    gate.check_oracle(c, uv, ui, table=np.asarray(q8))


def test_topk_quant_padded_rows_masked():
    """Rows >= valid can never surface from the int8 pass either, even
    when their zero rows would out-score real (negative) rows."""
    tbl = np.full((64, 8), -2.0, np.float32)
    q8, sc = qz.quantize_rows(tbl)
    q = jnp.asarray(np.ones((3, 8), np.float32))
    _, i = tk.topk_mips_quant(jnp.asarray(q8), jnp.asarray(sc), q, m=12,
                              valid=40, block_q=4, block_n=16,
                              interpret=True)
    got = np.asarray(i)
    assert got[got != tk.IDX_SENTINEL].max() < 40


# ------------------------------------------------- two-tier == oracle
@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_two_tier_matches_oracle_exactly(k, dtype, impl):
    """The acceptance criterion: quant first pass + exact rescore equals
    topk_mips_ref EXACTLY at the default overfetch for k in {1, 10, 100},
    across dtypes and an odd (non-tile-multiple) N."""
    N, d, Q = 317, 32, 9                      # odd N; k=100 -> m=317 (all)
    tbl = _int_table(N, d, seed=6, dtype=dtype)
    q8, sc = qz.quantize_rows(tbl)
    q = _int_table(Q, d, seed=7)
    rv, ri = ref.topk_mips_ref(np.asarray(tbl), np.asarray(q), k)
    v, i = qz.topk_mips_quant_rescored(
        tbl, jnp.asarray(q8), jnp.asarray(sc), q, k=k, valid=N,
        block_q=8, block_n=64, impl=impl, interpret=True)
    np.testing.assert_array_equal(np.asarray(i), ri)
    np.testing.assert_array_equal(np.asarray(v), rv)


def test_rescore_handles_sentinels_and_reranks():
    """Tier two must (a) re-rank candidates the quantized scores ordered
    wrongly and (b) keep sentinel slots losing (degraded shards)."""
    tbl = jnp.asarray(np.diag([1.0, 2.0, 3.0, 4.0]).astype(np.float32))
    q = jnp.asarray(np.ones((1, 4), np.float32))
    # candidates deliberately in the wrong order + sentinel padding
    cand = jnp.asarray(
        np.array([[0, 2, 3, 1, tk.IDX_SENTINEL]], np.int32))
    v, i = rescore_exact(tbl, q, cand, k=3, gather="xla")
    np.testing.assert_array_equal(np.asarray(i), [[3, 2, 1]])
    np.testing.assert_array_equal(np.asarray(v), [[4.0, 3.0, 2.0]])
    # pallas gather path, same answer
    v2, i2 = rescore_exact(tbl, q, cand, k=3, gather="pallas",
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))


def test_two_tier_continuous_table_recall():
    """Continuous (trained-like) normal table, the bench's data shape:
    recall@k == 1.0 at the default overfetch against the oracle."""
    rng = np.random.default_rng(11)
    N, d, k = 2048, 64, 10
    tbl = rng.normal(0, 0.1, size=(N, d)).astype(np.float32)
    store = ShardedEmbeddingStore.from_array(tbl, quant="int8")
    q = tbl[rng.integers(0, N, size=16)]
    rv, ri = store.oracle_topk(q, k)
    v, i = store.topk(q, k, impl="quant")
    assert recall_at_k(i, ri, got_vals=store.score_ids(q, i),
                       oracle_vals=rv) == 1.0


# ----------------------------------------------------------- store tier
@pytest.mark.parametrize("impl", ["quant", "quant_pallas", "quant_xla"])
def test_store_quant_multi_shard(impl):
    """Two shards: int8 fan-out + rescore + global-id merge equal the
    oracle over the unsharded table."""
    dev = jax.devices()[0]
    tbl = np.asarray(_int_table(143, 16, seed=10))
    store = ShardedEmbeddingStore.from_array(tbl, devices=[dev, dev],
                                             block_n=32, quant="int8")
    q = np.asarray(_int_table(6, 16, seed=11))
    rv, ri = store.oracle_topk(q, 9)
    v, i = store.topk(q, 9, impl=impl)
    np.testing.assert_array_equal(i, ri)
    np.testing.assert_array_equal(v, rv)


@pytest.mark.parametrize("impl", ["quant_pallas", "quant_xla"])
def test_store_quant_degraded_shards(impl):
    """Shards with fewer valid rows than k (and an empty tail shard)
    through the quant path: m clamps to the shard, sentinels keep losing
    the merge, result still equals the oracle."""
    dev = jax.devices()[0]
    tbl = np.asarray(_int_table(9, 8, seed=30))
    store = ShardedEmbeddingStore.from_array(tbl, devices=[dev] * 4,
                                             block_n=16, quant="int8")
    assert store.valid == (3, 3, 3, 0)        # every live shard < k rows
    q = np.asarray(_int_table(4, 8, seed=31))
    rv, ri = store.oracle_topk(q, 5)
    v, i = store.topk(q, 5, impl=impl)
    np.testing.assert_array_equal(i, ri)
    np.testing.assert_array_equal(v, rv)


def test_store_quant_tier_required():
    tbl = np.asarray(_int_table(30, 8, seed=32))
    store = ShardedEmbeddingStore.from_array(tbl)
    assert store.qshards is None and store.quant is None
    with pytest.raises(RuntimeError, match="no quantized tier"):
        store.topk(np.zeros((2, 8), np.float32), 3, impl="quant")
    with pytest.raises(ValueError, match="unknown quant tier"):
        ShardedEmbeddingStore.from_array(tbl, quant="int4")


def test_store_quant_overfetch_override():
    """overfetch=<all rows> forces an exhaustive-exact first pass — the
    query-time override knob works end to end."""
    tbl = np.asarray(_int_table(60, 8, seed=33))
    store = ShardedEmbeddingStore.from_array(tbl, quant="int8",
                                             overfetch=1.0)
    q = np.asarray(_int_table(3, 8, seed=34))
    rv, ri = store.oracle_topk(q, 4)
    v, i = store.topk(q, 4, impl="quant_xla", overfetch=60.0)
    np.testing.assert_array_equal(i, ri)
    np.testing.assert_array_equal(v, rv)


# -------------------------------------------------------------- planner
def test_choose_block_n_respects_vmem_budget():
    """At shapes whose (2*bn, d) double-buffer would bust the planner
    budget (below the scoped VMEM limit every scan launch requests) the
    planner shrinks the tile until the modeled working set fits; small
    shapes keep the cap."""
    budget = roofline.VMEM_BUDGET_BYTES
    assert budget < roofline.VMEM_LIMIT_BYTES
    for d, dtype in [(4096, jnp.float32), (8192, jnp.float32),
                     (8192, jnp.bfloat16)]:
        bn = tk.choose_block_n(d, dtype)
        assert tk.topk_scan_vmem_bytes(bn, d, dtype) <= budget, (d, bn)
        assert bn >= 8
    # the capped 512-row tile would NOT fit at d=8192 f32
    assert tk.topk_scan_vmem_bytes(512, 8192, jnp.float32) > budget
    # small shapes: the cap, not the budget, binds
    assert tk.choose_block_n(64, jnp.float32) == 512
    # int8 tiles are 4x denser, so the planner can afford bigger tiles
    assert (tk.choose_block_n(8192, jnp.int8)
            >= tk.choose_block_n(8192, jnp.float32))
    # the model counts lane padding: a k=10 list costs a full 128 lanes
    assert (tk.topk_scan_vmem_bytes(256, 128, jnp.float32, k=10)
            == tk.topk_scan_vmem_bytes(256, 128, jnp.float32, k=128))
    # d so large the double-buffered (bq, d) query block alone busts the
    # budget: the planner bottoms out at the sublane floor (the tile is
    # no longer what busts VMEM — shrinking block_q is the caller's knob)
    assert tk.choose_block_n(65536, jnp.int8) == 8


def test_choose_block_n_default_paths_are_exact():
    """block_n=None end to end: the planner-sized exact kernel, quant
    kernel, and store all still match the oracle."""
    tbl = _int_table(300, 24, seed=40)
    q = _int_table(7, 24, seed=41)
    rv, ri = ref.topk_mips_ref(np.asarray(tbl), np.asarray(q), 6)
    v, i = tk.topk_mips(tbl, q, k=6, valid=300, interpret=True)
    np.testing.assert_array_equal(np.asarray(i), ri)
    q8, sc = qz.quantize_rows(tbl)
    v2, i2 = qz.topk_mips_quant_rescored(
        tbl, jnp.asarray(q8), jnp.asarray(sc), q, k=6, valid=300,
        impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(i2), ri)
    store = ShardedEmbeddingStore.from_array(np.asarray(tbl), quant="int8")
    # the planner's tile, clamped to the shard's rows (tiny table here)
    assert store.block_n == min(tk.choose_block_n(24, np.float32),
                                store.part.padded_rows_per_shard)
    v3, i3 = store.topk(np.asarray(q), 6, impl="quant_pallas")
    np.testing.assert_array_equal(i3, ri)


# ------------------------------------------------- device quantizer
def _ragged_table(n, d, dtype, seed):
    """Seeded normal rows at mixed magnitudes, an all-zero row, and a row
    of exact halves: max 127 puts its scale at 1.0, so 2.5 and -3.5 test
    the round-half-to-even rule."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((n, d))
         * rng.uniform(0.01, 10, size=(n, 1))).astype(np.float32)
    x[5] = 0.0
    x[11] = 0.5
    x[11, :4] = [127.0, 2.5, -3.5, 1.5]
    return jnp.asarray(x).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block_rows", [64, 1 << 16])
def test_quantize_device_equals_numpy_bitwise(dtype, block_rows):
    """The store's block quantizer gives quantize_rows' bits: 301 rows are
    a multiple of neither the 64-row block nor a 64- or 512-row scan
    tile (the last block overlaps the one before), and a block larger
    than the table is cut to it."""
    tbl = _ragged_table(301, 48, dtype, seed=50)
    q8, sc = qz.quantize_device(tbl, block_rows=block_rows)
    want_q, want_s = qz.quantize_rows(tbl)
    assert q8.dtype == jnp.int8 and sc.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(q8), want_q)
    np.testing.assert_array_equal(np.asarray(sc).view(np.uint32),
                                  want_s.view(np.uint32))
    assert list(np.asarray(q8)[11, 1:4]) == [2, -4, 2]    # half to even
    assert float(sc[5]) == 1.0 and not np.asarray(q8)[5].any()


def test_div_exact_equals_numpy_division_bitwise():
    """The quantizer's division is numpy's correctly rounded f32 quotient
    without the device's divide: random quotients over 20 decades, ones
    within an ulp of a half-integer (where rounding to int8 turns on the
    last bit), max/127 as the scales take it, exact quotients, zeros,
    negatives, and subnormal or out-of-range cases (the plain quotient)."""
    rng = np.random.default_rng(60)
    n = 1 << 16
    a = (rng.standard_normal(n) * 10.0 ** rng.uniform(-10, 10, n))
    b = np.abs(rng.standard_normal(n)) * 10.0 ** rng.uniform(-10, 10, n)
    s = np.abs(rng.standard_normal(n)) * 0.003 + 1e-4
    k = rng.integers(-127, 127, n) + 0.5
    parts = [(a, b), ((k * s).astype(np.float32), s),
             (np.abs(a), np.full(n, 127.0)),
             (rng.integers(1, 999, n) * k.round(), k.round() + 200.0),
             (np.array([0.0, -0.0, -6.0, 3e38]),
              np.array([3.0, 3.0, 3.0, 1e-3]))]
    div = jax.jit(qz._div_exact)
    with np.errstate(over="ignore"):
        for a, b in parts:
            a, b = np.float32(a), np.float32(b)
            np.testing.assert_array_equal(
                np.asarray(div(a, b)).view(np.uint32),
                (a / b).view(np.uint32))
    # subnormal dividend or quotient: the device's own quotient
    a, b = np.float32([1e-40, 1e-30]), np.float32([7.0, 1e10])
    np.testing.assert_array_equal(np.asarray(div(a, b)),
                                  np.asarray(jax.jit(jnp.divide)(a, b)))


def _bf16_int_table(n, d, seed):
    """Integer rows in [-100, 100]: exact in bf16, so every f32 score is
    exact and the two-tier answer must equal the oracle bit for bit."""
    return _int_table(n, d, seed=seed, dtype=jnp.bfloat16, lo=-100, hi=101)


@pytest.mark.parametrize("impl", ["quant_xla", "quant_pallas"])
@pytest.mark.parametrize("built", ["from_array", "device_shards"])
def test_store_int8_path_on_bf16_equals_oracle(impl, built):
    """The store's int8 path over a bf16 table of ragged row count (301
    rows in 64-row tiles, two shards): from a host table, and from shards
    already on the device as the serving cell hands them over; both
    quantize on the device and equal kernels.ref.topk_mips_ref."""
    from repro.core.partition import NodePartition
    dev = jax.devices()[0]
    tbl = _bf16_int_table(301, 16, seed=51)
    host = np.asarray(tbl)
    if built == "from_array":
        store = ShardedEmbeddingStore.from_array(host, devices=[dev, dev],
                                                 block_n=64, quant="int8")
    else:
        part = NodePartition(301, dims=(2,), subparts=1)
        rows = part.padded_rows_per_shard
        padded = np.asarray(part.pad_table(host))
        shards = [jax.device_put(np.concatenate(
            [padded[s * rows:(s + 1) * rows],
             np.zeros((192 - rows, 16), host.dtype)]), dev) for s in (0, 1)]
        store = ShardedEmbeddingStore(
            shards, part, [rows, 301 - rows], [dev, dev], host_table=host,
            block_n=64, quant="int8")
    for sh, (q8, sc) in zip(store.shards, store.qshards):
        want_q, want_s = qz.quantize_rows(sh)
        np.testing.assert_array_equal(np.asarray(q8), want_q)
        np.testing.assert_array_equal(np.asarray(sc), want_s)
    q = np.asarray(_int_table(9, 16, seed=52, lo=-9, hi=10))
    rv, ri = ref.topk_mips_ref(host.astype(np.float32), q, 10)
    v, i = store.topk(q, 10, impl=impl)
    np.testing.assert_array_equal(i, ri)
    np.testing.assert_array_equal(v, rv)


@pytest.mark.parametrize("name", gate.CASES)
def test_topk_quant_counted_matches_and_counts(name):
    """The counted int8 pass returns topk_mips_quant's ids and scores, and
    counts consistent with the gate and the zero-block skip
    (``topk_gate_cases.check_merges``)."""
    m = 40
    c = gate.case(name, m)
    q8, sc = qz.quantize_rows(c["table"])
    q8, sc, q = jnp.asarray(q8), jnp.asarray(sc), jnp.asarray(c["queries"])
    kw = dict(m=m, valid=c["valid"], block_q=c["block_q"],
              block_n=c["block_n"], interpret=True)
    cv, ci, groups = tk.topk_mips_quant_counted(q8, sc, q, **kw)
    v, i = tk.topk_mips_quant(q8, sc, q, **kw)
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(i))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(v))
    gate.check_merges(c, groups)


@pytest.mark.parametrize("impl,counted", [("quant_pallas", True),
                                          ("quant_xla", False)])
def test_store_int8_counters_and_quantize_span(impl, counted):
    """The store's int8 path adds its gate counts to serve.quant.* (never
    to the exact scan's serve.topk.*) and the rows its rescore gathers,
    padded query rows included, to serve.quant.candidates; the load
    writes one embed_store.quantize span a shard."""
    from repro import obs
    c = gate.case("falling", 10)
    dev = jax.devices()[0]
    reg = obs.enable()
    tracer = obs.Tracer()
    obs.set_tracer(tracer)
    try:
        store = ShardedEmbeddingStore.from_array(
            c["table"], devices=[dev, dev], block_n=gate.BLOCK_N,
            quant="int8")
        store.topk(c["queries"], 10, impl=impl)
        counters = reg.snapshot()["counters"]
        spans = [e for e in tracer.to_json()["traceEvents"]
                 if e.get("name") == "embed_store.quantize"]
    finally:
        obs.set_tracer(None)
        obs.disable()
    assert [e["args"]["shard"] for e in spans] == [0, 1]
    Q = c["queries"].shape[0]
    assert counters["serve.quant.candidates"] == 2 * Q * 40
    assert not any(n.startswith("serve.topk.") for n in counters)
    if counted:
        # two shards x row groups x 2 tiles scored; falling rows: each
        # group merges its shard's first tile alone; one live block a shard
        assert counters["serve.quant.tile_groups"] == 2 * (Q // 8) * 2
        assert counters["serve.quant.merge_groups"] == 2 * (Q // 8)
        assert counters["serve.quant.query_blocks"] == 2
        assert counters["serve.quant.zero_blocks"] == 0
    else:
        assert "serve.quant.tile_groups" not in counters

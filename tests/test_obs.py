"""Telemetry layer (repro.obs): registry math, tracer, and integration.

Covers the observability PR's acceptance points: histogram percentiles
against the numpy inverted-CDF oracle (including past the reservoir cap),
thread-safety of counters and the tracer under a hammer, the disabled-mode
zero-allocation guarantee (the fault_point design rule), the pipeline
timing fixes (sync builds record, out-of-prefetch-order consumption no
longer loses timings), the metrics sink, and diagnostics.json on ANY
fatal launcher exception — metrics snapshot included.
"""
import json
import math
import os
import subprocess
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.obs import Histogram, Registry, Tracer
from repro.obs import trace as obs_trace


@pytest.fixture(autouse=True)
def _reset_obs():
    """Telemetry is process-global state; never leak it across tests."""
    obs.disable()
    obs.set_tracer(None)
    yield
    obs.disable()
    obs.set_tracer(None)


# ---------------------------------------------------------------------------
# histogram: exact percentiles vs the numpy oracle, reservoir behaviour
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 2, 3, 17, 100, 999, 4096])
def test_histogram_percentiles_match_numpy_inverted_cdf(n):
    rng = np.random.default_rng(n)
    vals = rng.normal(size=n)
    h = Histogram(cap=4096)
    for v in vals:
        h.observe(v)
    for q in (0, 1, 50, 95, 99, 100):
        assert h.percentile(q) == np.percentile(vals, q,
                                                method="inverted_cdf")
    s = h.summary()
    assert s["count"] == n and s["exact"]
    assert s["min"] == vals.min() and s["max"] == vals.max()
    assert s["sum"] == pytest.approx(float(vals.sum()), rel=1e-12)
    assert s["mean"] == pytest.approx(float(vals.mean()), rel=1e-12)
    assert (s["p50"], s["p95"], s["p99"]) == tuple(
        np.percentile(vals, q, method="inverted_cdf") for q in (50, 95, 99))


def test_histogram_reservoir_bounded_and_deterministic():
    n, cap = 20_000, 256
    rng = np.random.default_rng(7)
    vals = rng.random(n)
    h1, h2 = Histogram(cap=cap), Histogram(cap=cap)
    for v in vals:
        h1.observe(v)
        h2.observe(v)
    # bounded memory, exact moments, sampled percentiles
    assert len(h1._values) == cap
    s = h1.summary()
    assert s["count"] == n and not s["exact"]
    assert s["min"] == vals.min() and s["max"] == vals.max()
    assert s["sum"] == pytest.approx(float(vals.sum()), rel=1e-9)
    assert abs(s["p50"] - 0.5) < 0.12       # uniform(0,1) median via sample
    # deterministic per-histogram RNG: identical streams, identical summary
    assert s == h2.summary()


def test_histogram_empty_summary():
    h = Histogram()
    s = h.summary()
    assert s["count"] == 0 and s["p50"] is None and s["min"] is None
    assert math.isnan(h.percentile(50))


def test_registry_kind_collision_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# thread hammer: counters and spans under contention stay exact
# ---------------------------------------------------------------------------
def test_counter_hammer_multithreaded_is_exact():
    reg = obs.enable()
    threads, per = 8, 5_000

    def work():
        for _ in range(per):
            obs.counter_add("hammer")
            obs.counter_add("hammer.by3", 3)
            obs.observe("hammer.hist", 1.0)

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("hammer").value == threads * per
    assert reg.counter("hammer.by3").value == 3 * threads * per
    assert reg.histogram("hammer.hist").count == threads * per


def test_tracer_span_hammer_multithreaded():
    tr = Tracer()
    obs.set_tracer(tr)
    threads, per = 8, 250

    def work():
        for i in range(per):
            with obs_trace.span("unit", "train", {"i": i}):
                pass

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert tr.event_count() == threads * per
    assert tr.dropped == 0


def test_tracer_bounded_past_cap():
    tr = Tracer(max_events=10)
    for _ in range(50):
        tr.counter("tick", 1)
    assert tr.event_count() == 10
    assert tr.dropped == 40
    assert tr.to_json()["otherData"]["dropped_events"] == 40


# ---------------------------------------------------------------------------
# disabled mode: the fault_point rule — no allocation on the hot path
# ---------------------------------------------------------------------------
def test_disabled_span_is_shared_noop_singleton():
    assert obs_trace.span("a", "walk") is obs_trace.span("b", "serve")


def test_disabled_helpers_allocate_nothing():
    """With no registry/tracer installed, every helper must be one
    module-level None check: zero allocations attributed to repro.obs."""
    obs_dir = os.path.dirname(obs.__file__)

    def hot_loop():
        for _ in range(200):
            obs.counter_add("c")
            obs.counter_add("c", 5)
            obs.gauge_set("g", 1.0)
            obs.observe("h", 0.5)
            obs.trace_counter("tc", 3)
            with obs.span("i", "walk"):
                pass
            with obs_trace.span("s", "train"):
                pass

    hot_loop()                      # warm caches before measuring
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        hot_loop()
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = after.compare_to(before, "lineno")
    leaked = [s for s in stats
              if s.traceback[0].filename.startswith(obs_dir)
              and s.size_diff > 0]
    assert not leaked, [str(s) for s in leaked]


# ---------------------------------------------------------------------------
# profiler bridge: obs.span also writes into a recording jax.profiler trace
# ---------------------------------------------------------------------------
BATCHER_SPANS = ("batcher.collect", "batcher.stack", "batcher.backend",
                 "batcher.fanout")
STORE_SPANS = ("embed_store.topk", "embed_store.to_device",
               "embed_store.dispatch", "embed_store.fetch",
               "embed_store.merge")


def _profile(directory, body, names, window="test.window"):
    """Run ``body`` under a profiler session, inside a ``window``
    annotation; returns the trace's host events of those ``names`` and the
    window as [(name, start_ns, end_ns, stats)]."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(directory))
    try:
        with jax.profiler.TraceAnnotation(window):
            body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(directory), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name in names or e.name == window)
    return out


@pytest.mark.parametrize("shard_timeout_s", [None, 60.0],
                         ids=["all_shards", "per_shard_deadline"])
def test_serving_spans_land_in_the_profiler_trace(tmp_path, shard_timeout_s):
    """A store behind a batcher, traced by the profiler alone (no Tracer):
    every serving span is in the trace, inside the window, the store's
    inside a backend call, each batcher span with its batch's number."""
    import jax
    from repro.embed_serve import MicroBatcher, ShardedEmbeddingStore

    assert obs.tracer() is None
    rng = np.random.default_rng(0)
    table = rng.normal(size=(64, 8)).astype(np.float32)
    store = ShardedEmbeddingStore.from_array(
        table, devices=[jax.devices()[0]] * 2, shard_timeout_s=shard_timeout_s)
    store.topk(table[:4], 3, impl="xla")          # compile before the trace

    def body():
        with MicroBatcher(lambda q: store.topk(q, 3, impl="xla"), 8,
                          max_batch=4, window_ms=1.0) as b:
            for f in [b.submit(q) for q in table[:10]]:
                f.result(timeout=60)

    evs = _profile(tmp_path, body, BATCHER_SPANS + STORE_SPANS)
    (lo, hi), = [(s, e) for n, s, e, _ in evs if n == "test.window"]
    mine = [ev for ev in evs if ev[0] != "test.window"]
    assert {n for n, *_ in mine} == set(BATCHER_SPANS + STORE_SPANS)
    assert all(lo <= s <= e <= hi for _, s, e, _ in mine)
    backends = [(s, e) for n, s, e, _ in mine if n == "batcher.backend"]
    for n, s, e, _ in mine:
        if n in STORE_SPANS:
            assert any(bs <= s and e <= be for bs, be in backends), n
    by_batch = {}
    for n, _, _, st in mine:
        if n in BATCHER_SPANS:
            by_batch.setdefault(st["batch"], set()).add(n)
    assert len(by_batch) >= 3                        # 10 queries, 4 a batch
    assert all(names == set(BATCHER_SPANS) for names in by_batch.values())
    if shard_timeout_s is not None:                  # one fetch per shard
        shards = [st["shard"] for n, _, _, st in mine
                  if n == "embed_store.fetch"]
        assert sorted(set(shards)) == [0, 1]


def test_one_span_lands_in_both_sinks(tmp_path):
    tr = Tracer()
    obs.set_tracer(tr)

    def body():
        with obs.span("both.sinks", "serve", {"batch": 7}):
            time.sleep(0.001)

    evs = _profile(tmp_path, body, ("both.sinks",))
    assert [st for n, _, _, st in evs if n == "both.sinks"] == [{"batch": 7}]
    xs = [e for e in tr.to_json()["traceEvents"]
          if e["ph"] == "X" and e["name"] == "both.sinks"]
    assert len(xs) == 1 and xs[0]["args"] == {"batch": 7}


def test_span_enters_a_trace_annotation_only_while_the_profiler_records(
        tmp_path):
    import jax
    assert obs.span("a", "serve") is obs_trace._NOOP
    seen = []

    def body():
        s = obs.span("b", "serve", {"batch": 1})
        assert s is not obs_trace._NOOP
        with s:
            seen.append(s._me)

    _profile(tmp_path, body, ())
    assert isinstance(seen[0], jax.profiler.TraceAnnotation)
    assert obs.span("c", "serve") is obs_trace._NOOP


def test_importing_obs_imports_no_jax():
    """Walk producers import obs: it must load no JAX (and so start no
    backend), and a span there stays the shared no-op."""
    code = ("import sys\n"
            "from repro import obs\n"
            "from repro.obs import trace\n"
            "assert obs.span('x', 'walk') is trace._NOOP\n"
            "assert not any(m == 'jax' or m.startswith('jax.')\n"
            "               for m in sys.modules), 'obs imports jax'\n")
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, PYTHONPATH=os.path.join(
                           os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))), "src")),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# trace JSON shape: Perfetto-loadable, named ordered tracks
# ---------------------------------------------------------------------------
def test_trace_json_shape_and_roundtrip(tmp_path):
    tr = Tracer()
    obs.set_tracer(tr)
    with obs_trace.span("build", "build", {"episode": 0}):
        time.sleep(0.001)
    tr.add_span("recv_episode", "host:w1", 10.0, 250.0, {"chunks": 3})
    obs_trace.trace_counter("store.resident", 2)
    obs.set_tracer(None)

    j = tr.to_json()
    path = str(tmp_path / "trace.json")
    tr.save(path)
    with open(path) as f:
        assert json.load(f) == j

    evs = j["traceEvents"]
    names = {e["args"]["name"]: e["tid"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    # canonical lanes pinned in fixed order, dynamic lane appended after
    for i, track in enumerate(obs_trace.PIPELINE_TRACKS):
        assert names[track] == i + 1
    assert names["host:w1"] > len(obs_trace.PIPELINE_TRACKS)
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"build", "recv_episode"}
    for e in xs:
        assert e["pid"] == 1 and e["ts"] >= 0 and e["dur"] >= 0
    counters = [e for e in evs if e["ph"] == "C"]
    assert counters and counters[0]["args"]["value"] == 2


# ---------------------------------------------------------------------------
# registry sources: collector surfaces fold into one snapshot
# ---------------------------------------------------------------------------
def test_snapshot_sources_poll_and_capture_errors():
    reg = obs.enable()
    obs.counter_add("a.frames", 4)
    obs.gauge_set("a.depth", 7)
    obs.register_source("good", lambda: {"leases": 2})
    obs.register_source("bad", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["counters"]["a.frames"] == 4
    assert snap["gauges"]["a.depth"] == 7
    assert snap["sources"]["good"] == {"leases": 2}
    assert "ZeroDivisionError" in snap["sources"]["bad"]["error"]
    obs.unregister_source("bad")
    assert "bad" not in reg.snapshot()["sources"]
    json.dumps(snap, default=str)       # the whole snapshot serializes


# ---------------------------------------------------------------------------
# pipeline timing fixes (satellite: sync builds + out-of-order retention)
# ---------------------------------------------------------------------------
def _mk_pipe(store_depth=None, **kw):
    from repro.core import EpisodePipeline
    from repro.core.partition import NodePartition
    from repro.walk import MemorySampleStore

    rng = np.random.default_rng(0)
    store = MemorySampleStore() if store_depth is None else \
        MemorySampleStore(depth=store_depth)
    for ep in range(4):
        store.put(0, ep, rng.integers(0, 100, size=(60, 2)).astype(np.int32))
    part = NodePartition(100, dims=(1,), subparts=1)
    return EpisodePipeline(store, part, pad_multiple=8, **kw)


def test_pipeline_sync_build_records_stage_timings():
    """An episode built on the prefetch-miss path (no prefetch() call) must
    record the same per-stage timings as a prefetched one — and the registry
    histograms must see them too."""
    reg = obs.enable()
    pipe = _mk_pipe(stage_fn=lambda eb: eb)
    try:
        pipe.get(0, 0)                        # never prefetched: sync build
        times = pipe.pop_times(0, 0)
        assert set(times) == {"walk_wait_s", "build_s", "stage_s"}
        assert all(v >= 0 for v in times.values())
        hists = reg.snapshot()["histograms"]
        for name in ("pipeline.walk_wait_s", "pipeline.build_s",
                     "pipeline.stage_s"):
            assert hists[name]["count"] == 1
    finally:
        pipe.close()


def test_pipeline_out_of_order_consumption_keeps_timings():
    """Consuming prefetched episodes out of order used to sweep the timings
    of every not-yet-popped episode; now they survive until popped (or
    until the bounded-cap eviction, far away)."""
    pipe = _mk_pipe(depth=4)
    try:
        pipe.prefetch_window(0, 0, 3)
        for ep in (0, 1, 2):
            pipe.get(0, ep)
        # pop AFTER all gets — the old liveness sweep deleted these
        for ep in (0, 1, 2):
            times = pipe.pop_times(0, ep)
            assert set(times) == {"walk_wait_s", "build_s"}, (ep, times)
        assert pipe.pop_times(0, 2) == {}     # pop is consume-once
    finally:
        pipe.close()


def test_pipeline_times_dict_is_bounded():
    pipe = _mk_pipe()
    try:
        for i in range(pipe._times_cap * 3):
            pipe._record((0, i), "build_s", 0.001)
        assert len(pipe._times) == pipe._times_cap
        assert pipe.pop_times(0, 0) == {}                      # oldest gone
        assert pipe.pop_times(0, pipe._times_cap * 3 - 1)      # newest kept
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# store + batcher integration: counters/gauges/histograms flow when enabled
# ---------------------------------------------------------------------------
def test_store_metrics_flow():
    from repro.walk import MemorySampleStore

    reg = obs.enable()
    store = MemorySampleStore()
    pairs = np.zeros((5, 2), np.int32)
    store.put(0, 0, pairs)
    store.get(0, 0)
    store.drop(0, 0)
    snap = reg.snapshot()
    assert snap["counters"]["store.puts"] == 1
    assert snap["counters"]["store.gets"] == 1
    assert snap["gauges"]["store.resident"] == 0
    assert snap["histograms"]["store.put_wait_s"]["count"] == 1
    assert snap["histograms"]["store.get_blocked_s"]["count"] == 1


def test_batcher_metrics_and_source_lifecycle():
    from repro.embed_serve import MicroBatcher

    reg = obs.enable()

    def serve_fn(q):
        return q.sum(axis=1, keepdims=True), \
            np.zeros((q.shape[0], 1), np.int64)

    b = MicroBatcher(serve_fn, dim=4, max_batch=8, window_ms=1.0)
    try:
        assert "serve.batcher" in reg.snapshot()["sources"]
        futs = [b.submit(np.ones(4, np.float32)) for _ in range(5)]
        for f in futs:
            f.result(timeout=30)
    finally:
        b.close()
    snap = reg.snapshot()
    assert "serve.batcher" not in snap["sources"]   # unregistered at close
    assert snap["histograms"]["serve.request_s"]["count"] == 5
    assert "serve.queue_depth" in snap["gauges"]


# ---------------------------------------------------------------------------
# metrics sink: periodic jsonl + final summary
# ---------------------------------------------------------------------------
def test_metrics_writer_jsonl_and_summary(tmp_path):
    reg = obs.enable()
    obs.counter_add("sink.test", 42)
    w = obs.MetricsWriter(reg, str(tmp_path), interval_s=0.05)
    time.sleep(0.25)
    w.close()
    assert w.last_error is None
    with open(w.path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert len(lines) >= 2                  # periodic lines + the close line
    for snap in lines:
        assert {"ts", "elapsed_s", "counters", "gauges", "histograms",
                "sources"} <= set(snap)
        assert snap["counters"]["sink.test"] == 42
    with open(w.summary_path) as f:
        summary = json.load(f)
    assert summary["lines_written"] == len(lines)
    assert summary["counters"]["sink.test"] == 42


# ---------------------------------------------------------------------------
# launcher: ANY fatal exception dumps diagnostics.json with the metrics snap
# ---------------------------------------------------------------------------
_TRAIN_ARGS = ["--arch", "tencent-embedding", "--nodes", "240", "--dim", "16",
               "--epochs", "2", "--episodes", "3", "--subparts", "2",
               "--minibatch", "32", "--negatives", "4", "--neg-pool", "256",
               "--walk-workers", "2", "--seed", "3"]


def test_train_dumps_diagnostics_with_metrics_on_any_fatal(tmp_path):
    """A crash that is neither StoreStalled nor TransportError (here an
    InjectedFault) must still leave OUT_DIR/diagnostics.json — with the
    telemetry registry folded in when --metrics-dir enabled it."""
    from repro.launch.train import main as train_main
    from repro.runtime import InjectedFault

    out = str(tmp_path / "run")
    mdir = str(tmp_path / "metrics")
    with pytest.raises(InjectedFault):
        train_main(_TRAIN_ARGS + [
            "--out-dir", out, "--metrics-dir", mdir,
            "--metrics-interval-s", "0.2",
            "--inject", "train.episode:crash:key=0/1"])
    with open(os.path.join(out, "diagnostics.json")) as f:
        diag = json.load(f)
    assert diag["error"] == "InjectedFault"
    m = diag["metrics"]
    assert m["counters"]["walk.chunks"] >= 1
    assert m["counters"]["train.episodes"] == 1      # died before (0, 1)
    assert m["histograms"]["pipeline.build_s"]["count"] >= 1
    # the sink closed cleanly on the failure path too
    assert os.path.exists(os.path.join(mdir, "metrics_summary.json"))
    # the launcher's finally tore the global registry down
    assert obs.active() is None

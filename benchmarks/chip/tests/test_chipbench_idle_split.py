"""The split of the device's idle time by the serving program's spans
(``idle_store_share.serve``, ``idle_batcher_share.serve``,
``idle_untraced_share.serve``), on synthetic traces."""
import pytest

from chipbench_helpers import HARNESS  # noqa: F401 -- the harness's path

import common
import devtrace

MS = 1_000_000   # ns
PARTS = ("store", "batcher", "untraced")


def _trace(ops, host=(), window=(0, 100 * MS), devices=1):
    return devtrace.Trace(
        devices={f"/device:TPU:{d}": {devtrace.OPS_LINE: list(ops),
                                      devtrace.MODULES_LINE: []}
                 for d in range(devices)},
        host=[(devtrace.WINDOW, *window), *host], window=window)


def _read(tr):
    run = {"kind": "serve", "trace": tr}
    return {p: common.load_module("metrics",
                                  f"idle_{p}_share.serve").read(run)
            for p in PARTS}


# one cycle of the serving loop in a 100 ms window: the scan runs 10-70 ms
# inside the store's fetch; the device idles 0-10 (store and batcher spans
# in turn, the first two cut by the window's start), 70-75 (fetch and merge
# after the scan), 75-90 (backend, fan-out, then collect), 90-95 (no span)
# and 95-100 (the next collect, cut by the window's end)
CYCLE_OPS = [("%topk_mips.1", 10 * MS, 70 * MS)]
CYCLE_HOST = [
    ("embed_store.topk", -20 * MS, 4 * MS),        # across the window's start
    ("embed_store.fetch", -10 * MS, 3 * MS),
    ("batcher.backend", -25 * MS, 5 * MS),
    ("batcher.stack", 5 * MS, 8 * MS),             # 4-5: backend alone
    ("batcher.backend", 8 * MS, 76 * MS),          # 8-9: backend alone
    ("embed_store.topk", 9 * MS, 75 * MS),
    ("embed_store.to_device", 9 * MS, 9.5 * MS),
    ("embed_store.fetch", 10 * MS, 73 * MS),
    ("embed_store.merge", 73 * MS, 75 * MS),
    ("batcher.fanout", 76 * MS, 85 * MS),
    ("batcher.collect", 85 * MS, 90 * MS),
    ("bench.store_topk", 8.5 * MS, 75.5 * MS),     # the benchmark's, not read
    ("batcher.collect", 95 * MS, 130 * MS),        # across the window's end
]


def test_idle_time_is_split_by_the_span_open_over_it():
    tr = _trace(CYCLE_OPS, CYCLE_HOST)
    got = _read(tr)
    # store: 0-4 and 9-10 before the scan, 70-75 after it
    assert got["store"] == pytest.approx(10.0)
    # batcher alone: 4-9 (backend, stack, backend) and 75-90, and 95-100
    # up to the window's edge
    assert got["batcher"] == pytest.approx(25.0)
    # no program span: 90-95
    assert got["untraced"] == pytest.approx(5.0)
    assert sum(got.values()) == pytest.approx(devtrace.idle_share(tr),
                                              abs=1e-9)


@pytest.mark.parametrize("devices", [1, 4])
def test_three_parts_add_up_to_the_idle_share(devices):
    ops = [("a", -5 * MS, 12 * MS), ("b", 30 * MS, 44 * MS),
           ("c", 41 * MS, 66 * MS), ("d", 97 * MS, 140 * MS)]
    host = [("batcher.collect", 0, 20 * MS),
            ("embed_store.fetch", 15 * MS, 35 * MS),
            ("embed_store.topk", 60 * MS, 80 * MS),
            ("batcher.fanout", 70 * MS, 90 * MS)]
    tr = _trace(ops, host, devices=devices)
    got = _read(tr)
    # idle 12-30 and 66-97: store 15-30 and 66-80, batcher alone 12-15 and
    # 80-90, no span 90-97
    assert got == pytest.approx({"store": 15 + 14, "batcher": 3 + 10,
                                 "untraced": 7})
    assert sum(got.values()) == pytest.approx(devtrace.idle_share(tr),
                                              abs=1e-9)


def test_a_gap_across_the_window_edge_counts_inside_it_only():
    ops = [("k", 20 * MS, 80 * MS)]
    host = [("batcher.collect", -50 * MS, 20 * MS),
            ("embed_store.merge", 80 * MS, 150 * MS)]
    got = _read(_trace(ops, host))
    assert got == pytest.approx({"store": 20.0, "batcher": 20.0,
                                 "untraced": 0.0})


def test_a_trace_without_the_spans_reads_nothing():
    tr = _trace(CYCLE_OPS, [("bench.store_topk", 8 * MS, 76 * MS)])
    assert _read(tr) == dict.fromkeys(PARTS)
    store_only = [h for h in CYCLE_HOST if h[0].startswith("embed_store.")]
    got = _read(_trace(CYCLE_OPS, store_only))
    assert got["batcher"] is None and got["store"] == pytest.approx(10.0)
    assert got["untraced"] == pytest.approx(devtrace.idle_share(
        _trace(CYCLE_OPS)) - 10.0)
    train = {"kind": "train", "trace": _trace(CYCLE_OPS, CYCLE_HOST)}
    assert common.load_module(
        "metrics", "idle_store_share.serve").read(train) is None

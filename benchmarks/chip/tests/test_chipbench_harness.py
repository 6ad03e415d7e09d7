"""The harness's own arithmetic and plumbing, on the CPU: trace reduction,
algorithmic bytes, discovery by name, the names in BENCHMARK.json, and a
run that finds no TPU."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from chipbench_helpers import HARNESS, REPO, tiny_root

import common
import devtrace
import flops_bytes
import graphgen
import traffic

MS = 1_000_000   # ns


def _trace(ops, host=(), window=(0, 100 * MS)):
    return devtrace.Trace(
        devices={"/device:TPU:0": {devtrace.OPS_LINE: list(ops),
                                   devtrace.MODULES_LINE: []}},
        host=[(devtrace.WINDOW, *window), *host], window=window)


def test_busy_union_and_idle_share():
    ops = [("a", 0, 10 * MS), ("b", 5 * MS, 20 * MS), ("c", 50 * MS, 60 * MS),
           ("d", 90 * MS, 120 * MS)]            # d runs past the window
    tr = _trace(ops)
    assert devtrace.union_ns(ops) == 60 * MS
    assert devtrace.busy_s(tr) == pytest.approx(0.040)
    assert devtrace.idle_share(tr) == pytest.approx(60.0)


def test_kernel_time_per_name():
    ops = [("fusion.1", 0, 3), ("sgns_update_kernel", 3, 10),
           ("sgns_update_kernel", 12, 20), ("copy", 20, 21)]
    assert devtrace.time_ns(ops, r"sgns_update") == (15.0, 2)
    assert devtrace.time_ns(ops, r"topk_scan") == (0.0, 0)


def test_exposed_collective_time():
    ops = [("compute", 0, 10), ("collective-permute-start.1", 5, 15),
           ("compute", 12, 14), ("all-reduce.2", 30, 40)]
    # permute 5-15 overlaps compute 5-10 and 12-14: exposed 10-12 and 14-15;
    # the all-reduce is exposed whole
    assert devtrace.exposed_ns(ops) == 3 + 10


def test_breakdown_names_gaps_by_host_span():
    ops = [("k", 0, 10 * MS), ("k", 40 * MS, 100 * MS)]
    host = [("bench.feed_wait", 10 * MS, 40 * MS)]
    b = devtrace.breakdown(_trace(ops, host))
    assert b["device_ops"] == [["k", pytest.approx(0.07)]]
    assert b["idle_gaps"] == [["bench.feed_wait", pytest.approx(0.03)]]


def test_sgns_bytes_count_unique_rows_per_minibatch():
    # one cell, minibatch 4, 6 valid samples: minibatch 1 has vertex rows
    # {1, 2} and context rows {7, 8, 9}; minibatch 2 (2 valid + 2 padding)
    # has vertex rows {3} and context rows {7}
    blocks = np.array([[[1, 7], [1, 8], [2, 9], [2, 7],
                        [3, 7], [3, 7], [0, 0], [0, 0]]])
    counts = np.array([6])
    w = flops_bytes.sgns_episode(blocks, counts, minibatch=4, negatives=5,
                                 dim=128, itemsize=4)
    rows = (2 + 3 + 5) + (1 + 1 + 5)
    assert w["bytes"] == rows * 128 * 4 * 2
    assert w["flops"] == 6 * (6 * 128 * 5 + 6 * 128)
    assert w["samples"] == 6


def test_topk_bytes_one_table_read_per_call():
    w = flops_bytes.topk_scan(1000, 128, 4, 256)
    assert w["bytes"] == 1000 * 128 * 4
    assert w["flops"] == 2 * 256 * 1000 * 128


def test_graph_matches_the_repo_generator(tmp_path, monkeypatch):
    from repro.graph.generators import powerlaw_graph
    monkeypatch.setattr(graphgen, "CACHE", str(tmp_path))
    indptr, indices = graphgen.powerlaw_graph(5000, 4, 7)
    want = powerlaw_graph(5000, 4, seed=7)
    np.testing.assert_array_equal(indptr, want.indptr)
    np.testing.assert_array_equal(indices, want.indices)
    again = graphgen.powerlaw_graph(5000, 4, 7)        # from the cache
    np.testing.assert_array_equal(again[1], indices)


def test_walk_pairs_match_the_repo_augmentation():
    from repro.walk.augment import walks_to_pairs
    w = np.random.default_rng(0).integers(0, 50, (30, 11)).astype(np.int32)
    np.testing.assert_array_equal(traffic.walk_pairs(w, 5),
                                  walks_to_pairs(w, 5))


def test_traffic_same_work_for_every_seed(tmp_path, monkeypatch):
    monkeypatch.setattr(graphgen, "CACHE", str(tmp_path))
    indptr, indices = graphgen.powerlaw_graph(2000, 4, 0)
    mix = {"samples_per_chip": 3000, "replay": 2, "walk_length": 10,
           "window": 5}
    for seed in (1, 2**31 + 5, -3, 2**70):
        eps = traffic.episodes(indptr, indices, mix, 2,
                               common.rng(seed, common.STREAM_WALKS))
        assert [e.shape for e in eps] == [(6000, 2)] * 2
    a = traffic.episodes(indptr, indices, mix, 1, common.rng(9, 1))
    b = traffic.episodes(indptr, indices, mix, 1, common.rng(9, 1))
    np.testing.assert_array_equal(a[0], b[0])


def test_cell_config_traffic_kind_and_metric_found_from_files_alone(
        tmp_path, capsys):
    """A new configuration, mix, kind of cell and metric are files plus
    entries: run.main finds and drives them by name."""
    import run
    root = tiny_root(tmp_path)
    h = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(h, "configs", "other.json"), "w") as f:
        json.dump({"name": "other", "num_nodes": 7}, f)
    with open(os.path.join(h, "traffic", "echo.json"), "w") as f:
        json.dump({"kind": "echo", "rate_qps": 3}, f)
    with open(os.path.join(h, "cells", "echo.py"), "w") as f:
        f.write(
            "import common\n"
            "def run(ctx, args, devices, clock, t_start):\n"
            "    v = ctx['config']['num_nodes'] * ctx['traffic']['rate_qps']\n"
            "    info = {'kind': 'echo', 'x': float(v)}\n"
            "    return ({'correct': True, 'attempted': 1, 'failed': 0,\n"
            "             'metrics': common.read_metrics(ctx, info),\n"
            "             'device': common.device_info(devices, 1)}, [])\n")
    with open(os.path.join(h, "metrics", "new_metric.py"), "w") as f:
        f.write("def read(run):\n    return run.get('x')\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "other", "source": "s", "why": "w",
                             "file": "benchmarks/chip/configs/other.json",
                             "reduced": []})
    bench["workloads"].append({"name": "other.echo", "config": "other",
                               "traffic": "echo", "chips": 1, "why": "w"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "serve_p99_ms",
                               "workloads": ["other.echo"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    ctx = common.find_cell("other.echo", root)
    assert ctx["config"]["num_nodes"] == 7
    assert ctx["traffic"]["rate_qps"] == 3
    assert "new_metric" in [m["name"] for m in ctx["per_layer"]]
    assert "new_metric" not in [
        m["name"] for m in common.find_cell("tiny.serve", root)["per_layer"]]
    rc = run.main(["--workload", "other.echo", "--seed", "1", "--seconds",
                   "1", "--trace", "1"], root=root, require_tpu=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metrics"] == {"new_metric": {"value": 21.0, "unit": "ms"}}


@pytest.mark.parametrize("mix", [
    {"rate_qps": 1200},
    {"rate_qps": 1200, "arrival": "poisson"},
    {"rate_qps": 1200, "arrival": "onoff", "on_s": 0.5, "off_s": 1.5}])
def test_arrivals_offer_the_same_count_for_every_seed(mix):
    for seed in (1, 2**31 + 5, -3, 2**70):
        due = traffic.arrivals(mix, 10.0, common.rng(seed, 5))
        assert due.size == 12000
        assert np.all(np.diff(due) >= 0)
        assert 0.0 <= due[0] and due[-1] < 10.0
        if mix.get("arrival") == "onoff":
            assert np.all(due % 2.0 < 0.5)       # all inside on-time
            # the same mean rate: every period gets its share
            per = np.bincount((due // 2.0).astype(int), minlength=5)
            assert per.size == 5 and per.min() > 0.9 * 12000 / 5


def test_scan_metrics_count_the_same_calls_on_both_sides():
    """A call straddling either edge of the traced window counts on
    neither side; each call inside takes its own kernel events whole."""
    scan = common.load_module("metrics", "topk_scan_roofline")
    store = common.load_module("metrics", "store_call_ms")
    win = (100 * MS, 400 * MS)
    ops = [("%topk_mips.1", 80 * MS, 140 * MS),      # call A, before lo
           ("%topk_mips.1", 160 * MS, 200 * MS),     # call B
           ("%topk_mips.1", 250 * MS, 300 * MS),     # call C
           ("%fusion.3", 300 * MS, 301 * MS),
           ("%topk_mips.1", 360 * MS, 420 * MS)]     # call D, past hi
    host = [(scan.CALL, 70 * MS, 150 * MS), (scan.CALL, 155 * MS, 210 * MS),
            (scan.CALL, 240 * MS, 320 * MS), (scan.CALL, 350 * MS, 430 * MS)]
    tr = _trace(ops, host, window=win)
    assert scan.per_call(tr) == [(55 * MS, 40.0 * MS), (80 * MS, 50.0 * MS)]
    run = {"kind": "serve", "trace": tr, "table_bytes": 819e9 * 0.009,
           "peaks": {"hbm_bw": 819e9}}
    # two calls need 2 x 9 ms of bytes at the peak, and took 90 ms of scan
    assert scan.read(run) == pytest.approx(20.0)
    # (55 - 40 + 80 - 50) / 2 ms of host work per call
    assert store.read(run) == pytest.approx(22.5)
    empty = dict(run, trace=_trace(ops, host[:1] + host[3:], window=win))
    assert scan.read(empty) is None and store.read(empty) is None


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_names_and_units_use_allowed_characters():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for c in bench["configs"]:
        names += [c["name"], *c["reduced"]]
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4)
        mix = os.path.join(HARNESS, "traffic", w["traffic"] + ".json")
        with open(mix) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(HARNESS, "cells", kind + ".py"))
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(HARNESS, "metrics",
                                           m["name"] + ".py"))
    bad = [n for n in names if not NAME.match(n)]
    assert not bad, bad
    assert len(set(w["name"] for w in bench["workloads"])) == len(cells)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HARNESS, "run.py"), "--workload",
         "tencent-a-f32.serve", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())

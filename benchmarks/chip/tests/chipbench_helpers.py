"""A tiny copy of the benchmark's layout, driven on the CPU.

``tiny_root`` writes a checkout root holding a ``BENCHMARK.json`` with a
training cell and a serving cell of a small configuration (the real
configuration's keys at a width and row count a test can hold, the
program's plain-jnp impl), their traffic mixes, and the real metric
readers and cell drivers; ``base`` names the configuration it is cut
from. ``drive`` runs one cell through ``run.main`` with the chip check
skipped, and returns the parsed result line.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HARNESS))
if HARNESS not in sys.path:
    sys.path.insert(0, HARNESS)


def tiny_root(tmp, *, impl: str = "ref", base: str = "tencent-a-f32") -> str:
    root = str(tmp)
    h = os.path.join(root, "benchmarks", "chip")
    os.makedirs(os.path.join(h, "configs"))
    os.makedirs(os.path.join(h, "traffic"))
    for part in ("metrics", "cells"):
        shutil.copytree(os.path.join(HARNESS, part), os.path.join(h, part),
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(HARNESS, "configs", base + ".json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny", num_nodes=3000, dim=16, minibatch=64,
               neg_pool=512, impl=impl)
    # the real configurations hold no training limits until they are set
    # from readings on the chip; the tiny cell is held to these
    cfg["limits"]["train"] = {"loss_gap": 1e-3, "grad_gap": 1e-3,
                              "change_gap": 1e-3}
    with open(os.path.join(h, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(HARNESS, "traffic", "serve.json")) as f:
        serve = json.load(f)
    serve.update(rate_qps=400, sample=40, max_batch=32)
    for name, body in (
            ("replay", {"kind": "episodes", "samples_per_chip": 4096,
                        "replay": 4, "walk_length": 10, "window": 5,
                        "block_cap": 4096, "trace_episodes": 2}),
            ("serve", serve)):
        with open(os.path.join(h, "traffic", name + ".json"), "w") as f:
            json.dump(body, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.replay", "config": "tiny", "traffic": "replay",
         "chips": 1, "why": "test"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "serve",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def drive(root: str, workload: str, capsys, *, seed: int = 5_000_000_011,
          seconds: float = 1.0) -> dict:
    import graphgen
    import run
    graphgen.CACHE = os.path.join(root, "graph-cache")
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  root=root, require_tpu=False, t_start=time.perf_counter())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

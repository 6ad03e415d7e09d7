"""Retrieval-serving benchmark: the per-PR serving trajectory.

Sweeps ``ShardedEmbeddingStore.topk`` over (N, d, k, batch) for the serving
impls — including the two-tier ``quant`` tier (int8 first pass + exact
rescore) — and APPENDS a timestamped run to ``BENCH_serve.json`` (same
runs[] layout as the kernel/episode trajectories; see benchmarks/README.md
for the field reference). Two measurements per shape:

* **direct** — store.topk latency on a fixed query batch (p50/p99 over
  iterations) plus a table-scan byte model against the HBM roofline: a
  batch must read every byte of whichever tier it scans once (the shards'
  ACTUAL dtype itemsize — int8 for the quant tier, plus its f32 scales),
  and the quant tier additionally gathers ``m`` full-precision rows per
  query for the rescore (``rescore_bytes_model``, accounted separately).
  floor = (scan + rescore bytes) / HBM_BW; ``frac_of_roofline`` is
  floor/measured, same as ``bench_kernels.py``.
* **batched** — a seeded open-loop burst through ``MicroBatcher``:
  achieved QPS, request-latency percentiles, and the realized mean batch.

Every row also records recall@k against the numpy oracle (exact kernels ⇒
1.0; anything less is a correctness regression posting a fast number). On
this CPU container the pallas impl runs in interpret mode (Python-slow, so
its timings only track structure) — the ``xla`` impl is the meaningful CPU
trajectory; on TPU the same harness measures the real kernel.

    PYTHONPATH=src python benchmarks/bench_serve.py          # full sweep
    PYTHONPATH=src python benchmarks/bench_serve.py --smoke  # CI canary
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                   # noqa: E402

from common import append_run                                # noqa: E402
from repro.embed_serve import (MicroBatcher, ShardedEmbeddingStore,  # noqa: E402
                               drive_open_loop, overfetch_m, recall_at_k)
from repro.embed_serve import topk as tk                     # noqa: E402
from repro.kernels import ops                                # noqa: E402
from repro.launch import roofline                            # noqa: E402

# "quant" routes through the two-tier scan (int8 kernel on TPU, int8 jnp
# path on CPU — same auto rule as pallas/xla); "tiered" puts the hot-row
# exact tier (25% budget, powerlaw-ranked) in front of a compacted int8
# cold remainder — hot hits skip quantization entirely, recall stays 1.0
IMPLS = ("xla", "pallas", "quant", "tiered")

TIERED_BUDGET_FRAC = 0.25

# (N, d, k, batch): table rows x dim, top-k, queries per request batch
FULL_SHAPES = [
    (4096, 64, 10, 16),
    (4096, 128, 10, 64),
    (16384, 128, 10, 64),
    (16384, 128, 100, 64),
]
SMOKE_SHAPES = [(512, 32, 10, 8)]


def scan_bytes_model(store: ShardedEmbeddingStore, batch: int, k: int,
                     impl: str) -> tuple[int, int]:
    """(scan bytes, rescore bytes) one query batch must move; the (Q, k)
    outputs are noise next to the scan.

    Scan: every byte of the scanned tier once per resident query block —
    the shards' ACTUAL dtype itemsize (f32/bf16 exact shards, or the int8
    shards + their f32 row scales for the quant tier; do not assume f32).
    The pallas-kernel paths hold topk.DEFAULT_BLOCK_Q queries resident and
    re-scan per block; the jnp paths materialize all scores in one pass.
    Rescore (quant only): the tier-two gather reads m = ceil(k * overfetch)
    full-precision rows per query from the exact shards."""
    if impl == "tiered":
        # exact hot rows + compacted int8 cold remainder (value + f32 scale)
        tier_bytes = store.hot_tier_stats()["scan_bytes_tiered"]
    elif impl.startswith("quant"):
        tier_bytes = sum(
            int(np.prod(q8.shape)) * q8.dtype.itemsize
            + int(np.prod(sc.shape)) * sc.dtype.itemsize
            for q8, sc in store.qshards)
    else:
        tier_bytes = sum(int(np.prod(sh.shape)) * sh.dtype.itemsize
                         for sh in store.shards)
    kernel_path = impl == "pallas" or (
        impl in ("tiered",) + tuple(i for i in IMPLS if i.startswith("quant"))
        and not ops.interpret_mode())
    scans = (-(-batch // tk.DEFAULT_BLOCK_Q)) if kernel_path else 1
    rescore = 0
    itemsize = store.shards[0].dtype.itemsize
    d = store.dim
    if impl == "tiered":
        # only the cold (quant) tier rescores; hot hits are already exact
        for t in store.hot_tiers:
            if t.cold_valid == 0:
                continue
            m = overfetch_m(k, store.overfetch, t.cold_valid)
            rescore += batch * m * d * itemsize
    elif impl.startswith("quant"):
        for s, sh in enumerate(store.shards):
            if store.valid[s] == 0:
                continue
            m = overfetch_m(k, store.overfetch, store.valid[s])
            rescore += batch * m * d * itemsize
    return tier_bytes * scans, rescore


def bench_one(impl: str, N: int, d: int, k: int, batch: int, *,
              iters: int, requests: int, dtype: str, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    table = rng.normal(0, 0.1, size=(N, d)).astype(np.float32)
    quant = "int8" if (impl.startswith("quant")
                       or impl == "tiered") else None
    store = ShardedEmbeddingStore.from_array(table, dtype=dtype, quant=quant)
    hot_rows = None
    if impl == "tiered":
        # powerlaw access counts (zipf-1.3 traffic over the id space, the
        # training side's hot-row shape) rank the hot set; 25% budget
        traffic = np.minimum(rng.zipf(1.3, size=8 * N), N) - 1
        hot_rows = store.enable_hot_tier(
            int(TIERED_BUDGET_FRAC * N),
            counts=np.bincount(traffic, minlength=N).astype(np.float64))
    queries = table[rng.integers(0, N, size=batch)]

    # direct path: fixed-batch latency + scan-bytes roofline
    vals, ids = store.topk(queries, k, impl=impl)      # compile + warm up
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        store.topk(queries, k, impl=impl)
        times.append(time.perf_counter() - t0)
    times = np.sort(times)
    direct_s = float(np.percentile(times, 50))
    scan_bytes, rescore_bytes = scan_bytes_model(store, batch, k, impl)
    bound_s = (scan_bytes + rescore_bytes) / roofline.HBM_BW
    oracle_vals, oracle_ids = store.oracle_topk(queries, k)
    # tie tolerance from ground-truth rescoring, not the kernel's claims
    recall = recall_at_k(ids, oracle_ids,
                         got_vals=store.score_ids(queries, ids),
                         oracle_vals=oracle_vals)

    # batched path: seeded open-loop burst through the frontend.
    # fixed_batch pins the backend shape to max_batch (compiled above by
    # the direct-path warm-up), so no retrace lands in a request latency
    stream = table[rng.integers(0, N, size=requests)]
    batcher = MicroBatcher(lambda q: store.topk(q, k, impl=impl), d,
                           max_batch=batch, window_ms=2.0, fixed_batch=True)
    _, req_lat, wall = drive_open_loop(batcher, stream)
    batcher.close()

    extra = {}
    if impl == "tiered":
        st = store.hot_tier_stats()
        extra = {
            "hot_rows": hot_rows,
            "hot_budget_frac": hot_rows / N,
            "returned_hot_frac": st["returned_hot_frac"],
            "scan_bytes_tiered": st["scan_bytes_tiered"],
            "scan_bytes_quant": st["scan_bytes_quant"],
        }
    return {
        **extra,
        "impl": impl,
        "N": N,
        "d": d,
        "k": k,
        "batch": batch,
        "dtype": dtype,
        "quant": store.quant,
        "overfetch": store.overfetch if store.quant else None,
        "shards": len(store.shards),
        "direct_p50_s": direct_s,
        "direct_p99_s": float(np.percentile(times, 99)),
        "queries_per_s_direct": batch / direct_s,
        "scan_bytes_model": scan_bytes,
        "rescore_bytes_model": rescore_bytes,
        "roofline_bound_s": bound_s,
        "frac_of_roofline": bound_s / direct_s,
        "recall_at_k": recall,
        "batched_requests": requests,
        "batched_qps": requests / wall,
        "batched_p50_s": float(np.percentile(req_lat, 50)),
        "batched_p99_s": float(np.percentile(req_lat, 99)),
        "batched_mean_batch": batcher.stats_snapshot().mean_batch,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape / few iters (CI regression canary)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--impls", default=",".join(IMPLS))
    # f32 default like the other CPU trajectories (bf16 is emulated and
    # ~30x slower on CPU XLA); pass --dtype bfloat16 on TPU
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    args = ap.parse_args()

    shapes = SMOKE_SHAPES if args.smoke else FULL_SHAPES
    interpret = ops.interpret_mode()
    iters = args.iters or (2 if args.smoke else 5)
    requests = args.requests or (32 if args.smoke else 256)

    results = []
    for (N, d, k, batch) in shapes:
        for impl in args.impls.split(","):
            # interpret-mode pallas is Python-slow: keep its sweep light
            it = 1 if (impl == "pallas" and interpret) else iters
            req = min(requests, 4 * batch) if (impl == "pallas"
                                               and interpret) else requests
            r = bench_one(impl, N, d, k, batch, iters=it, requests=req,
                          dtype=args.dtype)
            results.append(r)
            print(f"N={N:6d} d={d:4d} k={k:4d} B={batch:4d} {impl:7s} "
                  f"direct p50 {r['direct_p50_s']*1e3:9.2f}ms "
                  f"({r['queries_per_s_direct']:9.1f} q/s, "
                  f"{r['frac_of_roofline']*100:8.4f}% of roofline) | "
                  f"batched {r['batched_qps']:9.1f} QPS | "
                  f"recall@{k} {r['recall_at_k']:.4f}")
            assert r["recall_at_k"] == 1.0, (
                "serving recall regression", impl, N, d, k)

    run = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "interpret_mode": interpret,
        "dtype": args.dtype,
        "hbm_bw_model_bytes_per_s": roofline.HBM_BW,
        "note": ("interpret-mode pallas timings are Python-bound; compare "
                 "xla timings and the scan-byte model across PRs, absolute "
                 "pallas timings only on TPU"),
        "results": results,
    }
    n = append_run(args.out, "embed_serve", run)
    print(f"wrote {os.path.abspath(args.out)} "
          f"(run {n}, {len(results)} rows)")


if __name__ == "__main__":
    main()

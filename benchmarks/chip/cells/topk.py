"""Serving cells: an open loop of top-k requests (mix kind ``topk``).

Set-up makes the table on the device from the seed (rows drawn normal,
then scaled to unit norm, in the served dtype), hands it to
``ShardedEmbeddingStore`` as its one shard, stands ``MicroBatcher`` in
front of ``store.topk``, and warms the one padded batch shape. The window
submits each request's queries at its due time from one generator thread,
and a request's latency runs from its due time to the last of its results.
After the window every request due in it gets up to a minute to finish;
the program's state is then freed, the table made again from the seed, and
the answers of a seed-drawn sample of the requests checked against the
exact reference.

The mix sets ``rate_qps``, ``arrival`` (see ``traffic.arrivals``),
``queries_per_request`` (default 1), ``zipf_a``, ``k``, ``impl``, the
batcher's ``max_batch``, ``window_ms`` and ``fixed_batch``, ``sample``
(queries compared), ``trace_skip_s`` and ``trace_seconds``.
"""
from __future__ import annotations

import gc
import os
import sys
import threading
import time

import numpy as np

import common
import compare
import devtrace
import peaks
import reference
import traffic

DRAIN_S = 60.0


def _table_fn(rows_p: int, n: int, d: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def fn(seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (rows_p, d),
                              jnp.float32)
        x = x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-12)
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        return jnp.where(row < n, x, 0.0).astype(jnp.dtype(dtype))
    return jax.jit(fn)


def block_n(cfg: dict) -> int:
    """The store's scan tile, as the program chooses it for the table."""
    import jax.numpy as jnp
    from repro.embed_serve import topk as tk
    d, n = cfg["dim"], cfg["num_nodes"]
    return min(tk.choose_block_n(d, np.dtype(jnp.dtype(cfg["dtype"]))), n)


def make_table(cfg: dict, seed: int, dev):
    """The run's table on ``dev``, made from the seed in one jitted call;
    rows padded to the scan tile are zero."""
    import jax
    n = cfg["num_nodes"]
    make = _table_fn(-(-n // block_n(cfg)) * block_n(cfg), n, cfg["dim"],
                     cfg["dtype"])
    return jax.device_put(
        make(np.int32(common.seed31(seed, common.STREAM_TABLES))), dev)


def inputs(ctx: dict, seed: int, seconds: float, dev) -> dict:
    """What a run of ``seconds`` is made of: the table (on ``dev``), the
    due times, and every query (host f32,
    ``(requests, queries_per_request, d)``)."""
    import jax.numpy as jnp
    cfg, tr = ctx["config"], ctx["traffic"]
    table = make_table(cfg, seed, dev)
    r = common.rng(seed, common.STREAM_QUERIES)
    due = traffic.arrivals(tr, seconds, r)
    qpr = tr.get("queries_per_request", 1)
    nodes = traffic.zipf_nodes(cfg["num_nodes"], tr["zipf_a"],
                               due.size * qpr, r)
    queries = np.asarray(jnp.take(table, jnp.asarray(nodes), axis=0)
                         .astype(jnp.float32))
    return {"table": table, "due": due,
            "queries": queries.reshape(due.size, qpr, cfg["dim"])}


def sample(seed: int, requests, qpr: int, want: int) -> np.ndarray:
    """A seed-drawn sample of the given request indices holding about
    ``want`` queries, ascending."""
    got = np.asarray(sorted(requests))
    m = min(got.size, -(-want // qpr))
    return np.sort(common.rng(seed, common.STREAM_SAMPLE).choice(
        got, size=m, replace=False))


def run(ctx: dict, args, devices, clock, t_start: float) -> tuple:
    import jax
    import jax.numpy as jnp
    from repro.core.partition import NodePartition
    from repro.embed_serve import MicroBatcher, ShardedEmbeddingStore
    cfg, tr = ctx["config"], ctx["traffic"]
    dev = devices[0]
    n, d, dtype, k = cfg["num_nodes"], cfg["dim"], cfg["dtype"], tr["k"]
    phases = [("start", time.perf_counter() - t_start)]
    x = inputs(ctx, args.seed, args.seconds, dev)
    due, queries, table = x["due"], x["queries"], x["table"]
    qpr = queries.shape[1]
    phases.append(("table and queries", time.perf_counter() - t_start))

    store = ShardedEmbeddingStore(
        [table], NodePartition(n, dims=(1,), subparts=1), [n], [dev],
        host_table=None, block_n=block_n(cfg))

    def serve_fn(q):
        with jax.profiler.TraceAnnotation("bench.store_topk"):
            return store.topk(q, k, impl=tr["impl"])
    batcher = MicroBatcher(serve_fn, d, max_batch=tr["max_batch"],
                           window_ms=tr["window_ms"],
                           fixed_batch=tr["fixed_batch"])
    warm = queries.reshape(-1, d)[:tr["max_batch"]]
    for f in [batcher.submit(q) for q in warm]:
        f.result()
    stats0 = batcher.stats_snapshot()
    setup_s = time.perf_counter() - t_start
    phases.append(("store and warm-up", setup_s))
    setup_compile_s = clock.seconds

    compiles0 = clock.count
    done = np.full(queries.shape[:2], np.nan)
    futs = [None] * due.size
    late = np.zeros(due.size)

    def submit_all(t0):
        for i in range(due.size):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - t0 - due[i]
            futs[i] = [batcher.submit(q) for q in queries[i]]
            for j, f in enumerate(futs[i]):
                f.add_done_callback(
                    lambda _f, i=i, j=j: done.__setitem__(
                        (i, j), time.perf_counter()))

    t0 = time.perf_counter() + 0.05
    gen = threading.Thread(target=submit_all, args=(t0,), name="bench-load")
    gen.start()
    trace_dir = os.path.join(common.HERE, ".trace", ctx["cell"]["name"])
    if args.trace:
        time.sleep(max(0.0, t0 + tr["trace_skip_s"] - time.perf_counter()))
        with devtrace.capture(trace_dir):
            time.sleep(tr["trace_seconds"])
    gen.join()
    end = time.perf_counter() + DRAIN_S
    for fs in futs:
        for f in fs:
            try:
                f.result(timeout=max(0.0, end - time.perf_counter()))
            except Exception:               # noqa: BLE001 -- counted below
                pass
    time.sleep(0.01)                        # done-callbacks follow result()
    compiles = clock.count - compiles0
    peak = common.memory_peak_bytes([dev])
    stats = batcher.stats_snapshot()
    batcher.close()
    answers = {}
    for i, fs in enumerate(futs):
        if all(f.done() and not f.cancelled() and f.exception() is None
               for f in fs):
            answers[i] = [f.result() for f in fs]
    failed = due.size - len(answers)
    last = done.max(axis=1)                 # NaN while any is missing
    lat_ms = np.where(np.isnan(last), np.inf, (last - t0 - due) * 1e3)
    q4 = max(1, due.size // 4)
    in_window = int(np.sum(last - t0 <= args.seconds))
    print("set-up phases (s from process start): " + ", ".join(
        f"{name} {t:.3f}" for name, t in phases) + f"; {setup_compile_s:.3f}"
        f" s compiling", file=sys.stderr)
    print(f"window: {due.size} requests of {qpr} queries due in "
          f"{args.seconds} s at {tr['rate_qps']} /s, {in_window} done inside "
          f"it; generator late by p50 {np.median(late) * 1e3:.3f} ms, p99 "
          f"{np.quantile(late, 0.99) * 1e3:.3f} ms, max "
          f"{late.max() * 1e3:.3f} ms; median latency of the first and "
          f"last quarter {np.median(lat_ms[:q4]):.3f} / "
          f"{np.median(lat_ms[-q4:]):.3f} ms; "
          f"{stats.batches - stats0.batches} backend calls; {compiles} "
          f"compiles inside the window; {failed} without a result",
          file=sys.stderr)

    del store, batcher, table, serve_fn, x
    gc.collect()
    numbers = check_answers(ctx, args.seed, answers, queries, dev)
    checks = compare.checks(numbers, cfg["limits"]["serve"])
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": int(due.size), "failed": int(failed),
              "device": common.device_info(devices, 1) | {
                  "memory_peak_bytes": peak}}
    if not args.trace:
        result["metrics"] = {
            "serve_p99_ms": {"value": float(np.quantile(lat_ms, 0.99)),
                             "unit": "ms"},
            "serve_p50_ms": {"value": float(np.quantile(lat_ms, 0.5)),
                             "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, checks
    trc = devtrace.load(trace_dir)
    itemsize = np.dtype(jnp.dtype(dtype)).itemsize
    run_info = {"kind": "serve", "trace": trc, "chips": 1,
                "peaks": peaks.peaks(dev.device_kind),
                "table_bytes": float(n * d * itemsize),
                "requests": stats.requests - stats0.requests,
                "batches": stats.batches - stats0.batches}
    result["metrics"] = common.read_metrics(ctx, run_info)
    result["device"]["busy_s"] = devtrace.busy_s(trc)
    result["device"]["window_s"] = trc.window_s
    result["breakdown"] = devtrace.breakdown(trc)
    return result, checks


def check_answers(ctx: dict, seed: int, answers: dict, queries,
                  dev) -> dict:
    """The served answers of a seed-drawn sample of the requests against
    the exact reference, over the table made again from the seed."""
    cfg, tr = ctx["config"], ctx["traffic"]
    n, k = cfg["num_nodes"], tr["k"]
    if not answers:
        return {"rank_gap": float("inf"), "score_gap": float("inf")}
    pick = sample(seed, answers, queries.shape[1], tr["sample"])
    table = make_table(cfg, seed, dev)
    q = queries[pick].reshape(-1, queries.shape[2])
    ref_v, _ = reference.topk_exact(table, q, k, n)
    ids = np.stack([i for r in pick for _, i in answers[r]])
    vals = np.stack([v for r in pick for v, _ in answers[r]])
    exact = reference.scores(table, q, ids)
    return compare.serve_numbers(ids, vals, ref_v, exact, n)


def calibrate(ctx: dict, seed: int, devices, seconds: float) -> dict:
    """The control, the exact reference in the configuration's ``control``
    precision put in the program's place, over the queries a run of
    ``seconds`` would compare if every request were answered, against the
    exact reference."""
    cfg, tr = ctx["config"], ctx["traffic"]
    n, k = cfg["num_nodes"], tr["k"]
    x = inputs(ctx, seed, seconds, devices[0])
    queries = x["queries"]
    pick = sample(seed, range(queries.shape[0]), queries.shape[1],
                  tr["sample"])
    q = queries[pick].reshape(-1, queries.shape[2])
    table = x["table"]
    ref_v, _ = reference.topk_exact(table, q, k, n)
    ctl = cfg["control"]
    cv, ci = reference.topk_exact(table, q, k, n, precision=ctl["precision"],
                                  dtype=ctl["dtype"])
    exact = reference.scores(table, q, ci)
    return {"control": compare.serve_numbers(ci, cv, ref_v, exact, n)}

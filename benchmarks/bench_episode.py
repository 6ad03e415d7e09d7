"""Episode-level benchmark: whole-episode samples/sec through core.hybrid.

GraphVite and PyTorch-BigGraph both report bucket/episode throughput — not
single-kernel microbenchmarks — as the number that matters at scale, and the
paper's 3-minute epochs are an episode-level claim. This harness times
``HybridEmbeddingTrainer.train_episode`` (ring rotation + sub-part pipeline
+ minibatch scan, i.e. everything a production step runs) over a sweep of
(impl, minibatch B, dim d, mesh shape) and APPENDS a timestamped run to
``BENCH_episode.json``, so every future perf PR moves an end-to-end number.

On this CPU container the Pallas impls run in interpret mode and multi-
device meshes are XLA host devices — absolute numbers are only comparable
across PRs on the same container; on TPU the same harness measures the real
thing.

    PYTHONPATH=src python benchmarks/bench_episode.py          # full sweep
    PYTHONPATH=src python benchmarks/bench_episode.py --smoke  # CI canary
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

# mesh-shape sweeps need >1 device on the CPU container; must be set before
# the first jax import
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=2")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                   # noqa: E402

from common import append_run                                # noqa: E402
from repro import obs                                        # noqa: E402
from repro.core import (EpisodePipeline, HybridConfig,          # noqa: E402
                        HybridEmbeddingTrainer, TieredEmbeddingTrainer,
                        build_episode_blocks)
from repro.graph.generators import powerlaw_graph            # noqa: E402
from repro.kernels import ops                                # noqa: E402
from repro.runtime import FaultPlan, clear_plan, install_plan  # noqa: E402
from repro.walk import MemorySampleStore, WalkConfig, WalkEngine  # noqa: E402

IMPLS = ("ref", "pallas", "pallas_fused2")

# (B, d): shared-negative minibatch rows x embedding dim
FULL_SHAPES = [(64, 64), (64, 128), (128, 128)]
SMOKE_SHAPES = [(32, 32)]
MESHES = [(1, 1), (1, 2)]

# the end-to-end dataflow comparison (walks + build + stage + train) measures
# the host pipeline, not the kernels — one impl is enough
DATAFLOW_SHAPES = [(64, 64)]
DATAFLOW_SMOKE_SHAPES = [(32, 32)]

# tiered-cache comparison (resident vs stream vs hot-row cache): like the
# dataflow rows, this measures dataflow structure, not kernels — one shape
CACHE_SHAPES = [(64, 64)]
CACHE_SMOKE_SHAPES = [(32, 32)]
CACHE_BUDGET_FRAC = 0.25     # HBM rows per table, as a fraction of all rows


def bench_one(impl: str, B: int, d: int, mesh_shape, *, nodes: int,
              samples: int, episodes: int, dtype: str, seed: int = 0):
    mesh = jax.make_mesh(mesh_shape, ("data", "model"))
    cfg = HybridConfig(dim=d, minibatch=B, negatives=8, subparts=2,
                       neg_pool=2048, impl=impl, dtype=dtype, seed=seed)
    trainer = HybridEmbeddingTrainer(nodes, mesh, cfg)
    trainer.init_embeddings()
    rng = np.random.default_rng(seed)
    pairs = rng.integers(0, nodes, size=(samples, 2), dtype=np.int64)
    eb = build_episode_blocks(pairs, trainer.part, pad_multiple=B)
    n_samples = int(eb.counts.sum())
    trainer.train_episode(eb)            # compile + warm up
    t0 = time.perf_counter()
    loss = 0.0
    for _ in range(episodes):
        loss = trainer.train_episode(eb)  # float() inside = full sync
    dt = (time.perf_counter() - t0) / episodes
    return {
        "impl": impl,
        "B": B,
        "d": d,
        "mesh": list(mesh_shape),
        "episode_s": dt,
        "samples_per_episode": n_samples,
        "samples_per_s": n_samples / dt,
        "loss": loss,                     # cross-impl sanity signal
    }


def _overlap_efficiency(train_s, wall_s):
    """Fraction of the timed epoch spent in device training rather than
    stalled on host dataflow stages: 1.0 = walks / block builds / staging
    fully hidden behind training, lower = the consumer sat waiting on host
    work. Both quantities are measured INSIDE the timed window and the
    formula is identical for sync and streamed rows, so the number is
    comparable across modes (busy-second columns like walk_s can include
    work that ran ahead of the window and would over-credit a ratio built
    from them)."""
    if wall_s <= 0:
        return 1.0
    return max(0.0, min(1.0, train_s / wall_s))


def bench_dataflow(impl: str, B: int, d: int, mesh_shape, *, nodes: int,
                   episodes: int, walk_workers: int, depth: int,
                   dtype: str, seed: int = 0):
    """End-to-end epoch through the full dataflow, sync vs streamed.

    sync        — serial walks (workers=1), then per episode: build, stage,
                  train, all on the consumer thread (the pre-PR-5 path).
    streamed    — multi-worker walk engine putting episodes as they complete
                  into a bounded store, consumed through the multi-stage
                  EpisodePipeline (walk-wait -> build -> device staging)
                  while the trainer runs.
    faults_idle — the streamed path again (same warm-start structure, later
                  epochs) with an inert FaultPlan installed: every
                  walk.chunk / store.put fault point runs the full matcher
                  but no spec ever fires. Gates the idle overhead of the
                  fault-injection layer against the streamed row.
    remote_walkers — the same consumer pipeline fed by TWO subprocess walk
                  producers over the episode transport (framing + chunk
                  assembly + ordered delivery), the paper's CPU-machines-
                  feed-GPU-trainers deployment shape. The row records wire
                  traffic (msgs/s, bytes, resend rate) for the timed epoch;
                  the gate warns when transport-fed throughput falls more
                  than 15% below the in-process streamed row.
    coordinator_failover — the remote_walkers shape with one mid-epoch
                  coordinator kill + recovering restart on the same port:
                  measures what a takeover (store-scan queue rebuild +
                  producer reconnect backoff) costs end to end, plus the
                  successor's time to first applied chunk. Warns when the
                  interrupted epoch's throughput drops >20% below the
                  uninterrupted remote_walkers row.
    obs_idle    — the streamed path once more with the telemetry layer live
                  (metrics registry + in-memory span tracer, no file sinks):
                  every instrumented hot path pays its enabled cost. Gated
                  within 5% of the streamed row — observability that taxes
                  the pipeline it observes is not cheap enough to leave on.

    Both modes time epoch 2 (identical sample stream — the chunk
    decomposition and RNG keying are worker-count-invariant) with the same
    pinned block_cap, so they compile once and train identical blocks; any
    cap overflow drops the same pairs in both modes (reported as `dropped`).
    """
    mesh = jax.make_mesh(mesh_shape, ("data", "model"))
    cfg = HybridConfig(dim=d, minibatch=B, negatives=8, subparts=2,
                       neg_pool=2048, impl=impl, dtype=dtype, seed=seed)
    g = powerlaw_graph(nodes, 5, seed=seed)
    trainer = HybridEmbeddingTrainer(g.num_nodes, mesh, cfg,
                                     degrees=g.degrees())
    trainer.init_embeddings()

    def wcfg(workers):
        # node2vec walks (rejection-sampled 2nd-order steps) — the paper's
        # production mode and a walk stage with real cost to overlap
        return WalkConfig(walk_length=8, window=4, episodes=episodes,
                          seed=seed, workers=workers, walks_per_node=2,
                          node2vec_p=0.5, node2vec_q=2.0,
                          chunk_size=max(256, nodes // 8))

    # pre-pass on epoch 0: pin the block shape (headroom so the measured
    # epoch rarely drops; any overflow drops identically in both modes
    # and is reported) and warm up the compile cache
    store = MemorySampleStore()
    WalkEngine(g, wcfg(1), store).run_epoch(0)
    cap = 0
    for ep in range(episodes):
        eb = build_episode_blocks(np.asarray(store.get(0, ep)), trainer.part,
                                  pad_multiple=B)
        cap = max(cap, int(eb.counts.max()))
    cap += B                                     # headroom for later epochs
    warm = build_episode_blocks(np.asarray(store.get(0, 0)), trainer.part,
                                block_cap=cap, pad_multiple=B)
    trainer.train_episode(warm)
    store.drop_epoch(0)

    rows = []

    # ---- sync: everything on the consumer thread, walks first. Times
    # epoch 2 — the same epoch (same sample stream) the streamed mode times.
    eng = WalkEngine(g, wcfg(1), store)
    t0 = time.perf_counter()
    eng.run_epoch(2)
    walk_s = sum(eng.episode_walk_s.values())
    build_s = stage_s = train_s = 0.0
    n_samples = dropped = 0
    for ep in range(episodes):
        pairs = np.asarray(store.get(2, ep))
        t = time.perf_counter()
        eb = build_episode_blocks(pairs, trainer.part, block_cap=cap,
                                  pad_multiple=B)
        build_s += time.perf_counter() - t
        t = time.perf_counter()
        staged = trainer.stage_blocks(eb)
        stage_s += time.perf_counter() - t
        t = time.perf_counter()
        trainer.train_episode(staged)            # float(loss) = full sync
        train_s += time.perf_counter() - t
        n_samples += staged.num_samples
        dropped += eb.dropped
    wall_s = time.perf_counter() - t0
    store.drop_epoch(2)
    rows.append({
        "mode": "sync", "impl": impl, "B": B, "d": d,
        "mesh": list(mesh_shape), "episodes": episodes,
        "walk_workers": 1, "pipeline_depth": 0,
        "walk_s": walk_s, "walk_wait_s": walk_s, "build_s": build_s,
        "stage_s": stage_s, "train_s": train_s, "wall_s": wall_s,
        "samples_per_epoch": n_samples, "dropped": dropped,
        "samples_per_s": n_samples / wall_s,
        "overlap_efficiency": _overlap_efficiency(train_s, wall_s),
        "peak_resident_episodes": None,
    })

    # ---- streamed: bounded store, async multi-worker walks, staged pipeline.
    # Steady-state timing: epoch 1 fills the pipeline and (as in production —
    # the paper walks one epoch ahead) epoch 2's walker starts as soon as
    # epoch 1's finishes, so the timed epoch sees the dataflow a long-running
    # job sees, not the one-time cold-start fill.
    store = MemorySampleStore(depth=depth + 1)
    pipe = EpisodePipeline(store, trainer.part, pad_multiple=B,
                           block_cap=cap, depth=depth,
                           stage_fn=trainer.stage_blocks, drop_consumed=True)
    eng = WalkEngine(g, wcfg(walk_workers), store)
    eng.start_async(1)
    eng2 = None
    for ep in range(episodes):                  # warm epoch (untimed)
        pipe.prefetch_window(1, ep, episodes)
        trainer.train_episode(pipe.get(1, ep))
        if eng2 is None and eng.finished():
            eng.join()
            eng2 = WalkEngine(g, wcfg(walk_workers), store)
            eng2.start_async(2)
    eng.join()
    if eng2 is None:
        eng2 = WalkEngine(g, wcfg(walk_workers), store)
        eng2.start_async(2)
    store.drop_epoch(1)

    t0 = time.perf_counter()
    walk_wait_s = build_s = stage_s = train_s = 0.0
    n_samples = dropped = 0
    for ep in range(episodes):                  # timed steady-state epoch
        pipe.prefetch_window(2, ep, episodes)
        staged = pipe.get(2, ep)
        times = pipe.pop_times(2, ep)
        t = time.perf_counter()
        trainer.train_episode(staged)
        train_s += time.perf_counter() - t
        walk_wait_s += times.get("walk_wait_s", 0.0)
        build_s += times.get("build_s", 0.0)
        stage_s += times.get("stage_s", 0.0)
        n_samples += staged.num_samples
        dropped += staged.dropped
    wall_s = time.perf_counter() - t0
    eng2.join()
    walk_s = sum(t for (e, _), t in eng2.episode_walk_s.items() if e == 2)
    store.drop_epoch(2)
    rows.append({
        "mode": "streamed", "impl": impl, "B": B, "d": d,
        "mesh": list(mesh_shape), "episodes": episodes,
        "walk_workers": walk_workers, "pipeline_depth": depth,
        "walk_s": walk_s, "walk_wait_s": walk_wait_s, "build_s": build_s,
        "stage_s": stage_s, "train_s": train_s, "wall_s": wall_s,
        "samples_per_epoch": n_samples, "dropped": dropped,
        "samples_per_s": n_samples / wall_s,
        "overlap_efficiency": _overlap_efficiency(train_s, wall_s),
        "peak_resident_episodes": store.peak_resident,
    })

    # ---- faults_idle: the streamed epoch again (epochs 3 warm, 4 timed —
    # same warm-start structure as above) with an inert plan installed. The
    # `at` ordinals are unreachable, so every walk.chunk / store.put
    # fault_point takes the full locked matcher path and nothing fires —
    # this row is the idle cost of the fault layer the runtime docs promise
    # is free.
    plan = FaultPlan(["walk.chunk:crash:at=1000000000",
                      "store.put:crash:at=1000000000"])
    install_plan(plan)
    try:
        eng3 = WalkEngine(g, wcfg(walk_workers), store)
        eng3.start_async(3)
        eng4 = None
        for ep in range(episodes):              # warm epoch (untimed)
            pipe.prefetch_window(3, ep, episodes)
            trainer.train_episode(pipe.get(3, ep))
            if eng4 is None and eng3.finished():
                eng3.join()
                eng4 = WalkEngine(g, wcfg(walk_workers), store)
                eng4.start_async(4)
        eng3.join()
        if eng4 is None:
            eng4 = WalkEngine(g, wcfg(walk_workers), store)
            eng4.start_async(4)
        store.drop_epoch(3)

        t0 = time.perf_counter()
        walk_wait_s = build_s = stage_s = train_s = 0.0
        n_samples = dropped = 0
        for ep in range(episodes):              # timed epoch, plan live
            pipe.prefetch_window(4, ep, episodes)
            staged = pipe.get(4, ep)
            times = pipe.pop_times(4, ep)
            t = time.perf_counter()
            trainer.train_episode(staged)
            train_s += time.perf_counter() - t
            walk_wait_s += times.get("walk_wait_s", 0.0)
            build_s += times.get("build_s", 0.0)
            stage_s += times.get("stage_s", 0.0)
            n_samples += staged.num_samples
            dropped += staged.dropped
        wall_s = time.perf_counter() - t0
        eng4.join()
        walk_s = sum(t for (e, _), t in eng4.episode_walk_s.items() if e == 4)
        store.drop_epoch(4)
    finally:
        clear_plan()
    rows.append({
        "mode": "faults_idle", "impl": impl, "B": B, "d": d,
        "mesh": list(mesh_shape), "episodes": episodes,
        "walk_workers": walk_workers, "pipeline_depth": depth,
        "walk_s": walk_s, "walk_wait_s": walk_wait_s, "build_s": build_s,
        "stage_s": stage_s, "train_s": train_s, "wall_s": wall_s,
        "samples_per_epoch": n_samples, "dropped": dropped,
        "samples_per_s": n_samples / wall_s,
        "overlap_efficiency": _overlap_efficiency(train_s, wall_s),
        "peak_resident_episodes": store.peak_resident,
        "fault_points_checked": (plan.count("walk.chunk")
                                 + plan.count("store.put")),
    })

    # ---- remote_walkers: same consumer pipeline, episodes produced by two
    # subprocess producers over the transport (epochs 5 warm / 6 timed, the
    # usual steady-state structure: both epochs are submitted up front so
    # epoch 6 production starts the instant epoch 5 fully lands).
    from repro.walk import RemoteWalkCoordinator
    coord = RemoteWalkCoordinator(g, wcfg(1), store, num_producers=2,
                                  heartbeat_s=0.5, lease_s=30.0,
                                  mode="process")
    coord.start()
    try:
        h5, h6 = coord.epoch_walker(), coord.epoch_walker()
        h5.start_async(5)
        h6.start_async(6)
        for ep in range(episodes):                  # warm epoch (untimed)
            pipe.prefetch_window(5, ep, episodes)
            trainer.train_episode(pipe.get(5, ep))
        h5.join()
        store.drop_epoch(5)

        st_before = coord.transport_stats()
        t0 = time.perf_counter()
        walk_wait_s = build_s = stage_s = train_s = 0.0
        n_samples = dropped = 0
        for ep in range(episodes):                  # timed steady-state epoch
            pipe.prefetch_window(6, ep, episodes)
            staged = pipe.get(6, ep)
            times = pipe.pop_times(6, ep)
            t = time.perf_counter()
            trainer.train_episode(staged)
            train_s += time.perf_counter() - t
            walk_wait_s += times.get("walk_wait_s", 0.0)
            build_s += times.get("build_s", 0.0)
            stage_s += times.get("stage_s", 0.0)
            n_samples += staged.num_samples
            dropped += staged.dropped
        wall_s = time.perf_counter() - t0
        h6.join()
        st_after = coord.transport_stats()
        store.drop_epoch(6)
    finally:
        coord.close()
    msgs = ((st_after["frames_recv"] + st_after["frames_sent"])
            - (st_before["frames_recv"] + st_before["frames_sent"]))
    rows.append({
        "mode": "remote_walkers", "impl": impl, "B": B, "d": d,
        "mesh": list(mesh_shape), "episodes": episodes,
        "walk_workers": 2, "pipeline_depth": depth,
        # walks run inside the producer subprocesses: no in-process walk
        # seconds to report — walk_wait_s still measures what the consumer
        # actually stalled on
        "walk_s": 0.0, "walk_wait_s": walk_wait_s, "build_s": build_s,
        "stage_s": stage_s, "train_s": train_s, "wall_s": wall_s,
        "samples_per_epoch": n_samples, "dropped": dropped,
        "samples_per_s": n_samples / wall_s,
        "overlap_efficiency": _overlap_efficiency(train_s, wall_s),
        "peak_resident_episodes": store.peak_resident,
        "transport_msgs_per_s": msgs / wall_s,
        "transport_wire_bytes": (st_after["bytes_recv"]
                                 - st_before["bytes_recv"]),
        "transport_resend_rate": st_after["resend_rate"],
        "transport_dup_chunks": st_after["dup_chunks"],
    })

    # ---- obs_idle: the streamed epoch again (epochs 7 warm / 8 timed, same
    # warm-start structure) with the telemetry layer LIVE: registry installed,
    # in-memory tracer recording every span, no file sinks. Every walk chunk,
    # store put/get, pipeline stage and train episode takes its instrumented
    # path — this row is the enabled cost of the obs layer, gated against the
    # streamed baseline (the DISABLED cost is the zero-allocation test).
    reg = obs.enable()
    tr_obs = obs.Tracer()
    obs.set_tracer(tr_obs)
    try:
        eng7 = WalkEngine(g, wcfg(walk_workers), store)
        eng7.start_async(7)
        eng8 = None
        for ep in range(episodes):              # warm epoch (untimed)
            pipe.prefetch_window(7, ep, episodes)
            trainer.train_episode(pipe.get(7, ep))
            if eng8 is None and eng7.finished():
                eng7.join()
                eng8 = WalkEngine(g, wcfg(walk_workers), store)
                eng8.start_async(8)
        eng7.join()
        if eng8 is None:
            eng8 = WalkEngine(g, wcfg(walk_workers), store)
            eng8.start_async(8)
        store.drop_epoch(7)

        t0 = time.perf_counter()
        walk_wait_s = build_s = stage_s = train_s = 0.0
        n_samples = dropped = 0
        for ep in range(episodes):              # timed epoch, telemetry live
            pipe.prefetch_window(8, ep, episodes)
            staged = pipe.get(8, ep)
            times = pipe.pop_times(8, ep)
            t = time.perf_counter()
            trainer.train_episode(staged)
            train_s += time.perf_counter() - t
            walk_wait_s += times.get("walk_wait_s", 0.0)
            build_s += times.get("build_s", 0.0)
            stage_s += times.get("stage_s", 0.0)
            n_samples += staged.num_samples
            dropped += staged.dropped
        wall_s = time.perf_counter() - t0
        eng8.join()
        walk_s = sum(t for (e, _), t in eng8.episode_walk_s.items() if e == 8)
        store.drop_epoch(8)
        snap = reg.snapshot()
    finally:
        obs.set_tracer(None)
        obs.disable()
    rows.append({
        "mode": "obs_idle", "impl": impl, "B": B, "d": d,
        "mesh": list(mesh_shape), "episodes": episodes,
        "walk_workers": walk_workers, "pipeline_depth": depth,
        "walk_s": walk_s, "walk_wait_s": walk_wait_s, "build_s": build_s,
        "stage_s": stage_s, "train_s": train_s, "wall_s": wall_s,
        "samples_per_epoch": n_samples, "dropped": dropped,
        "samples_per_s": n_samples / wall_s,
        "overlap_efficiency": _overlap_efficiency(train_s, wall_s),
        "peak_resident_episodes": store.peak_resident,
        "obs_trace_events": tr_obs.event_count(),
        "obs_metric_names": (len(snap["counters"]) + len(snap["gauges"])
                             + len(snap["histograms"])),
    })

    # ---- coordinator_failover: the remote_walkers row under one mid-epoch
    # coordinator kill + takeover (epochs 9 warm / 10 timed). Right after
    # the first timed episode is consumed, the episode server is killed and
    # a recovering successor starts on the same port: it rebuilds the work
    # queue from the store while the subprocess producers ride out the
    # outage in their jittered backoff loops and reattach. The row records
    # end-to-end samples/s ACROSS the takeover, the takeover wall time, and
    # the successor's time to its first applied chunk — gated against the
    # remote_walkers row (warn when the restart costs >20% throughput).
    coord = RemoteWalkCoordinator(g, wcfg(1), store, num_producers=2,
                                  heartbeat_s=0.5, lease_s=30.0,
                                  mode="process", server_grace_s=60.0)
    coord.start()
    try:
        h9 = coord.epoch_walker()
        h9.start_async(9)
        for ep in range(episodes):                  # warm epoch (untimed)
            pipe.prefetch_window(9, ep, episodes)
            trainer.train_episode(pipe.get(9, ep))
        h9.join()
        store.drop_epoch(9)

        st_before = coord.transport_stats()
        t0 = time.perf_counter()
        # open the timed epoch and kill the coordinator the moment its
        # first chunks are in flight: the epoch is produced almost entirely
        # by the recovering successor, so first_chunk_s measures the real
        # reattach-and-produce recovery latency
        h10 = coord.epoch_walker()
        h10.start_async(10)
        takeover_s = coord.restart_server()
        walk_wait_s = build_s = stage_s = train_s = 0.0
        n_samples = dropped = 0
        for ep in range(episodes):                  # timed epoch + takeover
            pipe.prefetch_window(10, ep, episodes)
            staged = pipe.get(10, ep)
            times = pipe.pop_times(10, ep)
            t = time.perf_counter()
            trainer.train_episode(staged)
            train_s += time.perf_counter() - t
            walk_wait_s += times.get("walk_wait_s", 0.0)
            build_s += times.get("build_s", 0.0)
            stage_s += times.get("stage_s", 0.0)
            n_samples += staged.num_samples
            dropped += staged.dropped
        wall_s = time.perf_counter() - t0
        h10.join()
        st_after = coord.transport_stats()
        fo = coord.failover_stats()
        store.drop_epoch(10)
    finally:
        coord.close()
    pipe.close()
    rows.append({
        "mode": "coordinator_failover", "impl": impl, "B": B, "d": d,
        "mesh": list(mesh_shape), "episodes": episodes,
        "walk_workers": 2, "pipeline_depth": depth,
        "walk_s": 0.0, "walk_wait_s": walk_wait_s, "build_s": build_s,
        "stage_s": stage_s, "train_s": train_s, "wall_s": wall_s,
        "samples_per_epoch": n_samples, "dropped": dropped,
        "samples_per_s": n_samples / wall_s,
        "overlap_efficiency": _overlap_efficiency(train_s, wall_s),
        "peak_resident_episodes": store.peak_resident,
        "takeover_s": takeover_s,
        # None when every episode had already landed before the kill and
        # the successor had nothing left to produce
        "recovery_first_chunk_s": fo.get("first_chunk_s"),
        "failover_recovered_episodes": fo["recovered_episodes"],
        "transport_resend_rate": st_after["resend_rate"],
        "transport_dup_chunks": (st_after["dup_chunks"]
                                 - st_before["dup_chunks"]),
    })
    return rows


def bench_cache(B: int, d: int, mesh_shape, *, nodes: int, samples: int,
                episodes: int, dtype: str, budget_frac: float = CACHE_BUDGET_FRAC,
                seed: int = 0):
    """Tiered hot-row cache vs the fully-resident trainer (``core.tiered``).

    Three trainers run the SAME powerlaw episode schedule (zipf-1.3
    endpoints — the paper's hot-vertex traffic shape) from the same init:

    cache_resident — HybridEmbeddingTrainer, both tables fully in device
                     memory: the throughput ceiling the cache must chase.
    cache_stream   — TieredEmbeddingTrainer with ``hbm_rows=0``: every
                     block's working set streams host→device→host, the
                     bytes floor any cache must beat.
    cache_tiered   — TieredEmbeddingTrainer with ``hbm_rows`` =
                     ``budget_frac`` of the table rows (default 25%): hot
                     rows update in place in the HBM cache, cold rows
                     stream in/out around them.

    All three must produce bitwise-identical embeddings — asserted hard; a
    fast cache that trains different numbers is a correctness regression
    posting a speedup. Timing includes staging/plan/write-back host work
    (each mode pays its real per-episode cost). Gates (warnings): tiered
    hit_rate >= 0.8 on the powerlaw stream, tiered samples/s within 20% of
    resident, and the byte model must show the cache cut host<->device
    traffic vs budget-0 streaming.
    """
    mesh = jax.make_mesh(mesh_shape, ("data", "model"))
    cfg = HybridConfig(dim=d, minibatch=B, negatives=8, subparts=2,
                       neg_pool=2048, impl="ref", dtype=dtype, seed=seed)
    rng = np.random.default_rng(seed)

    def zipf_ids(n):
        # rank-frequency powerlaw over the id space; out-of-range draws
        # clip to the last id (it just becomes one more hot node)
        return (np.minimum(rng.zipf(1.3, size=n), nodes) - 1).astype(np.int64)

    # two untimed warm episodes: the first compiles the block step, the
    # second absorbs the cold-start promotion wave (the cache fills from
    # empty) and its residency-op compiles — the timed episodes then
    # measure the steady state a long-running job sees
    warm = 2
    eps_pairs = [np.stack([zipf_ids(samples), zipf_ids(samples)], axis=1)
                 for _ in range(episodes + warm)]
    # negative pools follow the observed traffic skew (deg^0.75, as the
    # trainers build them) — identical degrees in every mode keeps the
    # negative streams, and therefore the bitwise gate, aligned
    deg = np.bincount(np.concatenate(eps_pairs).ravel(), minlength=nodes)
    budget = int(budget_frac * nodes)

    def run_mode(mode, hbm_rows):
        if hbm_rows is None:
            tr = HybridEmbeddingTrainer(nodes, mesh, cfg, degrees=deg)
        else:
            tr = TieredEmbeddingTrainer(nodes, mesh, cfg, degrees=deg,
                                        hbm_rows=hbm_rows)
        tr.init_embeddings()
        # pin one block shape across episodes so each mode compiles once
        ebs = [build_episode_blocks(p, tr.part, pad_multiple=B)
               for p in eps_pairs]
        cap = max(eb.block_cap for eb in ebs)
        ebs = [build_episode_blocks(p, tr.part, block_cap=cap,
                                    pad_multiple=B) for p in eps_pairs]
        for eb in ebs[:warm]:                # warm episodes: untimed
            tr.train_episode(eb)
        t0 = time.perf_counter()
        loss = 0.0
        for eb in ebs[warm:]:
            loss = tr.train_episode(eb)      # float() inside = full sync
        dt = time.perf_counter() - t0
        n_samples = sum(int(eb.counts.sum()) for eb in ebs[warm:])
        row = {
            "mode": mode, "impl": cfg.impl, "B": B, "d": d,
            "mesh": list(mesh_shape), "nodes": nodes,
            "episodes": episodes, "samples_per_epoch": n_samples // episodes,
            "hbm_rows": hbm_rows, "budget_frac": (None if hbm_rows is None
                                                  else hbm_rows / nodes),
            "samples_per_s": n_samples / dt, "loss": loss,
        }
        if hbm_rows is not None:
            st = tr.cache_stats()
            row.update(hit_rate=st["hit_rate"],
                       hbm_bytes_moved=st["hbm_bytes_moved"],
                       host_bytes_moved=st["host_bytes_moved"],
                       promotions=(st["vertex"]["promotions"]
                                   + st["context"]["promotions"]),
                       evictions=(st["vertex"]["evictions"]
                                  + st["context"]["evictions"]))
        return tr, row

    res_tr, res_row = run_mode("cache_resident", None)
    str_tr, str_row = run_mode("cache_stream", 0)
    tie_tr, tie_row = run_mode("cache_tiered", budget)

    # the load-bearing gate: same numbers, to the bit, in every mode
    v_ref = res_tr.embeddings().view(np.uint8)
    c_ref = res_tr.context_embeddings().view(np.uint8)
    for name, tr in (("cache_stream", str_tr), ("cache_tiered", tie_tr)):
        assert np.array_equal(v_ref, tr.embeddings().view(np.uint8)), (
            "tiered trainer diverged from resident (vertex)", name)
        assert np.array_equal(c_ref, tr.context_embeddings().view(np.uint8)), (
            "tiered trainer diverged from resident (context)", name)

    if tie_row["hit_rate"] < 0.8:
        print(f"WARNING: cache hit rate {tie_row['hit_rate']:.3f} < 0.8 at "
              f"budget {budget}/{nodes} rows under powerlaw traffic")
    if tie_row["samples_per_s"] < 0.8 * res_row["samples_per_s"]:
        print(f"WARNING: tiered throughput >20% below resident: "
              f"{tie_row['samples_per_s']:.1f} < "
              f"{res_row['samples_per_s']:.1f} samples/s")
    if tie_row["host_bytes_moved"] >= str_row["host_bytes_moved"]:
        print(f"WARNING: cache moved no fewer host<->device bytes than "
              f"budget-0 streaming: {tie_row['host_bytes_moved']} >= "
              f"{str_row['host_bytes_moved']}")
    return [res_row, str_row, tie_row]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / single mesh (CI regression canary)")
    ap.add_argument("--episodes", type=int, default=None)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--samples", type=int, default=None)
    ap.add_argument("--impls", default=",".join(IMPLS))
    # f32 default on purpose (NOT the HybridConfig bf16 default): CPU XLA
    # emulates bf16 ~30x slower, which would drown the structural
    # comparison this trajectory exists for; pass --dtype bfloat16 on TPU
    # where it's native
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    # leave a core for the trainer: extra walker threads on a small box just
    # thrash the GIL (the node2vec rejection loop is Python-heavy)
    ap.add_argument("--walk-workers", type=int,
                    default=max(1, min(4, (os.cpu_count() or 2) - 1)))
    ap.add_argument("--pipeline-depth", type=int, default=2)
    ap.add_argument("--dataflow-episodes", type=int, default=None)
    ap.add_argument("--no-dataflow", action="store_true",
                    help="skip the sync-vs-streamed dataflow comparison")
    ap.add_argument("--no-cache", action="store_true",
                    help="skip the tiered-cache comparison")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_episode.json"))
    args = ap.parse_args()

    shapes = SMOKE_SHAPES if args.smoke else FULL_SHAPES
    meshes = MESHES[:1] if args.smoke else MESHES
    nodes = args.nodes or (512 if args.smoke else 2048)
    samples = args.samples or (512 if args.smoke else 8192)
    episodes = args.episodes or (1 if args.smoke else 2)
    impls = tuple(args.impls.split(","))

    results = []
    for mesh_shape in meshes:
        for (B, d) in shapes:
            for impl in impls:
                r = bench_one(impl, B, d, mesh_shape, nodes=nodes,
                              samples=samples, episodes=episodes,
                              dtype=args.dtype)
                results.append(r)
                print(f"mesh={mesh_shape} B={B:4d} d={d:4d} {impl:14s} "
                      f"{r['samples_per_s']:10.1f} samples/s   "
                      f"({r['episode_s']*1e3:8.1f} ms/episode)")

    # the episode-level claim every perf PR must not regress: the fully
    # fused update path at least matches the separate-kernels pallas path
    by_key = {}
    for r in results:
        by_key.setdefault((tuple(r["mesh"]), r["B"], r["d"]), {})[
            r["impl"]] = r["samples_per_s"]
    for key, v in by_key.items():
        if "pallas" in v and "pallas_fused2" in v:
            if v["pallas_fused2"] < v["pallas"]:
                print(f"WARNING: fused2 slower than pallas at {key}: "
                      f"{v['pallas_fused2']:.1f} < {v['pallas']:.1f}")

    # ---- end-to-end dataflow: sync vs streamed over the same epoch
    dataflow_results = []
    if not args.no_dataflow:
        df_shapes = DATAFLOW_SMOKE_SHAPES if args.smoke else DATAFLOW_SHAPES
        # 4+ episodes so the warm epoch is long enough for the next epoch's
        # walker to get ahead (2 episodes end before it even starts)
        df_eps = args.dataflow_episodes or 4
        # below ~2048 nodes an epoch is <100 ms and fixed thread overhead
        # drowns the structural comparison — keep the dataflow rows at a
        # scale where per-stage times mean something, even in smoke
        df_nodes = args.nodes or 2048
        for (B, d) in df_shapes:
            rows = bench_dataflow(
                "ref", B, d, MESHES[0], nodes=df_nodes, episodes=df_eps,
                walk_workers=args.walk_workers, depth=args.pipeline_depth,
                dtype=args.dtype)
            dataflow_results.extend(rows)
            for r in rows:
                print(f"dataflow B={r['B']:4d} d={r['d']:4d} "
                      f"{r['mode']:8s} {r['samples_per_s']:10.1f} samples/s  "
                      f"walk {r['walk_s']:.2f}s build {r['build_s']:.2f}s "
                      f"stage {r['stage_s']:.2f}s train {r['train_s']:.2f}s "
                      f"wall {r['wall_s']:.2f}s "
                      f"overlap {r['overlap_efficiency']:.2f}")
            by_mode = {r["mode"]: r["samples_per_s"] for r in rows}
            if by_mode.get("streamed", 0) < by_mode.get("sync", 0):
                print(f"WARNING: streamed slower than sync at "
                      f"B={B} d={d}: {by_mode['streamed']:.1f} < "
                      f"{by_mode['sync']:.1f}")
            # the robustness PR's perf gate: an installed-but-idle fault
            # plan must cost nothing visible against walk noise
            if by_mode.get("faults_idle", 0) < 0.9 * by_mode.get("streamed", 0):
                print(f"WARNING: idle fault layer costs >10% streamed "
                      f"throughput at B={B} d={d}: "
                      f"{by_mode['faults_idle']:.1f} < "
                      f"{by_mode['streamed']:.1f}")
            # transport gate: subprocess producers over the wire must hold
            # within 15% of in-process streamed throughput (the protocol +
            # assembly overhead budget; resends under chaos are separate)
            if (by_mode.get("remote_walkers", 0)
                    < 0.85 * by_mode.get("streamed", 0)):
                print(f"WARNING: remote-walker transport costs >15% "
                      f"streamed throughput at B={B} d={d}: "
                      f"{by_mode['remote_walkers']:.1f} < "
                      f"{by_mode['streamed']:.1f}")
            # telemetry gate: the fully-instrumented pipeline with the
            # registry + tracer live must hold within 5% of streamed
            if by_mode.get("obs_idle", 0) < 0.95 * by_mode.get("streamed", 0):
                print(f"WARNING: live telemetry costs >5% streamed "
                      f"throughput at B={B} d={d}: "
                      f"{by_mode['obs_idle']:.1f} < "
                      f"{by_mode['streamed']:.1f}")
            # failover gate: one coordinator kill + store-reconstructed
            # takeover mid-epoch must cost <20% of the uninterrupted
            # remote-walker throughput (producer backoff + queue rebuild).
            # Only meaningful when the epoch is long enough to amortize the
            # fixed reattach latency — at --smoke scale a ~0.1s epoch is
            # dominated by it and the ratio says nothing.
            by_wall = {r["mode"]: r["wall_s"] for r in rows}
            if (by_wall.get("remote_walkers", 0) >= 1.0
                    and by_mode.get("coordinator_failover", 0)
                    < 0.80 * by_mode.get("remote_walkers", 0)):
                print(f"WARNING: coordinator failover costs >20% "
                      f"remote-walker throughput at B={B} d={d}: "
                      f"{by_mode['coordinator_failover']:.1f} < "
                      f"{by_mode['remote_walkers']:.1f}")

    # ---- tiered cache: resident vs stream vs hot-row cache, bitwise-gated
    cache_results = []
    if not args.no_cache:
        c_shapes = CACHE_SMOKE_SHAPES if args.smoke else CACHE_SHAPES
        c_nodes = args.nodes or (512 if args.smoke else 2048)
        c_samples = args.samples or (1024 if args.smoke else 8192)
        c_eps = args.episodes or (2 if args.smoke else 3)
        for (B, d) in c_shapes:
            rows = bench_cache(B, d, MESHES[0], nodes=c_nodes,
                               samples=c_samples, episodes=c_eps,
                               dtype=args.dtype)
            cache_results.extend(rows)
            for r in rows:
                extra = ""
                if r["hbm_rows"] is not None:
                    extra = (f"  hit_rate {r['hit_rate']:.3f} "
                             f"hbm_bytes {r['hbm_bytes_moved']} "
                             f"host_bytes {r['host_bytes_moved']}")
                print(f"cache    B={r['B']:4d} d={r['d']:4d} "
                      f"{r['mode']:14s} {r['samples_per_s']:10.1f} "
                      f"samples/s{extra}")

    run = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "interpret_mode": ops.interpret_mode(),
        "dtype": args.dtype,
        "nodes": nodes,
        "samples": samples,
        "episodes_timed": episodes,
        "note": ("end-to-end episode step (ring rotation + sub-part "
                 "pipeline + minibatch scan); interpret-mode pallas on "
                 "CPU — compare across PRs on the same container, "
                 "absolute numbers on TPU"),
        "results": results,
        "dataflow_results": dataflow_results,
        "cache_results": cache_results,
    }
    n = append_run(args.out, "sgns_episode", run)
    print(f"wrote {os.path.abspath(args.out)} "
          f"(run {n}, {len(results)} rows)")


if __name__ == "__main__":
    main()

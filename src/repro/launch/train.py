"""Production training launcher.

Two modes, selected by --arch:

* ``tencent-embedding`` — the paper's system: decoupled walk engine (async,
  one epoch ahead), episode pipeline, hybrid model-data parallel episode
  step, periodic checkpoints, link-prediction eval.
* any LM arch id — config-system LM training on the synthetic token
  pipeline with the same sharding rules as the production dry-run.

    PYTHONPATH=src python -m repro.launch.train --arch tencent-embedding \
        --epochs 10 --nodes 20000
    PYTHONPATH=src python -m repro.launch.train --arch phi3.5-moe-42b-a6.6b \
        --reduced --steps 100

Scale note: full (non-``--reduced``) LM configs need the real pod — on this
container they are exercised via ``repro.launch.dryrun``.

Fault tolerance (embedding mode): ``--ckpt-every N`` writes an atomic,
checksummed resume checkpoint (tables + mid-epoch cursor) every N episodes;
``--resume`` continues from it, bitwise-identical to an uninterrupted run.
``--inject SPEC`` installs a deterministic fault plan (crash/delay/corrupt
at named sites — see ``repro.runtime.faults``) for chaos testing;
``--stall-timeout-s`` bounds how long any stage may block without store
progress before failing with diagnostics instead of hanging.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import numpy as np


def train_embedding(args, devices=None):
    """The embedding trainer on a (1, len(devices)) mesh (default: every
    device JAX finds). Returns (final-epoch AUC, the trainer)."""
    import jax
    from repro import obs
    from repro.configs.tencent_embedding import SMALL
    from repro.core import (EpisodePipeline, HybridConfig,
                            HybridEmbeddingTrainer, TieredEmbeddingTrainer)
    from repro.core import eval as ev
    from repro.graph.csr import build_csr
    from repro.graph.generators import powerlaw_graph
    from repro.runtime import (FaultPlan, clear_plan, install_plan)
    from repro.train.checkpoint import load_arrays
    from repro.walk import (DiskSampleStore, MemorySampleStore,
                            RemoteWalkCoordinator, WalkConfig, WalkEngine)

    # flag validation first: fail before any graph/trainer work happens
    if args.coordinator_resume and args.remote_walkers <= 0:
        raise SystemExit("--coordinator-resume requires --remote-walkers")
    if args.coordinator_resume and not args.resume:
        raise SystemExit("--coordinator-resume requires --resume (the "
                         "trainer cursor tells the server which epochs to "
                         "re-submit)")

    # telemetry is opt-in (disabled-by-default hot paths are single None
    # checks); enable BEFORE building the dataflow so components register
    # their snapshot sources with the live registry
    writer = obs_tracer = None
    if args.metrics_dir or args.trace:
        reg = obs.enable()
        if args.trace:
            obs_tracer = obs.Tracer()
            obs.set_tracer(obs_tracer)
        if args.metrics_dir:
            writer = obs.MetricsWriter(reg, args.metrics_dir,
                                       interval_s=args.metrics_interval_s)
            print(f"metrics -> {writer.path} "
                  f"(every {writer.interval_s:g}s)")

    if args.graph:
        from repro.graph.io import load_edge_list
        g_full = load_edge_list(args.graph)
    elif args.graph_kind == "sbm":
        from repro.graph.generators import sbm_graph
        # candidate-pair budget must scale with n or large graphs come out
        # mostly degree-0 (expected edges ~ rounds * batch * 0.0075)
        g_full = sbm_graph(args.nodes, rounds=max(30, args.nodes // 40),
                           seed=args.seed)
    else:
        g_full = powerlaw_graph(args.nodes, 5, seed=args.seed)
    train_e, test_e = ev.split_edges(g_full, 0.03, seed=args.seed)
    g = build_csr(train_e, g_full.num_nodes, symmetrize=False, dedup=False)
    neg_e = ev.sample_negative_pairs(g_full, len(test_e), seed=args.seed + 1)
    print(f"graph: {g.num_nodes} nodes / {g.num_edges} train edges; "
          f"{len(test_e)} held out")

    devices = list(jax.devices() if devices is None else devices)
    mesh = jax.make_mesh((1, len(devices)), ("data", "model"),
                         devices=devices)
    cfg_kw = {}
    if args.dtype is not None:          # None -> HybridConfig default (bf16)
        cfg_kw["dtype"] = args.dtype
    cfg = HybridConfig(dim=args.dim,
                       minibatch=args.minibatch or SMALL.minibatch,
                       negatives=args.negatives or SMALL.negatives,
                       subparts=args.subparts,
                       neg_pool=args.neg_pool or SMALL.neg_pool,
                       lr=args.lr, seed=args.seed,
                       impl=args.impl, block_b=args.block_b, **cfg_kw)
    if args.hbm_rows is not None:
        # tiered tables: host-RAM master + HBM cache of --hbm-rows hot rows;
        # bitwise identical to the resident trainer at any budget, so the
        # artifacts (and --resume) are interchangeable between the two
        trainer = TieredEmbeddingTrainer(
            g.num_nodes, mesh, cfg, degrees=g.degrees(),
            hbm_rows=args.hbm_rows, policy=args.cache_policy,
            spill_dir=(os.path.join(args.out_dir, "master_spill")
                       if args.cache_spill else None))
        print(f"tiered tables: hbm_rows={args.hbm_rows} "
              f"policy={args.cache_policy}"
              + (" (disk-backed master)" if args.cache_spill else ""))
    else:
        trainer = HybridEmbeddingTrainer(g.num_nodes, mesh, cfg,
                                         degrees=g.degrees())

    # crash-resume: restore tables + (epoch, episode) cursor from the last
    # resume checkpoint; the remaining episodes replay bitwise-identically
    # (per-episode RNG streams are keyed by the config, never by history)
    start_epoch, start_episode = 0, 0
    resume_path = os.path.join(args.out_dir, "resume.npz")
    if args.resume:
        data, _ = load_arrays(resume_path)   # verifies the crc manifest
        start_epoch, start_episode = (int(v) for v in data["__cursor__"])
        trainer.set_embeddings(data["vertex"], data["context"])
        print(f"resume <- {resume_path} @ epoch {start_epoch} "
              f"episode {start_episode}")
        if start_epoch >= args.epochs:
            print("resume cursor is past the final epoch; nothing to do")
            return None, trainer
    else:
        trainer.init_embeddings()

    # bounded store: the walker can run at most store_depth episodes ahead of
    # the pipeline's drops, so peak sample memory is O(depth · episode)
    store_depth = args.store_depth or args.pipeline_depth + 1
    store_kw = {}
    if args.stall_timeout_s is not None:
        store_kw["stall_timeout_s"] = (args.stall_timeout_s
                                       if args.stall_timeout_s > 0 else None)
    if args.store == "disk":
        # fresh: this run produces NEW walks — stale episode files or .done
        # markers from a previous run in the same dir would race it. With
        # --keep-samples the files are the artifact the user asked to keep,
        # so never delete them — warn instead if any are present.
        sample_dir = args.store_dir or os.path.join(args.out_dir, "samples")
        if args.keep_samples and os.path.isdir(sample_dir) and any(
                f.startswith("epoch") and f.endswith((".npy", ".done"))
                for f in os.listdir(sample_dir)):
            print(f"WARNING: {sample_dir} already holds episode files from a "
                  f"previous run; this run's epochs will overwrite same-"
                  f"numbered files and may race stale .done markers — use a "
                  f"fresh --store-dir to keep both artifacts")
        # --coordinator-resume reconstructs the episode server's state FROM
        # the store, so a resuming run must never wipe the surviving files
        keep_files = args.keep_samples or args.coordinator_resume
        store = DiskSampleStore(sample_dir, depth=store_depth,
                                keep=args.keep_samples,
                                fresh=not keep_files, **store_kw)
    else:
        store = MemorySampleStore(depth=store_depth, **store_kw)
    wcfg = WalkConfig(walk_length=10, window=5, episodes=args.episodes,
                      seed=args.seed, workers=args.walk_workers)
    # rewalk: a never-started engine whose episode_pairs regenerates any
    # episode bitwise — the corrupt-episode-file recovery path
    pipe = EpisodePipeline(store, trainer.part, pad_multiple=cfg.minibatch,
                           block_cap=args.block_cap,
                           depth=args.pipeline_depth,
                           stage_fn=trainer.stage_blocks, drop_consumed=True,
                           rewalk=WalkEngine(g, wcfg, store).episode_pairs)
    os.makedirs(args.out_dir, exist_ok=True)

    plan = None
    if args.inject:
        plan = FaultPlan(args.inject)
        install_plan(plan)
        print(f"fault plan: {args.inject}")

    # walker factory: in-process threaded engine, or — with
    # --remote-walkers N — subprocess producers shipping episode chunks over
    # the checksummed socket transport (same RNG keys, bitwise-identical
    # sample stream, and real parallelism outside the GIL)
    coord = None
    if args.remote_walkers > 0:
        coord = RemoteWalkCoordinator(
            g, wcfg, store, num_producers=args.remote_walkers,
            heartbeat_s=args.heartbeat_s, lease_s=args.lease_s,
            inject_specs=args.inject, port=args.coordinator_port,
            recover=args.coordinator_resume,
            server_grace_s=args.server_grace_s)
        coord.start()
        mk_walker = coord.epoch_walker
        print(f"remote walkers: {args.remote_walkers} subprocess "
              f"producer(s) @ {coord.server.address[0]}:"
              f"{coord.server.address[1]} (heartbeat {args.heartbeat_s}s, "
              f"lease {args.lease_s}s, grace {args.server_grace_s}s)")
        if args.coordinator_resume:
            print(f"coordinator takeover: recovering server on port "
                  f"{coord.server.address[1]} reconstructs epoch state "
                  f"from the {args.store} store")
    else:
        def mk_walker():
            return WalkEngine(g, wcfg, store)

    engine = mk_walker()
    engine.start_async(start_epoch)
    try:
        auc = _train_embedding_epochs(args, cfg, trainer, engine, store,
                                      pipe, test_e, neg_e,
                                      mk_walker=mk_walker,
                                      start_epoch=start_epoch,
                                      start_episode=start_episode)
        if args.hbm_rows is not None:
            st = trainer.cache_stats()
            print(f"cache: hit_rate {st['hit_rate']:.3f} "
                  f"hbm_bytes {st['hbm_bytes_moved']} "
                  f"host_bytes {st['host_bytes_moved']} "
                  f"promotions {st['vertex']['promotions']}"
                  f"+{st['context']['promotions']} "
                  f"evictions {st['vertex']['evictions']}"
                  f"+{st['context']['evictions']}")
        if coord is not None:
            st = coord.transport_stats()
            print(f"transport: {st['frames_recv']} frames / "
                  f"{st['bytes_recv']} bytes received, "
                  f"{st['dup_chunks']} duplicate chunk(s) discarded")
            fo = coord.failover_stats()
            if fo["takeovers"] or fo["recovered_episodes"]:
                print(f"failover: {fo['takeovers']} takeover(s), "
                      f"{fo['recovered_episodes']} episode(s) recovered "
                      f"from the store without re-production")
    except BaseException as e:
        # leave a machine-readable dump for CI artifact upload on ANY fatal
        # exit — not just StoreStalled/TransportError, so a chaos leg that
        # dies on an unexpected error still produces an artifact: what
        # failed, what was resident, which hosts were (not) beating, and
        # the live metrics snapshot when telemetry is on
        _dump_diagnostics(args.out_dir, e, coord)
        raise
    finally:
        # always drain the prefetch workers: an in-flight build racing
        # interpreter teardown (e.g. after a KeyboardInterrupt) can crash
        # inside numpy after module unload
        pipe.close()
        if coord is not None:
            coord.close()
        if plan is not None:
            clear_plan()
        if writer is not None:
            writer.close()
            print(f"metrics summary -> {writer.summary_path}")
        if obs_tracer is not None:
            obs.set_tracer(None)
            obs_tracer.save(args.trace)
            print(f"trace -> {args.trace} "
                  f"({obs_tracer.event_count()} events, "
                  f"{obs_tracer.dropped} dropped)")
        if writer is not None or obs_tracer is not None:
            obs.disable()
    return auc, trainer


def _dump_diagnostics(out_dir, err, coord):
    """OUT_DIR/diagnostics.json: the stall/transport failure in machine-
    readable form (CI uploads it as an artifact on chaos-leg failure)."""
    import json
    from repro import obs
    from repro.runtime import StoreStalled

    diag = {"error": type(err).__name__, "message": str(err)}
    if isinstance(err, StoreStalled):
        diag.update({"op": err.op, "key": err.key,
                     "resident": sorted(err.resident),
                     "producer_alive": err.producer_alive,
                     "producer_info": err.producer_info,
                     "waited_s": err.waited_s})
    if coord is not None:
        diag["host_health"] = coord.server.health.snapshot()
        diag["transport"] = coord.transport_stats()
        diag["failover"] = coord.failover_stats()
    reg = obs.active()
    if reg is not None:          # fold the live registry into the dump
        diag["metrics"] = reg.snapshot()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "diagnostics.json")
    with open(path, "w") as f:
        json.dump(diag, f, indent=2, default=str)
    print(f"diagnostics -> {path}")


def _write_resume(args, trainer, epoch, next_ep):
    """Atomic resume checkpoint: tables + checksummed (epoch, episode)
    cursor. ``next_ep`` is the NEXT episode to train; a full epoch
    normalizes to (epoch+1, 0) so resume never re-enters a finished epoch."""
    from repro.train.checkpoint import save_checkpoint

    cur = (epoch + 1, 0) if next_ep >= args.episodes else (epoch, next_ep)
    path = os.path.join(args.out_dir, "resume.npz")
    save_checkpoint(path,
                    {"vertex": trainer.embeddings(),
                     "context": trainer.context_embeddings()},
                    step=epoch * args.episodes + next_ep,
                    extra={"__cursor__": np.asarray(cur, np.int64)})
    return path


def _train_embedding_epochs(args, cfg, trainer, engine, store, pipe,
                            test_e, neg_e, *, mk_walker,
                            start_epoch=0, start_episode=0):
    from repro.core import eval as ev
    from repro.obs import counter_add, observe, span
    from repro.obs import trace as _trace
    from repro.runtime import fault_point
    from repro.train.checkpoint import save_checkpoint

    auc = 0.0
    ckpt_every = max(0, args.ckpt_every)
    for epoch in range(start_epoch, args.epochs):
        # streamed: do NOT join — training starts as soon as episode 0 lands
        # in the bounded store; the walker streams the rest concurrently
        tr = _trace.tracer()
        t_epoch_us = tr.now_us() if tr is not None else 0.0
        t0 = time.perf_counter()
        nxt = None
        losses = []
        dropped = 0
        # resuming mid-epoch: episodes before the cursor were already trained
        # into the restored tables — drain them from the walker's stream
        # without training so the bounded store keeps flowing
        skip_until = start_episode if epoch == start_epoch else 0
        try:
            for ep in range(args.episodes):
                fault_point("train.episode", (epoch, ep))
                if ep < skip_until:
                    store.get(epoch, ep)
                    store.drop(epoch, ep)
                    continue
                pipe.prefetch_window(epoch, ep, args.episodes)
                eb = pipe.get(epoch, ep)
                dropped += eb.dropped
                t_ep = time.perf_counter()
                with span("train_episode", "train",
                          {"epoch": epoch, "episode": ep}):
                    losses.append(trainer.train_episode(
                        eb, lr=cfg.lr * max(1 - epoch / args.epochs, 0.05)))
                observe("train.episode_s", time.perf_counter() - t_ep)
                counter_add("train.episodes")
                # paper: walks for e+1 overlap training e — launch them the
                # moment this epoch's walker finishes (backpressure-paced)
                if nxt is None and epoch + 1 < args.epochs and engine.finished():
                    engine.join()        # surfaces walker errors
                    nxt = mk_walker()
                    nxt.start_async(epoch + 1)
                if ckpt_every and (epoch * args.episodes + ep + 1) % ckpt_every == 0:
                    path = _write_resume(args, trainer, epoch, ep + 1)
                    print(f"  resume checkpoint -> {path} "
                          f"@ ({epoch}, {ep + 1})")
        except Exception:
            # a dead walker finishes the epoch with episodes missing, which
            # surfaces here as a KeyError — join to re-raise its real error.
            # abandon() first: with nobody left to drain the bounded store, a
            # HEALTHY walker could be blocked in put() and join would hang
            store.abandon()
            engine.join()
            raise
        engine.join()
        if nxt is None and epoch + 1 < args.epochs:
            nxt = mk_walker()
            nxt.start_async(epoch + 1)
        store.drop_epoch(epoch)
        with span("eval", "train", {"epoch": epoch}):
            V = trainer.embeddings()
            Vn = V / (np.linalg.norm(V, axis=1, keepdims=True) + 1e-9)
            auc = ev.auc_score(
                np.einsum("ij,ij->i", Vn[test_e[:, 0]], Vn[test_e[:, 1]]),
                np.einsum("ij,ij->i", Vn[neg_e[:, 0]], Vn[neg_e[:, 1]]))
        if tr is not None:
            tr.add_span("epoch", "train", t_epoch_us, tr.now_us(),
                        {"epoch": epoch, "auc": round(float(auc), 4)})
        loss_s = f"{np.mean(losses):.4f}" if losses else "--"
        print(f"epoch {epoch:3d} loss {loss_s} AUC {auc:.4f} "
              f"({time.perf_counter()-t0:.1f}s)"
              + (f" [{len(pipe.recovered)} episode(s) re-walked]"
                 if pipe.recovered else "")
              + (f" [{dropped} samples dropped past --block-cap]"
                 if dropped else ""))
        if epoch + 1 < args.epochs:
            engine = nxt
        if epoch + 1 == args.epochs:
            path = os.path.join(args.out_dir, f"embeddings_{epoch+1}.npz")
            save_checkpoint(path, {"vertex": V,
                                   "context": trainer.context_embeddings()},
                            step=epoch + 1)
            print(f"  checkpoint -> {path}")
    if args.min_auc is not None and auc < args.min_auc:
        raise SystemExit(
            f"final AUC {auc:.4f} below --min-auc {args.min_auc}")
    return auc


def train_lm(args):
    import jax
    import jax.numpy as jnp
    from repro import configs as cfgs
    from repro.data.tokens import TokenPipeline
    from repro.launch.mesh import data_axes_of, make_host_mesh
    from repro.models import transformer as tfm
    from repro.models.common import count_params
    from repro.sharding.specs import param_shardings
    from repro.train.checkpoint import save_checkpoint
    from repro.train.train_step import make_train_step

    cfg = cfgs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(layers=args.layers, d_model=args.d_model, experts=4)
        cfg = dataclasses.replace(
            cfg, vocab_size=min(cfg.vocab_size, 8192), train_microbatches=1)
    params = tfm.init_params(jax.random.PRNGKey(args.seed), cfg)
    print(f"{args.arch}: {count_params(params)/1e6:.1f}M params")

    mesh = make_host_mesh()
    data_axes = data_axes_of(mesh)
    params = jax.device_put(params, param_shardings(params, mesh))
    step_fn, opt = make_train_step(cfg, mesh=mesh, data_axes=data_axes,
                                   lr=args.lr)
    opt_state = jax.device_put(
        opt.init(params),
        param_shardings(jax.eval_shape(opt.init, params), mesh))
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
    pipe = TokenPipeline(cfg, args.batch, args.seq, seed=args.seed)
    os.makedirs(args.out_dir, exist_ok=True)
    with mesh:
        for step in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
            batch.setdefault("positions", jnp.broadcast_to(
                jnp.arange(args.seq, dtype=jnp.int32),
                batch["tokens"].shape))
            params, opt_state, m = jit_step(params, opt_state,
                                            jnp.int32(step), batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(m['loss']):.4f} "
                      f"grad_norm {float(m['grad_norm']):.2f}")
        if args.save:
            save_checkpoint(os.path.join(args.out_dir, "lm_final.npz"),
                            params, step=args.steps)
    pipe.close()


def main(argv=None, *, devices=None):
    """CLI entry. ``devices`` (embedding mode) pins the training mesh to
    those devices instead of every device JAX finds; embedding mode returns
    (final-epoch AUC, the trainer)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tencent-embedding")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default="/tmp/repro_train")
    ap.add_argument("--lr", type=float, default=None)
    # embedding mode
    ap.add_argument("--graph", default=None, help="edge-list file (.npy/.txt)")
    ap.add_argument("--graph-kind", default="powerlaw",
                    choices=["powerlaw", "sbm"],
                    help="synthetic graph when no --graph file: powerlaw "
                         "(paper's social-network topology) or sbm (planted "
                         "communities — use when gating on --min-auc)")
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=96)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--episodes", type=int, default=4)
    ap.add_argument("--subparts", type=int, default=4)
    ap.add_argument("--minibatch", type=int, default=None,
                    help="shared-negative group rows (default: SMALL config)")
    ap.add_argument("--negatives", type=int, default=None,
                    help="shared negatives per minibatch (default: SMALL)")
    ap.add_argument("--neg-pool", type=int, default=None,
                    help="per-device negative pool size (default: SMALL)")
    # literal copy of kernels.ops.STEP_IMPLS: importing ops here would pull
    # jax into --help / arg-error paths (this module defers jax on purpose);
    # a stale copy fails loudly anyway (ops validates impl at trace time)
    ap.add_argument("--impl", default="ref",
                    choices=["ref", "pallas", "pallas_fused",
                             "pallas_fused2"],
                    help="kernels.ops execution path for the episode step")
    ap.add_argument("--dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="embedding-table dtype (default: the HybridConfig "
                         "default, bfloat16; pass float32 for the "
                         "paper-faithful tables)")
    ap.add_argument("--block-b", type=int, default=None,
                    help="pin the fused-kernel tile size (default: "
                         "VMEM-aware autotune in kernels.ops)")
    ap.add_argument("--hbm-rows", type=int, default=None,
                    help="train through tiered tables: host-RAM master + an "
                         "HBM cache of this many hot rows per table "
                         "(core.tiered; bitwise identical to the resident "
                         "trainer at any budget). Default: fully resident "
                         "shards")
    ap.add_argument("--cache-policy", default="freq",
                    choices=["freq", "lru"],
                    help="hot-row promotion policy for --hbm-rows: freq "
                         "(cumulative access count) or lru (most recent "
                         "episode touch); ties break to the smaller row id")
    ap.add_argument("--cache-spill", action="store_true",
                    help="with --hbm-rows: back the master tables with "
                         "np.memmap files under OUT_DIR/master_spill "
                         "(tables beyond host RAM)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="episodes between atomic resume checkpoints "
                         "(OUT_DIR/resume.npz: tables + cursor, crc-"
                         "manifested; 0 = final artifact only)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from OUT_DIR/resume.npz — restores tables "
                         "+ (epoch, episode) cursor and replays the rest of "
                         "the run bitwise-identically to an uninterrupted "
                         "one (per-episode RNG streams are config-keyed)")
    ap.add_argument("--inject", action="append", default=[], metavar="SPEC",
                    help="deterministic fault spec, repeatable: "
                         "site:kind[:opt=val]... e.g. walk.chunk:crash:at=5, "
                         "train.episode:crash:key=6/1, "
                         "disk.write:corrupt:at=0 (see repro.runtime.faults)")
    ap.add_argument("--stall-timeout-s", type=float, default=None,
                    help="seconds without sample-store progress before a "
                         "blocked stage fails with StoreStalled diagnostics "
                         "(default 600; <=0 disables the deadline — producer "
                         "liveness detection still applies)")
    # streaming dataflow knobs
    ap.add_argument("--walk-workers", type=int, default=2,
                    help="walk-engine chunk worker threads (1 = inline; the "
                         "sample stream is identical for any value)")
    ap.add_argument("--remote-walkers", type=int, default=0,
                    help="run N walk producers as subprocesses shipping "
                         "episode chunks over the checksummed socket "
                         "transport (0 = in-process threads). The sample "
                         "stream is bitwise-identical either way; "
                         "subprocesses walk outside the GIL and survive "
                         "producer crashes via lease-based reassignment")
    ap.add_argument("--coordinator-resume", action="store_true",
                    help="with --resume and --remote-walkers: build the "
                         "episode server in recovery mode — it reconstructs "
                         "the work queue from the sample store (complete "
                         "episodes skipped, partial ones replayed via the "
                         "RNG keys) instead of starting the epoch from 0")
    ap.add_argument("--coordinator-port", type=int, default=0,
                    help="fixed listen port for the episode server (default "
                         "0 = ephemeral); a restarted coordinator must "
                         "reuse its predecessor's port so producers in "
                         "their reconnect-backoff loop can reattach")
    ap.add_argument("--server-grace-s", type=float, default=30.0,
                    help="producer-side outage budget: how long a producer "
                         "keeps retrying (jittered capped backoff) against "
                         "an unreachable episode server before giving up")
    ap.add_argument("--heartbeat-s", type=float, default=1.0,
                    help="remote producer heartbeat interval")
    ap.add_argument("--lease-s", type=float, default=10.0,
                    help="seconds without a heartbeat before a remote "
                         "producer is declared dead and its unfinished "
                         "episodes are reassigned to survivors")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="episodes in flight through the fetch/build/stage "
                         "pipeline")
    ap.add_argument("--store", default="memory", choices=["memory", "disk"],
                    help="sample store backend (disk = the paper's "
                         "offline/slow-cluster mode: episode .npy files)")
    ap.add_argument("--store-dir", default=None,
                    help="disk-store directory (default: OUT_DIR/samples)")
    ap.add_argument("--store-depth", type=int, default=None,
                    help="bounded-store capacity in undrained episodes "
                         "(default: pipeline depth + 1)")
    ap.add_argument("--keep-samples", action="store_true",
                    help="disk store: keep episode files after consumption "
                         "(the offline artifact) instead of deleting them")
    ap.add_argument("--min-auc", type=float, default=None,
                    help="exit non-zero if the final epoch's link-prediction "
                         "AUC is below this (CI sanity gate)")
    # telemetry (repro.obs; disabled unless one of these is given)
    ap.add_argument("--metrics-dir", default=None,
                    help="enable the telemetry registry and append periodic "
                         "snapshots to DIR/metrics.jsonl (+ final "
                         "metrics_summary.json at exit)")
    ap.add_argument("--metrics-interval-s", type=float, default=5.0,
                    help="seconds between metrics.jsonl snapshots")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record a span timeline of the walk/build/stage/"
                         "train pipeline and write Chrome trace-event JSON "
                         "to FILE (load in ui.perfetto.dev)")
    ap.add_argument("--block-cap", type=int, default=None,
                    help="pin every episode's per-cell block capacity (rounds "
                         "up to the minibatch pad): episodes then share one "
                         "compiled step instead of re-lowering per bmax — "
                         "set it above the expected max cell count or "
                         "overflow samples are dropped (default: per-episode "
                         "bmax, recompiles when it changes)")
    # lm mode
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--save", action="store_true")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.arch == "tencent-embedding":
        args.lr = args.lr if args.lr is not None else 0.025
        return train_embedding(args, devices=devices)
    else:
        args.lr = args.lr if args.lr is not None else 3e-4
        train_lm(args)


if __name__ == "__main__":
    main()

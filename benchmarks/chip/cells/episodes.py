"""Training cells: pre-walked episodes replayed through the trainer (mix
kind ``episodes``).

Set-up builds one ``HybridEmbeddingTrainer`` with tables made on the
device from the seed, stages the first episodes on a worker thread through
``stage_blocks`` as the window does, and drives the same object through
three steps (episodes) of ``train_episode``: their losses, the norm of
each table's change after the first (over the learning rate: the first
gradient as plain SGD applies it) and after the third are what the
reference is compared with. The window then goes on with the same object
and the same feed. After the window the program's state is freed and the
reference retraces those three steps.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import queue
import sys
import threading
import time

import numpy as np

import common
import compare
import flops_bytes
import graphgen
import peaks
import reference
import tables
import traffic
import devtrace

SETUP_STEPS = 3


@dataclasses.dataclass
class Setup:
    cfg: dict
    traffic: dict
    chips: int
    num_nodes: int
    rows_pad: int           # padded rows of one shard
    pairs: list             # the mix's episodes, (samples, 2) int32 each
    pool: np.ndarray        # (chips, neg_pool) shard-local context rows
    table_seed: int
    trainer_seed: int

    def table_fn_args(self, dtype=None):
        return (self.rows_pad * self.chips, self.num_nodes, self.cfg["dim"],
                dtype or self.cfg["dtype"])


def prepare(ctx: dict, seed: int) -> Setup:
    """Host-side inputs of one run: graph, episodes and negative pool."""
    cfg, tr = ctx["config"], ctx["traffic"]
    chips = ctx["cell"]["chips"]
    n = cfg["num_nodes"] * chips
    indptr, indices = graphgen.powerlaw_graph(
        n, cfg["graph"]["m_per_node"], cfg["graph"]["graph_seed"])
    pairs = traffic.episodes(
        indptr, indices, tr, chips, common.rng(seed, common.STREAM_WALKS))
    rows_shard = -(-n // chips)
    rows_pad = -(-rows_shard // cfg["subparts"]) * cfg["subparts"]
    deg = np.diff(indptr).astype(np.float64)
    r = common.rng(seed, common.STREAM_POOL)
    pool = np.zeros((chips, cfg["neg_pool"]), np.int32)
    for s in range(chips):
        w = np.maximum(deg[s * rows_pad:min((s + 1) * rows_pad, n)] ** 0.75,
                       1e-12)
        pool[s] = r.choice(w.size, size=cfg["neg_pool"], p=w / w.sum())
    return Setup(cfg=cfg, traffic=tr, chips=chips, num_nodes=n,
                 rows_pad=rows_pad, pairs=pairs, pool=pool,
                 table_seed=common.seed31(seed, common.STREAM_TABLES),
                 trainer_seed=common.seed31(seed, common.STREAM_TRAINER))


def make_trainer(S: Setup, devices):
    """The system under test, with the seed's tables on the device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core import HybridConfig, HybridEmbeddingTrainer
    c = S.cfg
    mesh = jax.make_mesh((1, S.chips), ("data", "model"),
                         devices=devices[:S.chips])
    hc = HybridConfig(dim=c["dim"], lr=c["lr"], negatives=c["negatives"],
                      minibatch=c["minibatch"], subparts=c["subparts"],
                      neg_pool=c["neg_pool"], impl=c["impl"],
                      dtype=c["dtype"], seed=S.trainer_seed)
    trainer = HybridEmbeddingTrainer(S.num_nodes, mesh, hc)
    assert trainer.part.padded_rows_per_shard == S.rows_pad
    trainer.pool = S.pool
    sharding = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
    trainer.vert, trainer.ctx = tables.make(
        2, *S.table_fn_args(), sharding=sharding, seed=np.int32(S.table_seed))
    return trainer


def build_blocks(S: Setup, trainer) -> list:
    from repro.core import build_episode_blocks
    return [build_episode_blocks(p, trainer.part,
                                 block_cap=S.traffic["block_cap"],
                                 pad_multiple=S.cfg["minibatch"])
            for p in S.pairs]


class Feed:
    """Stages episodes ``start, start+1, ...`` (cycled) on a worker thread."""

    def __init__(self, trainer, ebs, depth: int = 2):
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err = None

        def work():
            i = 0
            try:
                while not self._stop.is_set():
                    staged = trainer.stage_blocks(ebs[i % len(ebs)])
                    while not self._stop.is_set():
                        try:
                            self._q.put((i, staged), timeout=0.1)
                            break
                        except queue.Full:
                            pass
                    i += 1
            except Exception as e:          # noqa: BLE001 -- re-raised
                self._err = e
                self._q.put(None)
        self._thread = threading.Thread(target=work, name="bench-feed",
                                        daemon=True)
        self._thread.start()

    def next(self):
        """(episode index, staged blocks, seconds waited)."""
        t = time.perf_counter()
        item = self._q.get()
        if item is None:
            raise RuntimeError("feed failed") from self._err
        return item[0], item[1], time.perf_counter() - t

    def close(self):
        self._stop.set()
        while self._thread.is_alive():
            try:
                self._q.get(timeout=0.05)
            except queue.Empty:
                pass
        self._thread.join()


def program_steps(S: Setup, trainer, feed) -> dict:
    """Steps 1-3 through the window's own call and feed, summarised."""
    lr = S.cfg["lr"]
    norm = [tables.change_norm_fn(t, *S.table_fn_args())
            for t in range(2)]
    seed = np.int32(S.table_seed)
    out = {"loss": []}
    for step in range(SETUP_STEPS):
        _, staged, _ = feed.next()
        out["loss"].append(float(trainer.train_episode(staged)))
        if step == 0:
            out["grad"] = {"vert": float(norm[0](trainer.vert, seed)) / lr,
                           "ctx": float(norm[1](trainer.ctx, seed)) / lr}
    out["change"] = {"vert": float(norm[0](trainer.vert, seed)),
                     "ctx": float(norm[1](trainer.ctx, seed))}
    return out


def reference_steps(S: Setup, *, precision: str = "highest",
                    dtype: str | None = None,
                    half_batch: bool = False) -> dict:
    """Steps 1-3 of the plain reference (or a control or a fault of it)."""
    c = S.cfg
    dtype = dtype or c["dtype"]
    args = S.table_fn_args(dtype)
    vert, ctx = tables.make(2, *args, seed=np.int32(S.table_seed))
    if dtype not in ("float32", "bfloat16"):
        vert, ctx = vert.astype("float32"), ctx.astype("float32")
    norm = [tables.change_norm_fn(t, *args) for t in range(2)]
    seed = np.int32(S.table_seed)
    out = {"loss": []}
    for step in range(SETUP_STEPS):
        sched = reference.schedule(
            S.pairs[step % len(S.pairs)], num_nodes=S.num_nodes,
            chips=S.chips, subparts=c["subparts"], minibatch=c["minibatch"],
            block_cap=S.traffic["block_cap"], half_batch=half_batch)
        vert, ctx, loss = reference.sgns_episode(
            vert, ctx, S.pool, sched, seed=S.trainer_seed, lr=c["lr"],
            negatives=c["negatives"], block_cap=S.traffic["block_cap"],
            minibatch=c["minibatch"], subparts=c["subparts"],
            rows_pad=S.rows_pad, chips=S.chips, dtype=dtype,
            precision=precision)
        out["loss"].append(loss)
        if step == 0:
            out["grad"] = {"vert": float(norm[0](vert, seed)) / c["lr"],
                           "ctx": float(norm[1](ctx, seed)) / c["lr"]}
    out["change"] = {"vert": float(norm[0](vert, seed)),
                     "ctx": float(norm[1](ctx, seed))}
    return out


def calibrate(ctx: dict, seed: int, devices, seconds: float) -> dict:
    """The program's three set-up steps (as a run makes them), the control
    (the reference in the configuration's ``control`` precision) and the
    fault "half of every minibatch left out, the mean taken over the
    rest", planted in the reference, each against the reference. The fault
    "a step that returns its state unchanged" reads 1 on ``change_gap`` by
    construction and needs no run."""
    S = prepare(ctx, seed)
    trainer = make_trainer(S, devices)
    feed = Feed(trainer, build_blocks(S, trainer))
    prog = program_steps(S, trainer, feed)
    feed.close()
    del trainer, feed
    gc.collect()
    ref = reference_steps(S)
    ctl = ctx["config"]["control"]
    control = reference_steps(S, dtype=ctl["dtype"],
                              precision=ctl["precision"])
    return {"program": compare.train_numbers(prog, ref),
            "control": compare.train_numbers(control, ref),
            "half_batch": compare.train_numbers(
                reference_steps(S, half_batch=True), ref),
            "raw": {"program": prog, "reference": ref}}


def run(ctx: dict, args, devices, clock, t_start: float) -> tuple:
    """One run of a training cell: (result line, checks)."""
    import jax
    if "train" not in ctx["config"]["limits"]:
        raise ValueError("the configuration holds no training limits: set "
                         "them from calibrate.py's readings on the chip")
    S = prepare(ctx, args.seed)
    trainer = make_trainer(S, devices)
    ebs = build_blocks(S, trainer)
    feed = Feed(trainer, ebs)
    prog = program_steps(S, trainer, feed)
    setup_s = time.perf_counter() - t_start
    setup_compile_s = clock.seconds

    compiles0 = clock.count
    waits, samples, attempted, dropped, traced = [], 0, 0, 0, []
    trace_dir = os.path.join(common.HERE, ".trace", ctx["cell"]["name"])
    n_trace = S.traffic["trace_episodes"] if args.trace else 0

    def episode():
        nonlocal samples, attempted, dropped
        with jax.profiler.TraceAnnotation("bench.feed_wait"):
            i, staged, wait = feed.next()
        with jax.profiler.TraceAnnotation("bench.train_episode"):
            trainer.train_episode(staged)
        waits.append(wait)
        samples += staged.num_samples
        attempted += S.pairs[i % len(S.pairs)].shape[0]
        dropped += staged.dropped
        return i

    t0 = time.perf_counter()
    if n_trace:
        episode()
        with devtrace.capture(trace_dir):
            traced = [episode() for _ in range(n_trace)]
    while True:
        episode()
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_s = time.perf_counter() - t0
    compiles = clock.count - compiles0
    peak = common.memory_peak_bytes(devices[:S.chips])
    feed.close()
    ebs_traced = [ebs[i % len(ebs)] for i in traced]
    del trainer, feed
    gc.collect()

    ref = reference_steps(S)
    numbers = compare.train_numbers(prog, ref)
    checks = compare.checks(numbers, S.cfg["limits"]["train"])
    print(f"set-up: {setup_s:.3f} s, {setup_compile_s:.3f} s of it "
          f"compiling; window: {len(waits)} episodes, {window_s:.3f} s, "
          f"{compiles} compiles inside it; program losses {prog['loss']}, "
          f"reference {ref['loss']}", file=sys.stderr)

    result = {"correct": all(c["ok"] for c in checks),
              "attempted": attempted, "failed": dropped,
              "device": common.device_info(devices, S.chips) | {
                  "memory_peak_bytes": peak}}
    if not args.trace:
        result["metrics"] = {
            "train_samples_per_s": {"value": samples / window_s,
                                    "unit": "samples/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, checks

    tr = devtrace.load(trace_dir)
    itemsize = 2 if S.cfg["dtype"] == "bfloat16" else 4
    work = [flops_bytes.sgns_episode(
        eb.blocks, eb.counts, minibatch=S.cfg["minibatch"],
        negatives=S.cfg["negatives"], dim=S.cfg["dim"], itemsize=itemsize)
        for eb in ebs_traced]
    run_info = {"kind": "train", "trace": tr, "chips": S.chips,
                "peaks": peaks.peaks(devices[0].device_kind),
                "episodes": len(ebs_traced),
                "bytes": sum(w["bytes"] for w in work),
                "flops": sum(w["flops"] for w in work),
                "feed_wait_s": waits}
    result["metrics"] = common.read_metrics(ctx, run_info)
    result["device"]["busy_s"] = devtrace.busy_s(tr)
    result["device"]["window_s"] = tr.window_s
    result["breakdown"] = devtrace.breakdown(tr)
    return result, checks


"""Hybrid model–data parallel embedding training (paper §III — the core).

Data parallelism: each episode's edge samples are 2D-partitioned into blocks
(`core.partition`) and each device trains only blocks whose endpoints are
resident. Model parallelism: the context table is pinned (row-sharded over
every mesh axis); the vertex table is row-sharded the same way but **rotates**
through nested rings (`core.rotation`) so each vertex shard meets each
context shard exactly once per episode.

The episode step is a single `shard_map`-ed, jit-ted function:

    scan over pod ring (Q)              ppermute "pod"   (DCN, slow)
      scan over data ring (D)           ppermute "data"  (ICI)
        scan over model ring (M)        per-sub-part ppermute "model" (fast)
          unrolled k sub-parts          <- paper's ping-pong pipelining:
            scan over minibatches          sub-part j's ppermute overlaps
              kernels.ops.sgns_step        sub-part j+1's training

XLA's async collective scheduling provides the compute/communication overlap
that the paper implements manually with CUDA streams and ping-pong buffers.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.partition import EpisodeBlocks, NodePartition
from repro.kernels import ops
from repro.sharding import compat


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    dim: int = 128
    lr: float = 0.025
    negatives: int = 16           # shared negatives per minibatch
    minibatch: int = 64           # shared-negative group size (Ji et al. [19])
    reduction: str = "sum"        # word2vec-faithful; see kernels.ops.sgns_step
    subparts: int = 4             # paper's k (ping-pong sub-parts)
    neg_pool: int = 8192          # deg^0.75-sampled per-device negative pool
    # kernels.ops impl: "ref" | "pallas" | "pallas_fused" | "pallas_fused2".
    # "pallas_fused2" is the pipelined fully-fused update kernel (double-
    # buffered DMA gather + in-kernel SGD apply) — the production path on TPU.
    impl: str = "ref"
    # kernel tile rows. None (default) = VMEM-aware autotune at trace time
    # (kernels.ops.plan_fused_update picks tile size, duplicate-combine
    # strategy and per-launch chunking from B/d/S/dtype); set an int only to
    # pin the tile size for experiments.
    block_b: int | None = None
    seed: int = 0
    # bf16 tables halve BOTH the ring-rotation bytes and the HBM footprint;
    # grads are computed in f32 inside the kernel (beyond-paper, §Perf A.3).
    # Default since the AUC-parity gate in tests/test_eval_auc.py showed
    # bf16 within 0.5% AUC of f32 on the small-graph run; pass
    # dtype="float32" (CLI: --dtype float32) for the paper-faithful tables.
    dtype: str = "bfloat16"
    # ablation switches (used by §Perf):
    fuse_subpart_permute: bool = True   # False -> one whole-shard ppermute/round


@dataclasses.dataclass(frozen=True)
class StagedEpisodeBlocks:
    """An episode's block layout already device_put with the episode-step
    shardings — the output of the pipeline's staging stage, accepted by
    ``train_episode`` directly so the H2D copies happen on a pipeline worker
    instead of the training loop's critical path."""

    blocks: object                 # jax.Array, sharded like eb.blocks
    counts: object                 # jax.Array, sharded like eb.counts
    num_samples: int               # host-side valid-sample count (logging)
    dropped: int = 0


def _shift_perm(n: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % n) for i in range(n)]


def build_episode_fn(mesh: Mesh, part: NodePartition, cfg: HybridConfig):
    """Returns (jitted episode fn, in_shardings dict). Shapes are static per
    (part, block_cap) so the caller re-lowers only when the layout changes."""
    axis_names = tuple(mesh.axis_names)
    dims = tuple(mesh.devices.shape)
    assert dims == tuple(part.dims), (dims, part.dims)
    k = part.subparts
    rows_sub = part.rows_per_subpart
    rows = part.padded_rows_per_shard
    mb = cfg.minibatch
    S = cfg.negatives

    def train_block(vert_j, ctx, blk, cnt, pool, key, lr):
        """All minibatches of one (sub-part, round) block. blk: (Bmax, 2)."""
        bmax = blk.shape[0]
        nmb = bmax // mb
        blk3 = blk.reshape(nmb, mb, 2)
        offsets = jnp.arange(nmb, dtype=jnp.int32) * mb

        def body(carry, xs):
            vj, ctx, key, lacc = carry
            blk_mb, off = xs
            key, kneg = jax.random.split(key)
            pidx = jax.random.randint(kneg, (S,), 0, pool.shape[0])
            idx_n = pool[pidx]
            mask = ((off + jnp.arange(mb, dtype=jnp.int32)) < cnt).astype(vj.dtype)
            vj, ctx, loss = ops.sgns_step(
                vj, ctx, blk_mb[:, 0], blk_mb[:, 1], idx_n, mask, lr,
                impl=cfg.impl, reduction=cfg.reduction, block_b=cfg.block_b)
            return (vj, ctx, key, lacc + loss), None

        (vert_j, ctx, key, loss), _ = jax.lax.scan(
            body, (vert_j, ctx, key, jnp.float32(0.0)), (blk3, offsets))
        return vert_j, ctx, loss, key

    model_axis = axis_names[-1]
    model_perm = _shift_perm(dims[-1])

    def model_round(carry, xs):
        vert, ctx, key, lacc = carry          # vert: k-tuple of (rows_sub, d)
        blk_r, cnt_r = xs                     # (k, Bmax, 2), (k,)
        # NOTE: vert is a TUPLE of sub-part arrays, not a stacked (k, ...)
        # array: slicing/stacking a stacked carry copies the whole shard
        # twice per ring round (§Perf hillclimb A, iteration 2).
        slots = []
        for j in range(k):
            vj, ctx, lj, key = train_block(
                vert[j], ctx, blk_r[j], cnt_r[j], _pool[0], key, _lr[0])
            if cfg.fuse_subpart_permute:
                # paper-faithful: ppermute sub-part j immediately; its
                # transfer overlaps sub-part j+1's compute.
                vj = jax.lax.ppermute(vj, model_axis, model_perm)
            slots.append(vj)
            lacc = lacc + lj
        if not cfg.fuse_subpart_permute:
            # naive variant (§Perf ablation): train everything, then one
            # bulk transfer — no overlap opportunity.
            slots = [jax.lax.ppermute(vj, model_axis, model_perm)
                     for vj in slots]
        return (tuple(slots), ctx, key, lacc), None

    # nested ring scans, innermost (model) to outermost (pod)
    def make_level(level_fn, axis: str, n: int):
        perm = _shift_perm(n)

        def level(carry, xs):
            carry, _ = jax.lax.scan(level_fn, carry, xs)
            vert, ctx, key, lacc = carry
            vert = jax.lax.ppermute(vert, axis, perm)
            return (vert, ctx, key, lacc), None

        return level

    # closure cells for pool/lr (set per-call below, avoids threading them
    # through every scan carry)
    _pool = [None]
    _lr = [None]

    # the step's stable name in the profiler trace (its ops' metadata)
    @jax.named_scope("episode_step")
    def episode_device_fn(vert, ctx, blocks, counts, pool, seed, lr):
        # local views; vert becomes a k-tuple of sub-part arrays (see
        # model_round) — the split/concat happen once per episode, not per
        # ring round.
        vert = tuple(vert.reshape(k, rows_sub, -1))
        blocks = blocks[0]                    # (Q, D, M, k, Bmax, 2)
        counts = counts[0]
        _pool[0] = pool[0]
        _lr[0] = lr

        key = jax.random.fold_in(
            jax.random.PRNGKey(seed[0]),
            compat.axis_flat_index(axis_names, dims))

        fn = model_round
        # wrap middle/outer rings (skip the innermost axis: handled per round)
        for axis, n in list(zip(axis_names, dims))[:-1][::-1]:
            fn = make_level(fn, axis, n)
        carry = (vert, ctx, key, jnp.float32(0.0))
        carry, _ = jax.lax.scan(fn, carry, (blocks, counts))
        vert, ctx, key, lacc = carry

        total = jnp.maximum(jnp.sum(counts).astype(jnp.float32), 1.0)
        loss = jax.lax.psum(lacc, axis_names) / jax.lax.psum(total, axis_names)
        return jnp.concatenate(vert, axis=0), ctx, loss

    all_axes = P(axis_names)
    in_specs = (
        all_axes,                  # vert (N_pad, d) row-sharded over all axes
        all_axes,                  # ctx
        P(axis_names),             # blocks (P, ...): dim0 over all axes
        P(axis_names),             # counts
        P(axis_names),             # pool (P, pool_n)
        P(),                       # seed (1,) replicated
        P(),                       # lr scalar
    )
    out_specs = (all_axes, all_axes, P())

    fn = compat.shard_map(episode_device_fn, mesh, in_specs, out_specs)
    shardings = {
        "table": NamedSharding(mesh, all_axes),
        "blocks": NamedSharding(mesh, P(axis_names)),
        "replicated": NamedSharding(mesh, P()),
    }
    jitted = jax.jit(
        fn, donate_argnums=(0, 1),
        in_shardings=(shardings["table"], shardings["table"],
                      shardings["blocks"], shardings["blocks"],
                      shardings["blocks"], shardings["replicated"],
                      shardings["replicated"]))
    return jitted, shardings


class HybridEmbeddingTrainer:
    """Driver tying partition + rotation + episode step together."""

    def __init__(self, num_nodes: int, mesh: Mesh, cfg: HybridConfig,
                 degrees: np.ndarray | None = None):
        self.mesh = mesh
        self.cfg = cfg
        self.part = NodePartition(
            num_nodes, dims=tuple(mesh.devices.shape), subparts=cfg.subparts)
        self.num_nodes = num_nodes
        self._built = None
        self.vert = None
        self.ctx = None
        self.pool = self._build_neg_pool(degrees)

    # ---------------------------------------------------------------- setup
    def _build_neg_pool(self, degrees: np.ndarray | None) -> np.ndarray:
        """Per-device pool of local context rows, sampled ∝ deg^0.75."""
        part, cfg = self.part, self.cfg
        P_shards, rows = part.num_shards, part.padded_rows_per_shard
        rng = np.random.default_rng(cfg.seed + 17)
        pool = np.zeros((P_shards, cfg.neg_pool), dtype=np.int32)
        for s in range(P_shards):
            lo = s * rows
            hi = min((s + 1) * rows, self.num_nodes)
            if hi <= lo:
                continue
            local_n = hi - lo
            if degrees is None:
                pool[s] = rng.integers(0, local_n, cfg.neg_pool)
            else:
                w = degrees[lo:hi].astype(np.float64) ** 0.75
                w = np.maximum(w, 1e-12)
                w /= w.sum()
                pool[s] = rng.choice(local_n, size=cfg.neg_pool, p=w)
        return pool

    def init_embeddings(self):
        """word2vec-style init: vertex ~ U(-0.5/d, 0.5/d), context = 0."""
        part, cfg = self.part, self.cfg
        d = cfg.dim
        rng = np.random.default_rng(cfg.seed)
        dt = np.dtype(cfg.dtype)
        vert = ((rng.random((part.padded_num_nodes, d), dtype=np.float32)
                 - 0.5) / d).astype(dt)
        ctx = np.zeros((part.padded_num_nodes, d), dtype=dt)
        _, sh = self._episode_fn()
        self.vert = jax.device_put(vert, sh["table"])
        self.ctx = jax.device_put(ctx, sh["table"])

    def _episode_fn(self):
        if self._built is None:
            self._built = build_episode_fn(self.mesh, self.part, self.cfg)
        return self._built

    # ---------------------------------------------------------------- train
    def stage_blocks(self, eb: EpisodeBlocks) -> StagedEpisodeBlocks:
        """device_put an episode's blocks with the episode-step shardings.
        Safe to call from a pipeline worker thread — the H2D copies then
        overlap the previous episode's device compute."""
        _, sh = self._episode_fn()
        return StagedEpisodeBlocks(
            blocks=jax.device_put(eb.blocks, sh["blocks"]),
            counts=jax.device_put(eb.counts, sh["blocks"]),
            num_samples=int(eb.counts.sum()),
            dropped=eb.dropped)

    def train_episode(self, eb: EpisodeBlocks | StagedEpisodeBlocks,
                      *, lr: float | None = None) -> float:
        fn, sh = self._episode_fn()
        if not isinstance(eb, StagedEpisodeBlocks):
            eb = self.stage_blocks(eb)
        pool = jax.device_put(self.pool, sh["blocks"])
        seed = jax.device_put(
            np.array([self.cfg.seed], np.int32), sh["replicated"])
        lr_arr = jax.device_put(
            np.float32(self.cfg.lr if lr is None else lr), sh["replicated"])
        self.vert, self.ctx, loss = fn(
            self.vert, self.ctx, eb.blocks, eb.counts, pool, seed, lr_arr)
        return float(loss)

    def set_embeddings(self, vert: np.ndarray, ctx: np.ndarray) -> None:
        """Install externally-provided (num_nodes, d) tables — the resume
        path. Pads to the partition geometry (padded rows never enter
        training math, so zero-padding restored tables is exact) and
        device_puts with the episode-step shardings."""
        part = self.part
        dt = np.dtype(self.cfg.dtype)
        _, sh = self._episode_fn()
        self.vert = jax.device_put(
            part.pad_table(np.asarray(vert).astype(dt, copy=False)),
            sh["table"])
        self.ctx = jax.device_put(
            part.pad_table(np.asarray(ctx).astype(dt, copy=False)),
            sh["table"])

    def embeddings(self) -> np.ndarray:
        return self.part.unpad_table(np.asarray(self.vert))

    def context_embeddings(self) -> np.ndarray:
        return self.part.unpad_table(np.asarray(self.ctx))

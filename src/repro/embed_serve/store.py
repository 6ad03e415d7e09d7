"""Device-sharded embedding store: trained tables → servable shards.

The serving twin of the training layout: tables are row-partitioned with
the same ``NodePartition`` block rule the trainer uses (node n → shard
n // rows, local row n % rows), one shard per device, so a checkpoint
written by ``launch/train.py`` loads without any re-indexing — shard s of
the store holds exactly the rows device s held during training (subparts=1:
serving has no rotation, so the sub-part split is irrelevant here).

Queries fan out to every shard (each runs the Pallas top-k kernel over its
resident rows — the GraphVite-style shard-local lookup), and the per-shard
(k) lists meet in ``topk.merge_topk``. Tables keep their checkpoint dtype
(bf16 by default, honoring ``HybridConfig.dtype``) and are loaded bitwise;
``normalize=True`` rescales rows to unit norm at load so the same MIPS
kernel serves cosine retrieval.

``quant="int8"`` additionally builds a symmetric per-row int8 copy of every
shard on its device (``quant.quantize_device``, in fixed row blocks),
enabling the two-tier scan (``impl="quant"``): int8 first pass at 4x less
scan traffic, exact rescore of the over-fetched survivors, same
cross-shard merge.

``enable_hot_tier(budget, counts=...)`` physically splits each shard into
an exact hot tier (the budget's hottest rows by observed access counts —
hub nodes under power-law traffic) and a compacted int8 cold remainder.
``impl="tiered"`` then scans the hot tier exactly (hits return exact
rows, so hub results never pay quantization error) and runs the quant
scan + exact rescore over only the cold rows; both per-shard lists merge
under the one smaller-index tie rule. Hot/returned-from-hot counters feed
``repro.obs`` and ``hot_tier_stats()`` for the bench's hit-rate ×
scan-bytes model.

Degraded mode: ``topk(shard_timeout_s=...)`` runs each shard's scan as its
own task; shards that miss the deadline are excluded from the merge and the
response is tagged degraded (``return_meta=True`` → :class:`TopKMeta` with
the failed shard list), so one slow or dead device degrades recall over its
rows instead of stalling every query — the answer over surviving shards is
still exact (``oracle_topk(exclude_shards=...)`` is the test oracle).
"""
from __future__ import annotations

import dataclasses
import threading
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import wait as _fut_wait

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import NodePartition
from repro.embed_serve import quant as qz
from repro.embed_serve import topk as tk
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs import counter_add, gauge_set, span
from repro.runtime import fault_point
from repro.train.checkpoint import load_arrays

QUERY_IMPLS = ("auto", "pallas", "rowwise", "xla",
               "quant", "quant_pallas", "quant_xla", "tiered")
QUANT_TIERS = (None, "int8")

_UNSET = object()   # "use the store's shard_timeout_s" vs an explicit None


@dataclasses.dataclass(frozen=True)
class TopKMeta:
    """Per-query-batch serving outcome (``topk(return_meta=True)``)."""

    degraded: bool = False
    failed_shards: tuple = ()
    timeout_s: float | None = None


@dataclasses.dataclass(frozen=True)
class _HotShard:
    """One shard's hot/cold physical split (``enable_hot_tier``).

    hot_shard holds the shard's hot rows exactly (served dtype), cold_*
    the compacted remainder: exact rows (the rescore source), the int8
    scan copy, and the compact-row → global-id maps. Row counts are
    padded to scan-tile multiples; *_valid mask the padding out.
    """

    hot_shard: object
    hot_map: object
    hot_valid: int
    cold_shard: object
    cold_q8: object
    cold_sc: object
    cold_map: object
    cold_valid: int


class ShardedEmbeddingStore:
    """Row-sharded embedding table + exact top-k retrieval over it."""

    def __init__(self, shards, part: NodePartition, valid, devices, *,
                 host_table, block_n: int, step: int = -1, quant=None,
                 overfetch: float = qz.DEFAULT_OVERFETCH,
                 shard_timeout_s: float | None = None):
        """shards: the per-device (rows_p, d) arrays, already on their
        devices. quant="int8" builds the int8 tier from them, on each
        shard's device (``_quantize_shards``)."""
        if quant not in QUANT_TIERS:
            raise ValueError(f"unknown quant tier {quant!r}; "
                             f"one of {QUANT_TIERS}")
        self.shards = shards                  # per-device (rows_p, d) arrays
        self.part = part
        self.valid = tuple(valid)             # real rows per shard
        self.devices = tuple(devices)
        self.host_table = host_table          # (num_nodes, d) as served,
        self.block_n = block_n                # or None (keep_host_table off)
        self.step = step
        # per-device (int8 rows, f32 scales), or None: no quantized tier
        self.qshards = _quantize_shards(shards) if quant == "int8" else None
        self.quant = quant
        self.overfetch = overfetch            # default tier-one margin
        self.shard_timeout_s = shard_timeout_s  # None = never degrade
        self._pool = None                     # lazy shard-scan executor
        self._pool_mu = threading.Lock()
        self.hot_tiers = None                 # per-shard _HotShard or None
        self.hot_budget = 0
        self._hot_mask = None                 # (num_nodes,) bool, host
        self._tiered_bn = block_n             # hot-aware cold-scan tile
        self._hot_stats = {"queries": 0, "returned": 0, "returned_hot": 0}

    # ------------------------------------------------------------- loading
    @classmethod
    def from_array(cls, table, *, devices=None, dtype=None,
                   block_n: int | None = None, normalize: bool = False,
                   keep_host_table: bool = True, quant: str | None = None,
                   overfetch: float = qz.DEFAULT_OVERFETCH,
                   shard_timeout_s: float | None = None,
                   step: int = -1) -> "ShardedEmbeddingStore":
        """Shard an in-memory (num_nodes, d) table across `devices`.

        dtype=None keeps the array's dtype (the checkpoint's, i.e. the
        training ``HybridConfig.dtype``). Shard rows are padded to a
        block_n multiple once, here, so serving never re-materializes the
        table; padded rows are masked out of every query by ``valid``.
        block_n=None sizes the scan tile with ``topk.choose_block_n``
        against the VMEM budget (k not known yet — planned at the
        ``DEFAULT_PLAN_K`` candidate allowance).
        keep_host_table=False drops the host copy after sharding (serving
        itself never reads it — it only backs ``oracle_topk`` and query
        sampling; at production table sizes it would double the footprint).
        quant="int8" builds the two-tier scan's per-shard int8 copies on
        the devices (``quant.quantize_device`` of the served — post-
        normalize — rows, same row order and padding as the exact shards);
        `overfetch` is the default tier-one margin ``topk(impl="quant")``
        uses.
        shard_timeout_s is the default per-shard scan deadline for
        degraded-mode queries (None = wait forever). `devices` may repeat
        a device (e.g. ``[cpu]*4``) to get a multi-shard layout on fewer
        physical devices — how the degraded-serving tests and CI leg run.
        """
        devices = list(devices) if devices is not None else jax.devices()
        table = np.asarray(table)
        if dtype is not None and np.dtype(jnp.dtype(dtype)) != table.dtype:
            table = np.asarray(jnp.asarray(table).astype(jnp.dtype(dtype)))
        if normalize:                         # cosine via the MIPS kernel
            f32 = table.astype(np.float32)
            f32 /= np.linalg.norm(f32, axis=1, keepdims=True) + 1e-12
            table = np.asarray(jnp.asarray(f32).astype(table.dtype))
        num_nodes, d = table.shape
        part = NodePartition(num_nodes, dims=(len(devices),), subparts=1)
        rows = part.padded_rows_per_shard
        if block_n is None:
            block_n = tk.choose_block_n(d, table.dtype)
        bn = min(block_n, rows)
        rows_p = -(-rows // bn) * bn
        padded = part.pad_table(table)
        shards, valid = [], []
        for s, dev in enumerate(devices):
            sh = padded[s * rows:(s + 1) * rows]
            if rows_p > rows:
                sh = np.concatenate(
                    [sh, np.zeros((rows_p - rows, d), sh.dtype)])
            shards.append(jax.device_put(sh, dev))
            valid.append(int(np.clip(num_nodes - s * rows, 0, rows)))
        return cls(shards, part, valid, devices,
                   host_table=table if keep_host_table else None,
                   block_n=bn, step=step, quant=quant,
                   overfetch=overfetch, shard_timeout_s=shard_timeout_s)

    @classmethod
    def load(cls, path: str, *, table: str = "vertex",
             **kwargs) -> "ShardedEmbeddingStore":
        """Load one embedding table from a ``launch/train.py`` checkpoint
        (``save_checkpoint({"vertex": ..., "context": ...})`` layout)."""
        arrays, step = load_arrays(path)
        if table not in arrays:
            raise KeyError(f"checkpoint {path!r} has no table {table!r}; "
                           f"keys: {sorted(arrays)}")
        return cls.from_array(arrays[table], step=step, **kwargs)

    # ------------------------------------------------------------ querying
    @property
    def num_nodes(self) -> int:
        return self.part.num_nodes

    @property
    def dim(self) -> int:
        return self.shards[0].shape[1]

    def _dispatch_shard(self, s: int, q, k: int, impl: str, ov: float):
        """Dispatch shard s's scan (async) → (scores, GLOBAL ids, groups)
        device arrays. Sub-k shards keep the IDX_SENTINEL so they lose the
        merge. groups is the Pallas scan's (4,) counts
        (``topk.topk_mips_counted``, or the int8 first pass's), None on
        every other path. The quant paths add the rows their rescore
        gathers to ``serve.quant.candidates``."""
        if impl == "tiered":
            # hot/cold split scans carry their own global-id maps
            return (*self._dispatch_shard_tiered(s, q, k, ov), None)
        shard = self.shards[s]
        interpret = (kops.interpret_mode()
                     if impl in ("pallas", "rowwise", "quant_pallas")
                     else False)
        groups = None
        if impl == "pallas":
            v, i, groups = tk.topk_mips_counted(
                shard, q, k=k, valid=self.valid[s], block_n=self.block_n,
                interpret=interpret)
        elif impl == "rowwise":
            v, i = tk.topk_mips_rowwise(shard, q, k=k,
                                        valid=self.valid[s],
                                        interpret=interpret)
        elif impl.startswith("quant"):
            q8, sc = self.qshards[s]
            v, i, groups = qz.topk_mips_quant_rescored_counted(
                shard, q8, sc, q, k=k, overfetch=ov,
                valid=self.valid[s], block_n=self.block_n,
                impl="pallas" if impl == "quant_pallas" else "xla",
                interpret=interpret)
            counter_add("serve.quant.candidates",
                        q.shape[0] * qz.overfetch_m(k, ov, self.valid[s]))
        else:
            v, i = tk.topk_mips_xla(shard, q, k=k, valid=self.valid[s])
        # shard-local → global node ids on the shard's own device
        # (elementwise, overlaps the other shards' scans)
        rows = self.part.padded_rows_per_shard
        gi = jnp.where(i == tk.IDX_SENTINEL, tk.IDX_SENTINEL, i + s * rows)
        return v, gi, groups

    # ------------------------------------------------------------ hot tier
    def enable_hot_tier(self, budget: int, *, ids=None, counts=None) -> int:
        """Split every shard into an exact hot tier + compacted int8 cold
        remainder for ``impl="tiered"`` queries.

        The hot set is the ``budget`` hottest rows by ``counts`` (observed
        access counts — training-episode traffic, degrees, or a query log;
        ties break toward the smaller id so the split is deterministic), or
        an explicit ``ids`` list. Hot hits are scanned exactly in the
        served dtype; cold rows get a fresh compacted int8 copy (genuinely
        fewer cold-scan bytes than the full quant tier — the byte model
        in bench_serve measures exactly this). The cold-scan tile is
        re-chosen with the hot tile's VMEM footprint accounted
        (``topk.choose_block_n(hot_rows=...)``). Returns the realized hot
        row count.
        """
        if ids is None:
            if counts is None:
                raise ValueError("enable_hot_tier needs ids or counts")
            counts = np.asarray(counts, np.float64)
            if counts.shape != (self.num_nodes,):
                raise ValueError(f"counts shape {counts.shape} != "
                                 f"({self.num_nodes},)")
            order = np.lexsort((np.arange(self.num_nodes), -counts))
            order = order[counts[order] > 0]
            ids = np.sort(order[: budget])
        else:
            ids = np.unique(np.asarray(ids, np.int64))
            ids = ids[(ids >= 0) & (ids < self.num_nodes)][: budget]
        mask = np.zeros(self.num_nodes, bool)
        mask[ids] = True
        rows = self.part.padded_rows_per_shard
        bn = self.block_n
        tiers = []
        for s, dev in enumerate(self.devices):
            n_valid = self.valid[s]
            sh = np.asarray(self.shards[s])       # padded (rows_p, d) host
            d = sh.shape[1]
            loc_mask = np.zeros(sh.shape[0], bool)
            loc_mask[:n_valid] = mask[s * rows: s * rows + n_valid]
            hot_loc = np.flatnonzero(loc_mask)
            cold_loc = np.flatnonzero(~loc_mask[:n_valid])

            def _compact(loc):
                n = loc.size
                n_p = max(bn, -(-max(n, 1) // bn) * bn)
                tbl = np.zeros((n_p, d), sh.dtype)
                tbl[:n] = sh[loc]
                gmap = np.zeros(n_p, np.int32)
                gmap[:n] = (s * rows + loc).astype(np.int32)
                return tbl, gmap, n

            hot_tbl, hot_map, n_hot = _compact(hot_loc)
            cold_tbl, cold_map, n_cold = _compact(cold_loc)
            q8, sc = qz.quantize_rows(cold_tbl)
            tiers.append(_HotShard(
                hot_shard=jax.device_put(hot_tbl, dev),
                hot_map=jax.device_put(jnp.asarray(hot_map), dev),
                hot_valid=n_hot,
                cold_shard=jax.device_put(cold_tbl, dev),
                cold_q8=jax.device_put(q8, dev),
                cold_sc=jax.device_put(sc, dev),
                cold_map=jax.device_put(jnp.asarray(cold_map), dev),
                cold_valid=n_cold))
        self.hot_tiers = tiers
        self.hot_budget = int(ids.size)
        self._hot_mask = mask
        self._tiered_bn = min(bn, tk.choose_block_n(
            self.dim, np.int8, hot_rows=int(ids.size)))
        self._hot_stats = {"queries": 0, "returned": 0, "returned_hot": 0}
        gauge_set("serve.hot_tier.rows", int(ids.size))
        return int(ids.size)

    def hot_tier_stats(self) -> dict:
        """Serving-side cache telemetry: realized hot rows, the fraction of
        returned results served from the exact tier, and the modeled scan
        bytes per query of the tiered vs full-quant layouts."""
        st = dict(self._hot_stats)
        d = self.dim
        item = np.dtype(self.shards[0].dtype).itemsize
        n_cold = sum(t.cold_valid for t in (self.hot_tiers or []))
        n_hot = sum(t.hot_valid for t in (self.hot_tiers or []))
        return {
            **st,
            "hot_rows": n_hot,
            "cold_rows": n_cold,
            "returned_hot_frac": st["returned_hot"] / max(st["returned"], 1),
            # per-query scan bytes: exact hot rows + int8 cold (value + f32
            # scale) vs the untiered int8 scan of every row
            "scan_bytes_tiered": n_hot * d * item + n_cold * (d + 4),
            "scan_bytes_quant": (n_hot + n_cold) * (d + 4),
        }

    def _pad_k(self, v, i, k: int):
        pad = k - v.shape[1]
        if pad <= 0:
            return v, i
        return (jnp.pad(v, ((0, 0), (0, pad)), constant_values=tk.NEG_INF),
                jnp.pad(i, ((0, 0), (0, pad)),
                        constant_values=tk.IDX_SENTINEL))

    def _dispatch_shard_tiered(self, s: int, q, k: int, ov: float):
        """Shard s under the two-physical-tier layout: exact hot scan +
        quant-with-rescore cold scan, merged under the global tie rule.
        Compact → global maps live on the device, so like the plain path
        nothing syncs until the caller's device_get."""
        ht = self.hot_tiers[s]
        interpret = kops.interpret_mode()   # kernels on TPU, jnp on CPU
        outs = []
        if ht.hot_valid > 0:
            kh = min(k, ht.hot_shard.shape[0])
            if not interpret:
                hv, hi = tk.topk_mips(
                    ht.hot_shard, q, k=kh, valid=ht.hot_valid,
                    block_n=min(self._tiered_bn, ht.hot_shard.shape[0]))
            else:
                hv, hi = tk.topk_mips_xla(ht.hot_shard, q, k=kh,
                                          valid=ht.hot_valid)
            hg = jnp.where(
                hi == tk.IDX_SENTINEL, tk.IDX_SENTINEL,
                jnp.take(ht.hot_map,
                         jnp.minimum(hi, ht.hot_map.shape[0] - 1)))
            outs.append(self._pad_k(hv, hg, k))
        if ht.cold_valid > 0:
            kc = min(k, ht.cold_shard.shape[0])
            cv, ci = qz.topk_mips_quant_rescored(
                ht.cold_shard, ht.cold_q8, ht.cold_sc, q, k=kc,
                overfetch=ov, valid=ht.cold_valid,
                block_n=min(self._tiered_bn, ht.cold_shard.shape[0]),
                impl="xla" if interpret else "pallas")
            cg = jnp.where(
                ci == tk.IDX_SENTINEL, tk.IDX_SENTINEL,
                jnp.take(ht.cold_map,
                         jnp.minimum(ci, ht.cold_map.shape[0] - 1)))
            outs.append(self._pad_k(cv, cg, k))
        if not outs:
            raise RuntimeError(f"shard {s} has no valid rows")
        if len(outs) == 1:
            return outs[0]
        return tk.merge_topk(jnp.stack([v for v, _ in outs]),
                             jnp.stack([i for _, i in outs]), k=k)

    def _note_tiered_result(self, gi) -> None:
        gi = np.asarray(gi)
        real = gi[gi != tk.IDX_SENTINEL]
        n_hot = int(self._hot_mask[real].sum())
        self._hot_stats["queries"] += int(gi.shape[0])
        self._hot_stats["returned"] += int(real.size)
        self._hot_stats["returned_hot"] += n_hot
        counter_add("serve.hot_tier.hits", n_hot)
        counter_add("serve.hot_tier.misses", int(real.size) - n_hot)

    def _merge(self, per_v, per_i, k: int):
        if len(per_v) == 1:
            return per_v[0], per_i[0]
        gv, gi = tk.merge_topk(jnp.asarray(np.stack(per_v)),
                               jnp.asarray(np.stack(per_i)), k=k)
        return np.asarray(gv), np.asarray(gi)

    def resolve_impl(self, impl: str) -> str:
        """The concrete scan path a query impl runs: "auto" is the Pallas
        kernel on a TPU and plain jnp on the CPU, "quant" likewise."""
        if impl not in QUERY_IMPLS:
            raise ValueError(f"unknown impl {impl!r}; one of {QUERY_IMPLS}")
        if impl in ("auto", "quant"):
            kernel = not kops.interpret_mode()
            impl = {"auto": ("xla", "pallas"),
                    "quant": ("quant_xla", "quant_pallas")}[impl][kernel]
        if impl.startswith("quant") and self.qshards is None:
            raise RuntimeError("store has no quantized tier; build it with "
                               "quant='int8'")
        if impl == "tiered" and self.hot_tiers is None:
            raise RuntimeError("store has no hot tier; call "
                               "enable_hot_tier(budget, counts=...) first")
        return impl

    def _scan_pool(self) -> ThreadPoolExecutor:
        with self._pool_mu:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=max(1, len(self.shards)),
                    thread_name_prefix="shard-scan")
            return self._pool

    def topk(self, queries, k: int, *, impl: str = "auto",
             overfetch: float | None = None,
             shard_timeout_s=_UNSET,
             return_meta: bool = False):
        """Exact MIPS top-k over all shards.

        queries: (Q, d). Returns ((Q, k) f32 scores, (Q, k) i32 global node
        ids), k clamped to num_nodes. impl: "pallas" (the blocked DMA
        kernel; interpret mode on the CPU), "rowwise" (reference kernel),
        "xla" (plain jnp — the CPU serving path), "auto" (pallas on TPU,
        xla elsewhere), "quant" (the two-tier int8 scan + exact rescore —
        requires ``quant="int8"`` at load; kernel path on TPU, jnp path
        elsewhere, or force with "quant_pallas"/"quant_xla"). `overfetch`
        overrides the store's default tier-one margin for quant impls.

        shard_timeout_s (unset: the store's ``shard_timeout_s``; an
        explicit None = wait forever, e.g. for compile warmup) runs each
        shard's scan as its own task and merges
        only the shards that answered in time — exact over the survivors,
        degraded over the failed shards' rows. All shards failing raises.
        return_meta=True appends a :class:`TopKMeta` (degraded flag +
        failed shard list) to the return tuple.
        """
        with span("embed_store.topk", "serve"):
            impl = self.resolve_impl(impl)
            ov = self.overfetch if overfetch is None else overfetch
            k = min(k, self.num_nodes)
            with span("embed_store.to_device", "serve"):
                q = jnp.asarray(np.asarray(queries, dtype=np.float32))
            timeout = (self.shard_timeout_s if shard_timeout_s is _UNSET
                       else shard_timeout_s)
            live = [s for s in range(len(self.shards)) if self.valid[s] > 0]

            if timeout is None:
                # fast path (unchanged from the always-healthy store):
                # dispatch every shard before syncing any — jax dispatch is
                # async, so P devices scan concurrently instead of one
                # behind the other
                with span("embed_store.dispatch", "serve"):
                    launched = [self._dispatch_shard(s, q, k, impl, ov)
                                for s in live]
                with span("embed_store.fetch", "serve"):
                    staged = jax.device_get(launched)
                per_v = [v for v, _, _ in staged]
                per_i = [i for _, i, _ in staged]
                _note_scan_groups([g for _, _, g in staged], impl)
                meta = TopKMeta()
            else:
                per_v, per_i, failed = self._scan_within(
                    live, q, k, impl, ov, timeout)
                meta = TopKMeta(degraded=bool(failed),
                                failed_shards=tuple(sorted(failed)),
                                timeout_s=timeout)
            with span("embed_store.merge", "serve"):
                gv, gi = self._merge(per_v, per_i, k)
                if impl == "tiered":
                    self._note_tiered_result(gi)
        return (gv, gi, meta) if return_meta else (gv, gi)

    def _scan_within(self, live, q, k: int, impl: str, ov: float,
                     timeout: float):
        """Each live shard's scan as its own task; returns the per-shard
        (scores, ids) of those that answered within ``timeout``, and the
        failed shards."""
        def scan(s):
            fault_point("serve.shard", (s,))
            with span("embed_store.dispatch", "serve", {"shard": s}):
                launched = self._dispatch_shard(s, q, k, impl, ov)
            with span("embed_store.fetch", "serve", {"shard": s}):
                return jax.device_get(launched)

        pool = self._scan_pool()
        futs = {s: pool.submit(scan, s) for s in live}
        # wait for ALL to complete (a crashed shard completes immediately
        # with its exception; healthy shards keep their full deadline)
        _fut_wait(list(futs.values()), timeout=timeout)
        per_v, per_i, failed = [], [], []
        for s, f in futs.items():
            if f.done() and f.exception() is None:
                v, i, groups = f.result()
                per_v.append(v)
                per_i.append(i)
                _note_scan_groups([groups], impl)
            else:
                # timed out (result, if it ever lands, is discarded) or
                # crashed — either way the shard is out of this answer
                failed.append(s)
        if not per_v:
            raise RuntimeError(
                f"all {len(live)} shard scans failed or timed out "
                f"({timeout}s); shards: {failed}")
        return per_v, per_i, failed

    def oracle_topk(self, queries, k: int, *, exclude_shards=()):
        """Numpy ground truth over the full (unsharded) table.

        ``exclude_shards`` drops those shards' rows first — the surviving-
        shards oracle a degraded response must match exactly. The id remap
        is monotone, so the kernel's smaller-index tie rule is preserved."""
        if self.host_table is None:
            raise RuntimeError("store was built with keep_host_table=False; "
                               "the oracle needs the host copy")
        if not exclude_shards:
            return kref.topk_mips_ref(self.host_table, queries,
                                      min(k, self.num_nodes))
        rows = self.part.padded_rows_per_shard
        keep = np.ones(self.num_nodes, dtype=bool)
        for s in exclude_shards:
            keep[s * rows: min((s + 1) * rows, self.num_nodes)] = False
        idx = np.nonzero(keep)[0]
        if idx.size == 0:
            raise ValueError("exclude_shards leaves no rows to rank")
        v, i = kref.topk_mips_ref(self.host_table[idx], queries,
                                  min(k, idx.size))
        return v, idx[np.asarray(i)].astype(np.asarray(i).dtype)

    def score_ids(self, queries, ids) -> np.ndarray:
        """Ground-truth numpy f32 scores of specific (Q, k) candidate ids.

        This is what ``recall_at_k``'s tie tolerance should be fed — NOT a
        kernel's own reported values, which would let a broken kernel
        vouch for its own answers."""
        if self.host_table is None:
            raise RuntimeError("store was built with keep_host_table=False; "
                               "rescoring needs the host copy")
        q = np.asarray(queries, dtype=np.float32)
        rows = self.host_table.astype(np.float32)[np.asarray(ids)]  # (Q,k,d)
        return np.einsum("qd,qkd->qk", q, rows)


def _quantize_shards(shards) -> list:
    """The int8 tier of device shards: ``quant.quantize_device`` of each
    on its own device, under one ``embed_store.quantize`` span a shard."""
    qshards = []
    for s, sh in enumerate(shards):
        with span("embed_store.quantize", "serve", {"shard": s}):
            qshards.append(jax.block_until_ready(qz.quantize_device(sh)))
    return qshards


def _note_scan_groups(groups, impl: str) -> None:
    """Add the fetched counts of the Pallas scans (None for the other
    paths) to ``<scan>.merge_groups`` (row-group merges run),
    ``<scan>.tile_groups`` ((row group, tile) pairs scored),
    ``<scan>.query_blocks`` (query blocks launched) and
    ``<scan>.zero_blocks`` (those of zero rows alone, scored only on the
    tiles holding their first k rows), where ``<scan>`` is
    ``serve.quant`` for the int8 first pass and ``serve.topk`` for the
    exact scan, so a process serving both never mixes them."""
    scan = "serve.quant" if impl.startswith("quant") else "serve.topk"
    for g in groups:
        if g is not None:
            for name, n in zip(("merge_groups", "tile_groups",
                                "query_blocks", "zero_blocks"), g):
                counter_add(f"{scan}.{name}", int(n))


def recall_at_k(got_ids, oracle_ids, *, got_vals=None, oracle_vals=None,
                rtol: float = 1e-6) -> float:
    """Mean |top-k ∩ oracle top-k| / k over queries.

    With scores supplied, an id outside the oracle's list still counts if
    its score reaches the oracle's k-th score within rtol: the kernels
    (XLA/MXU accumulation) and the numpy oracle (BLAS) are not bitwise-
    identical on continuous data, so an exact tie at the rank-k boundary
    can ulp-flip between the two — and any row scoring at the boundary is
    a legitimate top-k member. ``got_vals`` must be GROUND-TRUTH scores of
    the returned ids (``ShardedEmbeddingStore.score_ids``), not the
    kernel's own claims. Duplicate returned ids count once — a kernel that
    repeats rank-1 k times scores 1/k here, not 1.0. Real retrieval bugs
    surface as scores well below the boundary and still count as misses.
    The single recall definition shared by the CLI gate and bench_serve,
    so the two can't drift."""
    got_ids = np.asarray(got_ids)
    oracle_ids = np.asarray(oracle_ids)
    hits = 0
    for qi in range(oracle_ids.shape[0]):
        o = set(oracle_ids[qi].tolist())
        seen = set()
        for j, g in enumerate(got_ids[qi].tolist()):
            if g in seen:                # duplicates can't double-count
                continue
            seen.add(g)
            if g in o:
                hits += 1
            elif got_vals is not None and oracle_vals is not None:
                kth = float(oracle_vals[qi][-1])
                if float(got_vals[qi][j]) >= kth - rtol * max(1.0, abs(kth)):
                    hits += 1
    return hits / oracle_ids.size

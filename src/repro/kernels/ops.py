"""Public jit'd ops over the SGNS kernels.

``impl`` selects the execution path:
  * ``"ref"``           — pure jnp (XLA). Default on CPU: fast and exact.
  * ``"pallas"``        — separate Pallas kernels: blocked gather → grads
                          (MXU tile kernel) → blocked scatter-add.
  * ``"pallas_fused"``  — one kernel for DMA-gather + grads; SGD apply still
                          runs as standalone scatter-add passes.
  * ``"pallas_fused2"`` — the pipelined fully-fused update kernel: gather,
                          grads, and SGD apply in a single pallas_call with
                          the tables aliased in-place (one HBM round-trip per
                          row; no separate scatters, no (idx_c ++ idx_n)
                          concatenate). This is the production pallas path.

Pallas kernels compile for the TPU and run in interpret mode only when the
default backend is the CPU (:func:`interpret_mode`, asked per call, so
importing this module does not start a backend).

`sgns_step` is the fused edge-minibatch update the hybrid trainer calls in
its inner loop. Its kernel launch geometry — tile size ``block_b``, the
duplicate-combine strategy, and how many minibatch rows fit one launch —
is picked at trace time by :func:`plan_fused_update` from
(B, d, S, dtype, VMEM budget); callers no longer guess a static knob
(pass ``block_b=`` only to override the autotuner).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import sgns as _k
from repro.launch import roofline


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: True on the CPU backend,
    False on a TPU, an error on any other platform (a kernel there would
    neither compile nor be what users run)."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                       f"{backend!r}; use impl='ref'/'xla' there")


# --------------------------------------------------------------------------
# VMEM-aware launch-geometry autotuner. All decisions are made from static
# shape/dtype info at trace time, so they cost nothing at run time and the
# jit cache keys stay the same per shape.
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FusedPlan:
    """Trace-time launch geometry for the fused SGNS update.

    block_b:    pipeline tile rows per grid step.
    combine:    duplicate-combine strategy ("eq" | "segsum").
    chunk_rows: max minibatch rows per kernel launch; sgns_step splits
                larger batches into sequential launches (each chunk's SGD
                apply lands before the next chunk's gathers — plain
                sequential minibatch SGD at a coarser grain).
    """

    block_b: int
    combine: str
    chunk_rows: int


_LANE_ROW = 128 * 4    # bytes of one (rows, 1) 32-bit VMEM row: 128 lanes


def fused_update_vmem_bytes(B: int, d: int, S: int, dtype,
                            combine: str, staging_rows: int = 0,
                            block_b: int = 256) -> int:
    """Modeled VMEM of one sgns_fused_update launch of B rows.

    Mirrors kernels/sgns.py: the scratch_shapes — gathered rows (v/c/n;
    4 bytes per lane for f32 rows and for bf16 pair words alike), f32
    grads (dv/dc/dn), and the combine's own footprint (eq: the (B,B)/(B,S)/
    (S,S) equality matrices, twice for pair words, which also sum partner
    rows; segsum: the sorted finals and f32 segment-prefix buffers, two
    prefix buffers for pair words) — plus the pipelined blocks, each
    double-buffered with every (rows, 1) column padded to 128 lanes: the
    (bb, 1) mask tile, for bf16 the (bb, 1)/(S, 1) pair-half selectors, and
    for eq the resident (B, 1)/(S, 1) index vectors.

    staging_rows models a co-resident cache-tier miss-staging block (the
    tiered trainer streams a (staging_rows, d) cold-row block alongside
    the update); 0 — the default — is the resident path.
    """
    item = jnp.dtype(dtype).itemsize
    packed = item == 2
    bb = min(block_b, B)
    L = B + S
    Sp = -(-S // 8) * 8
    total = (2 * B + S) * d * 4             # v_s, c_s, n_s (32-bit units)
    total += (2 * B + S) * d * 4            # dv_s, dc_s, dn_s
    total += 2 * bb * _LANE_ROW             # mask tile
    if packed:
        total += 2 * (2 * bb + Sp) * _LANE_ROW      # pair-half selectors
    if combine == "eq":
        total += (B * B + B * S + S * S) * 4 * (2 if packed else 1)
        total += 2 * (2 * B + Sp) * _LANE_ROW       # index vectors
    else:
        total += (B + L) * d * 4                    # fv_s, fc_s
        total += L * d * 4 * (2 if packed else 1)   # ps_s (+ ps2_s)
    total += staging_rows * d * item        # cache miss-staging block
    return total


def choose_block_b(B: int, d: int, S: int, dtype,
                   vmem_budget: int = roofline.VMEM_BUDGET_BYTES,
                   staging_rows: int = 0) -> int:
    """Pipeline tile rows from (B, d, S, dtype, VMEM budget).

    The tile only drives the per-step working set (two f32 (bb, d) row
    tiles, the (bb, S) logits/grads, the f32 grad tiles) and the pipeline
    depth, so the rule is: big enough to feed the MXU (cap 256), small
    enough that a tile's compute working set stays well under the budget.
    Batches past the cap get >= 2 grid steps automatically, which is where
    the double-buffered gather actually has a compute phase to hide behind;
    small batches run a single tile.
    """
    # per-tile active rows: the gathered v/c tile slices (table dtype) plus
    # the f32 compute temporaries (v/c casts, dv/dc, the (bb, S) logits);
    # a cache-tier staging block shrinks the budget the tile can claim
    per_row = 2 * d * jnp.dtype(dtype).itemsize + 4 * (4 * d + 2 * S)
    budget = max(per_row * 8,
                 vmem_budget - staging_rows * d * jnp.dtype(dtype).itemsize)
    cap = max(8, budget // 8 // per_row)
    bb = min(256, B, cap)
    if bb >= 8:
        bb -= bb % 8                    # f32 sublane alignment
    return max(1, bb)


def plan_fused_update(B: int, d: int, S: int, dtype, *,
                      block_b: int | None = None,
                      combine: str | None = None,
                      vmem_budget: int = roofline.VMEM_BUDGET_BYTES,
                      staging_rows: int = 0) -> FusedPlan:
    """Pick (block_b, combine, chunk_rows) for a B-row fused update.

    combine: equality-matrix reference up to sgns.EQ_COMBINE_MAX_ROWS
    padded rows while its O(B²) matrices fit the budget, segment-sum
    beyond.
    chunk_rows: the largest block_b multiple whose modeled VMEM fits the
    budget (at least one tile). The budget sits below the scoped VMEM limit
    every launch requests (``roofline.VMEM_LIMIT_BYTES``), and the model
    counts the lane-padded pipelined blocks, so every plan it returns
    compiles (``tests/test_tpu_compile.py``).

    Deliberate tradeoff when chunking kicks in: combine is decided from
    the WHOLE padded batch, so a batch too big for eq runs segsum chunks
    sized by segsum's (smaller) footprint — the fewest launches. Pass
    combine="eq" with a pinned block_b to force eq-sized chunks.

    staging_rows reserves VMEM headroom for a co-resident cache-tier
    miss-staging block (tiered trainer); 0 keeps the resident plan.
    NOTE: passing staging_rows to a call whose result feeds sgns_step can
    change block_b and thus the f32 gradient accumulation tiling — the
    tiered trainer therefore plans with the SAME (block_b=None,
    staging_rows=0) geometry as the resident path and uses this extended
    model only to validate that the geometry still fits with the staging
    block co-resident.
    """
    bb = block_b if block_b is not None else choose_block_b(
        B, d, S, dtype, vmem_budget, staging_rows)
    bb = min(bb, B)
    Bp = -(-B // bb) * bb               # rows after sgns_step's tile padding

    def fits(rows, comb):
        return fused_update_vmem_bytes(rows, d, S, dtype, comb, staging_rows,
                                       block_b=bb) <= vmem_budget
    if combine is None:
        combine = ("eq" if Bp <= _k.EQ_COMBINE_MAX_ROWS and fits(Bp, "eq")
                   else "segsum")
    if fits(Bp, combine):
        chunk = Bp                      # whole batch in one launch
    else:
        chunk = bb
        while chunk + bb < Bp and fits(chunk + bb, combine):
            chunk += bb
    return FusedPlan(block_b=bb, combine=combine, chunk_rows=chunk)


def _pad_to(x: jax.Array, mult: int, axis: int = 0, fill=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def sgns_grads(v, c, n, mask, *, impl: str = "ref",
               block_b: int | None = None):
    """loss + (dv, dc, dn) for a shared-negative SGNS minibatch.

    block_b=None autotunes the tile size (choose_block_b)."""
    _check_impl(impl, ("ref", "pallas"))
    if impl == "ref":
        return _ref.sgns_grads_ref(v, c, n, mask)
    B, d = v.shape
    S = n.shape[0]
    if block_b is None:
        block_b = choose_block_b(B, d, S, v.dtype)
    bb = min(block_b, B)
    vp, cp, mp = (_pad_to(v, bb), _pad_to(c, bb), _pad_to(mask, bb))
    loss, dv, dc, dn = _k.sgns_grads(vp, cp, n, mp, block_b=bb,
                                     interpret=interpret_mode())
    return loss, dv[:B], dc[:B], dn


STEP_IMPLS = ("ref", "pallas", "pallas_fused", "pallas_fused2")


def _check_impl(impl: str, allowed=STEP_IMPLS):
    if impl not in allowed:
        raise ValueError(f"unknown impl {impl!r}; expected one of {allowed}")


def gather_rows(table, idx, *, impl: str = "ref", rows_per_block: int = 8):
    _check_impl(impl, ("ref", "pallas"))
    if impl == "ref":
        return _ref.gather_rows_ref(table, idx)
    return _k.gather_rows(table, idx, rows_per_block=rows_per_block,
                          interpret=interpret_mode())


def scatter_add_rows(table, idx, upd, *, impl: str = "ref",
                     rows_per_block: int = 8):
    _check_impl(impl, ("ref", "pallas"))
    if impl == "ref":
        return _ref.scatter_add_rows_ref(table, idx, upd)
    return _k.scatter_add_rows(table, idx, upd,
                               rows_per_block=rows_per_block,
                               interpret=interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("impl", "reduction", "block_b"))
def sgns_step(vert, ctx, idx_v, idx_c, idx_n, mask, lr, *, impl: str = "ref",
              reduction: str = "sum", block_b: int | None = None):
    """One SGNS SGD minibatch against local (vert, ctx) shards.

    vert: (Nv, d), ctx: (Nc, d); idx_v/idx_c: (B,), idx_n: (S,), mask: (B,).
    Returns (vert', ctx', summed loss).

    ``block_b=None`` (the default) autotunes the whole launch geometry via
    :func:`plan_fused_update`; pass an int to pin the tile size. Batches
    larger than the plan's VMEM-sized ``chunk_rows`` run as sequential
    fused launches (each chunk's SGD apply lands before the next chunk
    gathers — coarser-grained sequential SGD, loss is the sum over chunks).

    ``reduction="sum"`` is word2vec-faithful: every pair's gradient is applied
    at full lr, and a shared-negative row accumulates up to B aligned
    contributions per step. This matches Ji et al. [19] / BlazingText [20]
    shared-negative batching and is stable for small-to-moderate B (the
    trainer's minibatch config). ``"mean"`` divides by B — stable at any B but
    under-weights positives relative to the shared negatives (degenerates; see
    EXPERIMENTS.md §Perf ablation). Default: sum.
    """
    _check_impl(impl)
    lr_eff = lr / mask.shape[0] if reduction == "mean" else lr
    if impl == "ref":
        return _ref.sgns_step_ref(vert, ctx, idx_v, idx_c, idx_n, mask, lr_eff)
    if impl in ("pallas_fused", "pallas_fused2"):
        # both fused branches tile B by bb and pad with (index 0, mask 0)
        # rows, which produce zero grads
        B = idx_v.shape[0]
        d = vert.shape[1]
        S = idx_n.shape[0]
        plan = plan_fused_update(B, d, S, vert.dtype, block_b=block_b)
        bb = plan.block_b
        if impl == "pallas_fused2":
            # fully-fused pipelined update: the kernel applies -lr*grad
            # straight to the aliased tables — no standalone scatter passes,
            # no (idx_c ++ idx_n) concatenate round-trip through HBM. The
            # kernel's duplicate-combine write-back makes padded positions
            # write row 0's correct final value.
            if B <= plan.chunk_rows:
                iv_p, ic_p, m_p = (_pad_to(idx_v, bb), _pad_to(idx_c, bb),
                                   _pad_to(mask, bb))
                return _k.sgns_fused_update(
                    vert, ctx, iv_p, ic_p, idx_n, m_p, lr_eff, block_b=bb,
                    combine=plan.combine, interpret=interpret_mode())
            # chunked launches: B rows don't fit one launch's VMEM —
            # sequential fused updates over chunk_rows-row slices
            loss = jnp.float32(0.0)
            for s in range(0, B, plan.chunk_rows):
                e = min(s + plan.chunk_rows, B)
                iv_c, ic_c, m_c = (_pad_to(idx_v[s:e], bb),
                                   _pad_to(idx_c[s:e], bb),
                                   _pad_to(mask[s:e], bb))
                vert, ctx, lc = _k.sgns_fused_update(
                    vert, ctx, iv_c, ic_c, idx_n, m_c, lr_eff,
                    block_b=bb, combine=plan.combine,
                    interpret=interpret_mode())
                loss = loss + lc
            return vert, ctx, loss
        # pallas_fused: one kernel for DMA-gather + grads (rows never
        # round-trip HBM), then standalone scatters. Scatter the REAL rows
        # only: padded zero-grad rows would be wasted DMAs, and their
        # repeated index 0 would serialize the blocks they land in.
        iv_p, ic_p, m_p = (_pad_to(idx_v, bb), _pad_to(idx_c, bb),
                           _pad_to(mask, bb))
        loss, dv, dc, dn = _k.sgns_fused_grads(
            vert, ctx, iv_p, ic_p, idx_n, m_p, block_b=bb,
            interpret=interpret_mode())
        vert = scatter_add_rows(vert, idx_v, -lr_eff * dv[:B], impl="pallas")
        idx_cn = jnp.concatenate([idx_c, idx_n])
        upd_cn = jnp.concatenate([-lr_eff * dc[:B], -lr_eff * dn])
        ctx = scatter_add_rows(ctx, idx_cn, upd_cn, impl="pallas")
        return vert, ctx, loss
    v = gather_rows(vert, idx_v, impl=impl)
    c = gather_rows(ctx, idx_c, impl=impl)
    n = gather_rows(ctx, idx_n, impl=impl)
    loss, dv, dc, dn = sgns_grads(v, c, n, mask, impl=impl, block_b=block_b)
    vert = scatter_add_rows(vert, idx_v, -lr_eff * dv, impl=impl)
    # combined ctx scatter (see ref.sgns_step_ref: keeps ctx aliasable)
    idx_cn = jnp.concatenate([idx_c, idx_n])
    upd_cn = jnp.concatenate([-lr_eff * dc, -lr_eff * dn])
    ctx = scatter_add_rows(ctx, idx_cn, upd_cn, impl=impl)
    return vert, ctx, loss

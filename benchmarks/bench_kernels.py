"""SGNS kernel microbenchmark: the per-PR performance trajectory.

Sweeps ``ops.sgns_step`` over (B, d, S, block_b) for every impl and APPENDS
a timestamped run to ``BENCH_kernels.json`` (so the roofline trajectory is
an actual trajectory across PRs) with rows/s, a bytes-moved model, and the
roofline bound from ``launch/roofline.py`` (see benchmarks/README.md for
the field reference). On this CPU container the Pallas impls run in
interpret mode — Python-slow, so their absolute numbers only track
*relative* regressions in kernel structure; the ``ref`` impl numbers and
the roofline bound are the meaningful trajectory. On TPU the same harness
measures the real thing.

    PYTHONPATH=src python benchmarks/bench_kernels.py          # full sweep
    PYTHONPATH=src python benchmarks/bench_kernels.py --smoke  # CI: 1 shape
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

from common import append_run, load_runs                     # noqa: E402,F401
from repro.kernels import ops                                # noqa: E402
from repro.launch import roofline                            # noqa: E402

IMPLS = ops.STEP_IMPLS

# (B, d, S, block_b): shared-negative minibatch geometry. The first entry is
# the hybrid trainer's production SMALL config shape.
FULL_SHAPES = [
    (64, 96, 16, 64),
    (128, 128, 16, 64),
    (256, 128, 32, 128),
    (512, 256, 32, 128),
    # past the old (B, B) equality-matrix wall: pallas_fused2 runs the
    # sort-based segment-sum combine here (ops.plan_fused_update)
    (2048, 128, 16, 256),
]
SMOKE_SHAPES = [
    (32, 32, 8, 16),
    (48, 32, 8, 16),   # odd multiple: exercises multi-tile pipelining
    (64, 64, 8, 32),
    (64, 64, 16, 64),
]


def bytes_moved_model(B: int, d: int, S: int, itemsize: int,
                      impl: str) -> int:
    """HBM bytes for one sgns_step under each impl's execution structure.

    Row traffic per step: gathers read (2B + S) rows; the SGD apply reads
    and writes the same rows (scatter-add is read-modify-write). The
    non-fused impls additionally round-trip the (B,d) dv/dc and (S,d) dn
    gradient tensors and the gathered copies through HBM between kernels;
    pallas_fused keeps the gather+grads on-chip but still scatters from HBM
    gradient tensors; pallas_fused2 moves each row exactly once each way.
    """
    row = d * itemsize
    grad_row = d * 4  # grads are f32
    table_rw = (2 * B + S) * row * 2            # gather reads + apply writes
    if impl == "pallas_fused2":
        return table_rw                          # one round-trip per row
    grads = (2 * B + S) * grad_row * 2          # grads written then re-read
    if impl == "pallas_fused":
        return table_rw + grads + (2 * B + S) * row  # scatter re-reads rows
    gathered = (2 * B + S) * row * 2            # gathered copies out + in
    return table_rw + grads + gathered


def roofline_bound_rows_s(B: int, d: int, S: int, itemsize: int) -> float:
    """Memory-bound rows/s ceiling: the paper's O(1) arithmetic-intensity
    analysis says HBM bandwidth is the binding term, so the bound is the
    minimal traffic (fused2's one round-trip per row) at full HBM_BW."""
    min_bytes = bytes_moved_model(B, d, S, itemsize, "pallas_fused2")
    return B / (min_bytes / roofline.HBM_BW)


def time_step(impl: str, B: int, d: int, S: int, block_b: int,
              iters: int, dtype=jnp.float32) -> dict:
    Nv = Nc = max(4 * B, 256)
    key = jax.random.PRNGKey(0)
    vert = (jax.random.normal(key, (Nv, d)) * 0.1).astype(dtype)
    ctx = (jax.random.normal(jax.random.fold_in(key, 1), (Nc, d))
           * 0.1).astype(dtype)
    iv = jax.random.randint(jax.random.fold_in(key, 2), (B,), 0, Nv)
    ic = jax.random.randint(jax.random.fold_in(key, 3), (B,), 0, Nc)
    inn = jax.random.randint(jax.random.fold_in(key, 4), (S,), 0, Nc)
    mask = jnp.ones(B)
    lr = jnp.float32(0.025)

    def step(v, c):
        return ops.sgns_step(v, c, iv, ic, inn, mask, lr, impl=impl,
                             block_b=block_b)

    vert, ctx, loss = step(vert, ctx)            # compile + warm up
    jax.block_until_ready((vert, ctx, loss))
    loss0 = float(loss)       # first-step loss: impl-parity canary (identical
    t0 = time.perf_counter()  # inputs across impls; timed iterates diverge)
    for _ in range(iters):
        vert, ctx, loss = step(vert, ctx)
    jax.block_until_ready((vert, ctx, loss))
    dt = (time.perf_counter() - t0) / iters
    itemsize = jnp.dtype(dtype).itemsize
    moved = bytes_moved_model(B, d, S, itemsize, impl)
    bound = roofline_bound_rows_s(B, d, S, itemsize)
    return {
        "impl": impl,
        "step_s": dt,
        "rows_per_s": B / dt,
        "bytes_moved_model": moved,
        "achieved_gbps_model": moved / dt / 1e9,
        "roofline_bound_rows_per_s": bound,
        "frac_of_roofline": (B / dt) / bound,
        "first_step_loss": loss0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / 1 iter (CI regression canary)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_kernels.json"))
    ap.add_argument("--impls", default=",".join(IMPLS))
    args = ap.parse_args()

    shapes = SMOKE_SHAPES if args.smoke else FULL_SHAPES
    interpret = ops.interpret_mode()
    # interpret-mode pallas is Python-slow: 1 timed iter is plenty there
    ref_iters = args.iters or (2 if args.smoke else 10)
    pallas_iters = args.iters or 1 if interpret else ref_iters

    impls = tuple(args.impls.split(","))
    results = []
    for (B, d, S, bb) in shapes:
        for impl in impls:
            iters = ref_iters if impl == "ref" else pallas_iters
            r = time_step(impl, B, d, S, bb, iters)
            r.update(B=B, d=d, S=S, block_b=bb)
            results.append(r)
            print(f"B={B:4d} d={d:4d} S={S:3d} bb={bb:4d} {impl:14s} "
                  f"{r['rows_per_s']:12.1f} rows/s   "
                  f"{r['frac_of_roofline']*100:8.4f}% of roofline")

    # cross-impl parity on the last shape: the benchmark itself verifies the
    # fused path's numerics so a silent kernel break can't post a fast number
    losses = {r["impl"]: r["first_step_loss"] for r in results
              if (r["B"], r["d"], r["S"]) == shapes[-1][:3]}
    if "ref" in losses:
        for impl, lv in losses.items():
            assert abs(lv - losses["ref"]) <= 1e-3 * max(1.0, abs(
                losses["ref"])), (impl, lv, losses["ref"])

    run = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "interpret_mode": interpret,
        "dtype": "float32",
        "hbm_bw_model_bytes_per_s": roofline.HBM_BW,
        "note": ("interpret-mode pallas timings are Python-bound; compare "
                 "ref timings and structural byte counts across PRs, and "
                 "absolute pallas timings only on TPU"),
        "results": results,
    }
    n = append_run(args.out, "sgns_kernels", run)
    print(f"wrote {os.path.abspath(args.out)} "
          f"(run {n}, {len(results)} rows)")


if __name__ == "__main__":
    main()

"""The work each call needs, counted from its inputs, whatever implements it.

SGNS minibatch of B samples with S shared negatives at width d: every
distinct row it touches has to come from HBM once and go back once, so
the bytes are (unique vertex rows + unique context rows + S negative
rows) x d x itemsize x 2. The negatives are drawn on the device, so they
count as S rows. A minibatch with no valid sample needs nothing. The
operations are those of the forward dot products and the three gradient
products: per valid sample 2d (positive) + 2dS (negatives) + 2d + 2dS
(vertex gradient) + 2d (context gradient) + 2dS (negative gradient).

Top-k scan: one read of the table per call, and 2 x Q x N x d operations
for the scores of the Q queries it serves.
"""
from __future__ import annotations

import numpy as np


def sgns_episode(blocks: np.ndarray, counts: np.ndarray, *, minibatch: int,
                 negatives: int, dim: int, itemsize: int) -> dict:
    """Bytes and operations of one episode's staged blocks.

    blocks: (..., Bmax, 2) (vertex row, context row) per cell; counts:
    (...) valid samples per cell, the first ``count`` of each cell.
    """
    b = blocks.reshape(-1, blocks.shape[-2], 2)
    c = counts.reshape(-1)
    rows = 0
    valid = 0
    for cell in range(b.shape[0]):
        n = int(c[cell])
        valid += n
        if n == 0:
            continue
        nmb = -(-n // minibatch)
        pad = nmb * minibatch - n
        x = np.concatenate([b[cell, :n], np.full((pad, 2), -1, np.int64)])
        x = x.reshape(nmb, minibatch, 2)
        rows += _unique_per_row(x[..., 0]) + _unique_per_row(x[..., 1])
        rows += nmb * negatives
    flops = valid * (6 * dim * negatives + 6 * dim)
    return {"bytes": float(rows * dim * itemsize * 2), "flops": float(flops),
            "samples": valid}


def _unique_per_row(x: np.ndarray) -> int:
    """Distinct values >= 0 in each row, summed over rows."""
    s = np.sort(x, axis=1)
    new = np.ones_like(s, dtype=bool)
    new[:, 1:] = s[:, 1:] != s[:, :-1]
    return int((new & (s >= 0)).sum())


def topk_scan(rows: int, dim: int, itemsize: int, queries: int) -> dict:
    return {"bytes": float(rows * dim * itemsize),
            "flops": float(2 * queries * rows * dim)}

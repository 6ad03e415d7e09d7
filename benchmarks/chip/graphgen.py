"""The deployment's graph: preferential attachment over a chip's rows.

A copy of the repo's ``powerlaw_graph`` (Barabasi-Albert style, the same
random draws, so the same graph) with a CSR build that sorts once. The
graph is fixed by the configuration's ``graph_seed``, not by a run's
seed, and is cached as ``.npy`` files under ``.cache/`` beside this file
(ignored by git), so only a checkout's first run builds it.
"""
from __future__ import annotations

import os

import numpy as np

CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")


def powerlaw_edges(n: int, m_per_node: int, seed: int) -> np.ndarray:
    """(m, 2) int64 edges; node v >= n0 attaches to m_per_node endpoints
    drawn from the endpoint list as it stood before v's batch."""
    rng = np.random.default_rng(seed)
    n0 = max(m_per_node + 1, 4)
    src0 = np.repeat(np.arange(n0), n0 - 1)
    dst0 = np.concatenate([np.delete(np.arange(n0), i) for i in range(n0)])
    m = src0.size + (n - n0) * m_per_node
    src = np.empty(m, np.int64)
    dst = np.empty(m, np.int64)
    src[:src0.size], dst[:src0.size] = src0, dst0
    # the endpoint list grows by (sources, then targets) of each batch
    pool = np.empty(2 * m, np.int64)
    pool[:src0.size], pool[src0.size:2 * src0.size] = src0, dst0
    filled, e = 2 * src0.size, src0.size
    batch = max(1024, n // 64)
    v = n0
    while v < n:
        nb = min(batch, n - v)
        k = nb * m_per_node
        new_src = np.repeat(np.arange(v, v + nb), m_per_node)
        targets = pool[rng.integers(0, filled, size=k)]
        src[e:e + k], dst[e:e + k] = new_src, targets
        pool[filled:filled + k] = new_src
        pool[filled + k:filled + 2 * k] = targets
        filled += 2 * k
        e += k
        v += nb
    return np.stack([src, dst], axis=1)


def csr(edges: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Symmetrised, self-loop-free, deduplicated CSR (indptr, indices)
    with each row's neighbours ascending."""
    s, d = edges[:, 0], edges[:, 1]
    keep = s != d
    s, d = s[keep], d[keep]
    keys = np.unique(np.concatenate([s * n + d, d * n + s]))
    src = keys // n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, (keys % n).astype(np.int32)


def powerlaw_graph(n: int, m_per_node: int, seed: int):
    """(indptr, indices) of the graph, from the cache when it is there."""
    cache = CACHE
    stem = os.path.join(cache, f"powerlaw-n{n}-m{m_per_node}-s{seed}")
    names = (stem + ".indptr.npy", stem + ".indices.npy")
    if all(os.path.exists(p) for p in names):
        return tuple(np.load(p) for p in names)
    out = csr(powerlaw_edges(n, m_per_node, seed), n)
    os.makedirs(cache, exist_ok=True)
    for arr, path in zip(out, names):
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            np.save(f, arr)
        os.replace(tmp, path)
    return out

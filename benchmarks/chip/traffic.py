"""The one generator every traffic mix goes through.

A mix is a data file, ``traffic/<name>.json``; its ``kind`` names the
driver in ``cells/<kind>.py`` that consumes the streams below:

* ``episodes`` -- training samples: DeepWalk walks (p = q = 1) from start
  nodes drawn uniformly from the seed, turned into (center, context) pairs
  within ``window`` hops like the repo's ``walks_to_pairs`` (forward pairs,
  by hop distance, self pairs dropped), cut to exactly
  ``samples_per_chip`` x chips pairs per episode.
* ``topk`` -- serving requests: due times from ``arrivals`` (a Poisson or
  an on/off stream at ``rate_qps``), each request ``queries_per_request``
  queries, a query the table row of a node drawn Zipf(``zipf_a``).

Every seed gets the same amount of work: the same number of pairs per
episode, and the same number of requests over the window.
"""
from __future__ import annotations

import numpy as np


def walks(indptr, indices, starts, length, rng) -> np.ndarray:
    """(W, length + 1) uniform random walks; a dead end stays in place."""
    out = np.empty((starts.size, length + 1), np.int32)
    out[:, 0] = starts
    m = indices.size
    for t in range(length):
        cur = out[:, t].astype(np.int64)
        deg = indptr[cur + 1] - indptr[cur]
        off = rng.integers(0, np.maximum(deg, 1))
        nxt = indices[np.minimum(indptr[cur] + off, m - 1)]
        out[:, t + 1] = np.where(deg > 0, nxt, cur)
    return out


def walk_pairs(w: np.ndarray, window: int) -> np.ndarray:
    """(W, L+1) walks -> (P, 2) int32 pairs (w[t], w[t+h]), h = 1..window,
    ordered by h, then walk, then t; self pairs dropped."""
    L1 = w.shape[1]
    out = [np.stack([w[:, :L1 - h].ravel(), w[:, h:].ravel()], axis=1)
           for h in range(1, min(window, L1 - 1) + 1)]
    pairs = np.concatenate(out).astype(np.int32)
    return pairs[pairs[:, 0] != pairs[:, 1]]


def episode(indptr, indices, samples: int, length: int, window: int,
            rng) -> np.ndarray:
    """Exactly ``samples`` pairs of one episode."""
    n = indptr.size - 1
    per_walk = sum(length + 1 - h for h in range(1, window + 1))
    got, need = [], samples
    while need > 0:
        starts = rng.integers(0, n, -(-need * 21 // (20 * per_walk)) + 1)
        p = walk_pairs(walks(indptr, indices, starts, length, rng), window)
        got.append(p[:need])
        need -= got[-1].shape[0]
    return np.concatenate(got)


def episodes(indptr, indices, traffic: dict, chips: int, rng):
    """The mix's distinct episodes, replayed in turn by the window."""
    samples = traffic["samples_per_chip"] * chips
    return [episode(indptr, indices, samples, traffic["walk_length"],
                    traffic["window"], rng)
            for _ in range(traffic["replay"])]


def arrivals(mix: dict, seconds: float, rng) -> np.ndarray:
    """Due times (s from the window's start, ascending) of the mix's
    requests: exactly ``round(rate_qps x seconds)`` of them, so that every
    seed offers the same work in another order.

    ``arrival`` "poisson" (the default) is a Poisson stream at ``rate_qps``
    given its count: uniform times over the window. "onoff" keeps the count
    and so the mean rate, but puts every request into the first ``on_s``
    seconds of each ``on_s + off_s`` period, uniform within the on-time.
    """
    n = max(1, round(mix["rate_qps"] * seconds))
    process = mix.get("arrival", "poisson")
    if process == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    if process == "onoff":
        on, period = mix["on_s"], mix["on_s"] + mix["off_s"]
        full, rest = divmod(seconds, period)
        u = np.sort(rng.uniform(0.0, full * on + min(rest, on), n))
        return (u // on) * period + u % on
    raise ValueError(f"unknown arrival process {process!r}")


def zipf_nodes(n: int, a: float, count: int, rng) -> np.ndarray:
    """``count`` node ids in [0, n): rank r (1-based) with weight r^-a,
    node = r - 1."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -a)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(count)),
                      n - 1).astype(np.int32)

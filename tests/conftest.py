# NOTE: deliberately no XLA_FLAGS here — smoke tests must see the real
# (single) device. Multi-device behaviour is tested via subprocesses in
# test_distributed.py, and the 512-device production mesh only ever exists
# inside `python -m repro.launch.dryrun` (which sets the flag first-thing).
import numpy as np
import pytest


@pytest.fixture(scope="session")
def sbm_graph():
    """Small stochastic-block-model graph with real community structure."""
    from repro.graph.csr import build_csr
    rng = np.random.default_rng(0)
    n, k = 1200, 12
    comm = rng.integers(0, k, n)
    src, dst = [], []
    for _ in range(30):
        a = rng.integers(0, n, 20000)
        b = rng.integers(0, n, 20000)
        p = np.where(comm[a] == comm[b], 0.08, 0.001)
        keep = rng.random(20000) < p
        src.append(a[keep])
        dst.append(b[keep])
    edges = np.stack([np.concatenate(src), np.concatenate(dst)], 1)
    return build_csr(edges, n)

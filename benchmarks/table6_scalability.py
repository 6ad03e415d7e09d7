"""Tables VI/VII + Fig. 6/7 analogue: intra-node scalability at 1/2/4/8
devices, ours vs the parameter-server baseline.

Every device count runs in this one process, as a sub-mesh of the first
n devices of ``jax.devices()`` (a process that holds a chip must not start
children that need it). Counts above the devices present are skipped; on
the CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives the
process eight virtual devices. On one physical CPU core the *compute*
cannot speed up; what the benchmark then shows is the per-device-count
dispatch/communication structure (ours: one jitted episode; PS baseline:
4*n^2*k host round-trips per epoch) and the paper's schedule invariance.
"""
import jax

from benchmarks.common import sbm_graph, time_epochs
from repro.core import (HybridConfig, HybridEmbeddingTrainer,
                        ParameterServerTrainer)


def run():
    out = []
    devices = jax.devices()
    g = sbm_graph(n=2000, rounds=30)
    cfg = HybridConfig(dim=64, minibatch=64, negatives=5, subparts=2,
                       neg_pool=2048, lr=0.025)
    for n in (1, 2, 4, 8):
        if n > len(devices):
            out.append(f"table6/devices{n},SKIPPED,{len(devices)} device(s)")
            continue
        mesh = jax.make_mesh((1, n), ("data", "model"), devices=devices[:n])
        hy = HybridEmbeddingTrainer(g.num_nodes, mesh, cfg,
                                    degrees=g.degrees())
        hy.init_embeddings()
        t_h, _ = time_epochs(hy, g, cfg, epochs=2)
        ps = ParameterServerTrainer(g.num_nodes, n, cfg, degrees=g.degrees())
        t_p, _ = time_epochs(ps, g, cfg, epochs=2)
        out.append(f"table6/ours_{n}dev_epoch_s,{t_h*1e6:.0f},")
        out.append(f"table6/ps_{n}dev_epoch_s,{t_p*1e6:.0f},"
                   f"host_syncs={ps.counters.host_syncs}")
        out.append(f"table6/speedup_{n}dev,{t_p/t_h:.3f},")
    return out

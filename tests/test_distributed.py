"""True multi-device correctness, via subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (the main pytest process
deliberately stays single-device; see conftest.py)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 4, timeout: int = 420):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.mark.slow
def test_moe_ep_matches_oracle_4dev():
    run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.config import ModelConfig
from repro.models import mlp
cfg = ModelConfig(name='t', arch_type='moe', num_layers=1, d_model=64,
                  num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=64,
                  moe_num_experts=8, moe_top_k=2, moe_d_ff=96,
                  moe_capacity_factor=8.0)
key = jax.random.PRNGKey(0)
params = mlp.init_moe_params(key, cfg)
mesh = jax.make_mesh((2, 2), ('data', 'model'))
x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 64))
y0, _ = mlp.moe_ref(params, x, cfg)
xs = jax.device_put(x, NamedSharding(mesh, P('data', 'model', None)))
y1, _ = jax.jit(lambda p, xx: mlp.moe_forward(p, xx, cfg, mesh=mesh))(params, xs)
np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-4, atol=1e-4)
# decode/quota path
x1 = jax.random.normal(jax.random.fold_in(key, 2), (4, 1, 64))
y0, _ = mlp.moe_ref(params, x1, cfg)
xs1 = jax.device_put(x1, NamedSharding(mesh, P('data', None, None)))
y1, _ = jax.jit(lambda p, xx: mlp.moe_forward(p, xx, cfg, mesh=mesh))(params, xs1)
np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-4, atol=1e-4)
print('OK')
""")


@pytest.mark.slow
def test_hybrid_multidevice_quality_parity():
    """The rotation schedule on a 2x2 mesh with k=2 sub-parts must reach the
    same quality as single-device training (the paper's Fig. 5 claim)."""
    run_py(r"""
import jax, numpy as np
from repro.core import HybridConfig, HybridEmbeddingTrainer, build_episode_blocks
from repro.core import eval as ev
from repro.graph.csr import build_csr
from repro.walk import MemorySampleStore, WalkConfig, WalkEngine
rng = np.random.default_rng(0)
n = 1200
comm = rng.integers(0, 12, n)
src, dst = [], []
for _ in range(30):
    a = rng.integers(0, n, 20000); b = rng.integers(0, n, 20000)
    keep = rng.random(20000) < np.where(comm[a]==comm[b], 0.08, 0.001)
    src.append(a[keep]); dst.append(b[keep])
g_full = build_csr(np.stack([np.concatenate(src), np.concatenate(dst)],1), n)
train_e, test_e = ev.split_edges(g_full, 0.05, seed=1)
g = build_csr(train_e, n, symmetrize=False, dedup=False)
neg_e = ev.sample_negative_pairs(g_full, len(test_e), seed=3)

def run(mesh_shape, k):
    mesh = jax.make_mesh(mesh_shape, ('data','model'))
    cfg = HybridConfig(dim=64, minibatch=32, negatives=8, subparts=k,
                       neg_pool=2048, lr=0.025)
    tr = HybridEmbeddingTrainer(n, mesh, cfg, degrees=g.degrees())
    tr.init_embeddings()
    store = MemorySampleStore()
    E = 10
    for epoch in range(E):
        WalkEngine(g, WalkConfig(walk_length=10, window=5, episodes=1,
                                 seed=epoch), store).run_epoch(epoch)
        eb = build_episode_blocks(np.asarray(store.get(epoch,0)), tr.part,
                                  pad_multiple=32)
        assert eb.dropped == 0
        tr.train_episode(eb, lr=0.025*max(1-epoch/E, 0.05))
        store.drop_epoch(epoch)
    V = tr.embeddings()
    Vn = V/(np.linalg.norm(V,axis=1,keepdims=True)+1e-9)
    return ev.auc_score(np.einsum('ij,ij->i', Vn[test_e[:,0]], Vn[test_e[:,1]]),
                        np.einsum('ij,ij->i', Vn[neg_e[:,0]], Vn[neg_e[:,1]]))

a1 = run((1,1), 1)
a4 = run((2,2), 2)
print('auc1', a1, 'auc4', a4)
assert a4 > a1 - 0.04, (a1, a4)
""")


@pytest.mark.slow
def test_lm_train_step_sharded_4dev():
    """One sharded LM train step on a 2x2 mesh (GSPMD path end-to-end)."""
    run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from repro import configs as cfgs
from repro.models import transformer as tfm
from repro.sharding.specs import param_shardings
from repro.train.train_step import make_train_step, synthetic_batch
import dataclasses
cfg = cfgs.get_config('phi3.5-moe-42b-a6.6b').reduced(layers=2, d_model=256,
                                                      experts=4)
cfg = dataclasses.replace(cfg, train_microbatches=2)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((2, 2))
params = tfm.init_params(jax.random.PRNGKey(0), cfg)
p_sh = param_shardings(params, mesh)
params = jax.device_put(params, p_sh)
step_fn, opt = make_train_step(cfg, mesh=mesh, data_axes=('data',))
opt_state = jax.device_put(opt.init(params), param_shardings(
    jax.eval_shape(opt.init, params), mesh))
batch = {k: jnp.asarray(v) for k, v in synthetic_batch(cfg, 4, 32).items()}
with mesh:
    p2, o2, m = jax.jit(step_fn)(params, opt_state, jnp.int32(0), batch)
assert np.isfinite(float(m['loss']))
print('loss', float(m['loss']))
""")

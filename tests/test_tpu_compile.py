"""Compile the main path's kernels for a described TPU v5e.

Nothing here needs a chip: the TPU compiler is installed, and it compiles
for a topology that is described, not attached. It refuses what interpret
mode accepts — tile-misaligned DMA slices, more VMEM than a launch may use,
a program that does not fit the device — so these cases guard every kernel
of the main path (train: the fused SGNS update and the one-chip episode
step; serve: the exact and int8 top-k scans) at real width, d=128.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and every test worker imports this
file). The fixture also steers the kernels off interpret mode — the
default backend here is the CPU — and turns the persistent compilation
cache off, since an entry compiled for a described chip cannot be read
back without one.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels import ops

D = 128            # the paper configuration's width
NEGATIVES = 5
TABLE_ROWS = 1 << 20
CELL_ROWS = 6_562_500   # one chip's share of the paper's Anonymized A table


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "interpret_mode", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    mp.undo()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is there
    return compiled


def _kernel_names(text):
    """The HLO names of the Pallas kernels in a compiled module, without
    their numeric suffix: what the trace readers match."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("B,combine", [(256, "eq"), (8192, "segsum"),
                                       (16384, "segsum")])
def test_sgns_step_fused2_compiles(one_chip, dtype, B, combine):
    """ops.sgns_step as the trainer calls it: the plan the planner returns
    (eq at the paper's minibatch, segsum past it, chunked launches at
    16384 rows) compiles, and updates the tables in place — no table-sized
    temporary, so bf16's pair-word view costs no copy."""
    plan = ops.plan_fused_update(B, D, NEGATIVES, dtype)
    assert plan.combine == combine, plan
    assert (plan.chunk_rows < B) == (B == 16384), plan

    def step(vert, ctx, iv, ic, inn, mask):
        return ops.sgns_step(vert, ctx, iv, ic, inn, mask, jnp.float32(0.025),
                             impl="pallas_fused2")
    table = _spec((TABLE_ROWS, D), dtype, one_chip)
    idx = _spec((B,), jnp.int32, one_chip)
    compiled = _compile(step, table, table, idx, idx,
                        _spec((NEGATIVES,), jnp.int32, one_chip),
                        _spec((B,), jnp.float32, one_chip), donate=(0, 1))
    table_bytes = TABLE_ROWS * D * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < table_bytes // 8


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("k", [10, 100])
def test_topk_mips_compiles(one_chip, dtype, k):
    """The exact scan keeps its name inside a larger jitted program."""
    from repro.embed_serve import topk as tk

    def serve_step(t, q):
        v, i = tk.topk_mips(t, q, k=k, interpret=False)
        return v * 2.0, i + 1
    compiled = _compile(serve_step, _spec((TABLE_ROWS, D), dtype, one_chip),
                        _spec((256, D), jnp.float32, one_chip))
    assert _kernel_names(compiled.as_text()) == {"topk_mips"}


@pytest.mark.parametrize("k", [10, 100])
def test_topk_mips_compiles_at_cell_shape(one_chip, k):
    """The gated scan as the store calls it in the serving cell (merge
    counts included): the f32 shard of 6,562,500 valid rows padded to
    512-row tiles, two 128-row query blocks, the planner's 512-row tile."""
    from repro.embed_serve import topk as tk
    assert tk.choose_block_n(D, jnp.float32, k=k) == 512

    def serve_step(t, q):
        v, i, groups = tk.topk_mips_counted(t, q, k=k, valid=CELL_ROWS,
                                            block_n=512, interpret=False)
        return v * 2.0, i + 1, groups
    rows = -(-CELL_ROWS // 512) * 512
    compiled = _compile(serve_step, _spec((rows, D), jnp.float32, one_chip),
                        _spec((256, D), jnp.float32, one_chip))
    assert _kernel_names(compiled.as_text()) == {"topk_mips"}
    assert _scan_grids(serve_step, (rows, D), jnp.float32) == [
        (1, rows // 512)]


def _scan_grids(fn, table_shape, dtype, *args):
    """The grids of the Pallas calls ``fn(table, queries, *args)`` makes
    at 256 query rows: one table pass a call is (1, N / bn)."""
    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(table_shape, dtype),
                               *args,
                               jax.ShapeDtypeStruct((256, D), jnp.float32))
    return [tuple(e.params["grid_mapping"].grid) for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("m", [40, 400])
def test_topk_mips_quant_compiles(one_chip, m):
    """The two-tier int8 scan as the store runs it on the chip: the int8
    first pass over-fetching m = 4k candidates (k=10 and k=100), then the
    kernel gather and exact rescore of the survivors."""
    from repro.embed_serve import quant as qz
    compiled = _compile(lambda t, t8, s, q: qz.topk_mips_quant_rescored(
                            t, t8, s, q, k=m // 4, overfetch=4.0,
                            impl="pallas", interpret=False),
                        _spec((TABLE_ROWS, D), jnp.float32, one_chip),
                        _spec((TABLE_ROWS, D), jnp.int8, one_chip),
                        _spec((TABLE_ROWS,), jnp.float32, one_chip),
                        _spec((256, D), jnp.float32, one_chip))
    assert "topk_mips_quant" in _kernel_names(compiled.as_text())


A40_ROWS = 26_250_000   # one chip's share of the same table over 40 chips


def test_store_int8_path_compiles_at_cell_shape(one_chip):
    """The store's int8 path as the int8 serving cell runs it: the counted
    first pass over the int8 copy of a 26,250,240-row bf16 shard (26.25 M
    valid rows in 512-row tiles) at m = 40 for 256 queries, then the
    rescore, its own program, gathering the 10,240 candidate rows of the
    bf16 shard with the pair-word gather."""
    from repro.embed_serve import quant as qz
    from repro.embed_serve import topk as tk
    assert tk.choose_block_n(D, jnp.bfloat16) == 512
    rows = -(-A40_ROWS // 512) * 512
    m = qz.overfetch_m(10, qz.DEFAULT_OVERFETCH, A40_ROWS)
    assert (rows, m) == (26_250_240, 40)
    q = _spec((256, D), jnp.float32, one_chip)
    first = tk.topk_mips_quant_counted.lower(
        _spec((rows, D), jnp.int8, one_chip),
        _spec((rows,), jnp.float32, one_chip), q, m=m, valid=A40_ROWS,
        block_n=512).compile()
    assert _kernel_names(first.as_text()) == {"topk_mips_quant"}
    assert _scan_grids(
        lambda t, s, q: tk.topk_mips_quant_counted(
            t, s, q, m=m, valid=A40_ROWS, block_n=512),
        (rows, D), jnp.int8, jax.ShapeDtypeStruct((rows,), jnp.float32)
    ) == [(1, rows // 512)]
    rescore = qz.rescore_exact.lower(
        _spec((rows, D), jnp.bfloat16, one_chip), q,
        _spec((256, m), jnp.int32, one_chip), k=10,
        gather="pallas").compile()
    text = rescore.as_text()
    assert re.match(r"HloModule jit_rescore_exact\b", text)
    assert "tpu_custom_call" in text               # the gather kernel
    table_bytes = rows * D * 2
    assert rescore.memory_analysis().temp_size_in_bytes < table_bytes // 64


def test_device_quantizer_compiles_at_cell_shape(one_chip):
    """The store's device quantizer over the bf16 shard: its temporaries
    are a block's, not the shard's f32 copy."""
    from repro.embed_serve import quant as qz
    rows = -(-A40_ROWS // 512) * 512
    compiled = qz.quantize_device.lower(
        _spec((rows, D), jnp.bfloat16, one_chip)).compile()
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes < rows * (D + 4) * 1.001
    assert ma.temp_size_in_bytes < 16 * qz.QUANT_BLOCK_ROWS * D * 4


def test_topk_rowwise_kernel_is_named():
    """The rowwise scan is the interpret-mode reference: the TPU compiler
    refuses its one-row blocks, so its name is read from the program
    before lowering."""
    from repro.embed_serve import topk as tk
    jaxpr = jax.make_jaxpr(lambda t, q: tk.topk_mips_rowwise(
        t, q, k=4, interpret=True))(jnp.zeros((16, D)), jnp.zeros((2, D)))
    names = [e.params["name"] for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert names == ["topk_mips_rowwise"]


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("chips", [1, 4])
def test_episode_step_compiles(topo, one_chip, chips):
    """The whole hybrid episode step at f32, the paper's widths: ring scans,
    ppermutes and the fused kernel in one program, on one chip and as the
    (1, 4) mesh of a four-chip host, with each chip's share fitting its
    16 GB of HBM."""
    from repro.configs.tencent_embedding import CONFIG
    from repro.core.hybrid import HybridConfig, build_episode_fn
    from repro.core.partition import NodePartition
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    cfg = HybridConfig(dim=D, negatives=CONFIG.negatives,
                       minibatch=CONFIG.minibatch, subparts=CONFIG.subparts,
                       neg_pool=CONFIG.neg_pool, dtype="float32",
                       impl="pallas_fused2")
    part = NodePartition(TABLE_ROWS, dims=(1, chips), subparts=cfg.subparts)
    fn, sh = build_episode_fn(mesh, part, cfg)
    bmax = 4 * cfg.minibatch
    n = part.padded_num_nodes
    args = (
        _spec((n, D), jnp.float32, sh["table"]),
        _spec((n, D), jnp.float32, sh["table"]),
        _spec((chips, 1, chips, cfg.subparts, bmax, 2), jnp.int32,
              sh["blocks"]),
        _spec((chips, 1, chips, cfg.subparts), jnp.int32, sh["blocks"]),
        _spec((chips, cfg.neg_pool), jnp.int32, sh["blocks"]),
        _spec((1,), jnp.int32, sh["replicated"]),
        _spec((), jnp.float32, sh["replicated"]),
    )
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "/episode_step/" in text            # the step's named scope
    if chips > 1:
        assert "collective-permute" in text       # the ring rotation
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < 16 * 10**9, used

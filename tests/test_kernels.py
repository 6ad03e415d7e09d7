"""Per-kernel allclose vs the pure-jnp oracle (interpret mode on CPU),
swept over shapes/dtypes + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref, sgns


KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, k=0, scale=0.1):
    return (jax.random.normal(jax.random.fold_in(KEY, k), shape) * scale
            ).astype(dtype)


@pytest.mark.parametrize("B,d,S,block_b", [
    (128, 128, 16, 64),
    (256, 64, 8, 256),
    (512, 256, 32, 128),
    (64, 32, 4, 64),
])
def test_sgns_grads_matches_ref(B, d, S, block_b):
    v, c, n = _rand((B, d), k=1), _rand((B, d), k=2), _rand((S, d), k=3)
    mask = (jax.random.uniform(jax.random.fold_in(KEY, 4), (B,)) > 0.2
            ).astype(jnp.float32)
    l0, dv0, dc0, dn0 = ref.sgns_grads_ref(v, c, n, mask)
    l1, dv1, dc1, dn1 = sgns.sgns_grads(v, c, n, mask, block_b=block_b,
                                        interpret=True)
    np.testing.assert_allclose(l0, l1, rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(dv0, dv1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dc0, dc1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dn0, dn1, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("N,d,B", [(50, 128, 20), (200, 64, 64), (7, 32, 9)])
def test_gather_rows(N, d, B):
    tbl = _rand((N, d), k=5)
    idx = jax.random.randint(jax.random.fold_in(KEY, 6), (B,), 0, N)
    out = sgns.gather_rows(tbl, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.gather_rows_ref(tbl, idx)))


@pytest.mark.parametrize("dup", [False, True])
def test_scatter_add_rows(dup):
    N, d, B = 40, 64, 32
    tbl = _rand((N, d), k=7)
    if dup:
        idx = jnp.zeros(B, jnp.int32).at[B // 2:].set(3)
    else:
        idx = jnp.asarray(np.random.default_rng(0).permutation(N)[:B])
    upd = _rand((B, d), k=8)
    out = sgns.scatter_add_rows(tbl, idx, upd, interpret=True)
    expect = ref.scatter_add_rows_ref(tbl, idx, upd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


def test_sgns_step_paths_agree():
    Nv, Nc, d, B, S = 64, 80, 128, 96, 16
    vert, ctx = _rand((Nv, d), k=9), _rand((Nc, d), k=10)
    iv = jax.random.randint(jax.random.fold_in(KEY, 11), (B,), 0, Nv)
    ic = jax.random.randint(jax.random.fold_in(KEY, 12), (B,), 0, Nc)
    inn = jax.random.randint(jax.random.fold_in(KEY, 13), (S,), 0, Nc)
    mask = jnp.ones(B)
    lr = jnp.float32(0.05)
    v0, c0, l0 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr, impl="ref")
    v1, c1, l1 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr, impl="pallas")
    np.testing.assert_allclose(l0, l1, rtol=3e-5)
    np.testing.assert_allclose(v0, v1, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(c0, c1, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("Bt", [16, 64])
def test_sgns_fused_grads_matches_ref(Bt):
    """The fused DMA-gather+grads kernel (the paper's CUDA hot loop,
    TPU-native) against the compose-of-oracles reference."""
    Nv, Nc, d, B, S = 70, 90, 64, 64, 8
    vert, ctx = _rand((Nv, d), k=40), _rand((Nc, d), k=41)
    iv = jax.random.randint(jax.random.fold_in(KEY, 42), (B,), 0, Nv)
    ic = jax.random.randint(jax.random.fold_in(KEY, 43), (B,), 0, Nc)
    inn = jax.random.randint(jax.random.fold_in(KEY, 44), (S,), 0, Nc)
    mask = (jax.random.uniform(jax.random.fold_in(KEY, 45), (B,)) > 0.2
            ).astype(jnp.float32)
    v, c, n = (ref.gather_rows_ref(vert, iv), ref.gather_rows_ref(ctx, ic),
               ref.gather_rows_ref(ctx, inn))
    l0, dv0, dc0, dn0 = ref.sgns_grads_ref(v, c, n, mask)
    l1, dv1, dc1, dn1 = sgns.sgns_fused_grads(vert, ctx, iv, ic, inn, mask,
                                              block_b=Bt, interpret=True)
    np.testing.assert_allclose(l0, l1, rtol=3e-5)
    np.testing.assert_allclose(dv0, dv1, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dc0, dc1, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dn0, dn1, rtol=1e-4, atol=1e-6)


def test_sgns_step_fused_path():
    Nv, Nc, d, B, S = 40, 50, 32, 32, 4
    vert, ctx = _rand((Nv, d), k=50), _rand((Nc, d), k=51)
    iv = jax.random.randint(jax.random.fold_in(KEY, 52), (B,), 0, Nv)
    ic = jax.random.randint(jax.random.fold_in(KEY, 53), (B,), 0, Nc)
    inn = jax.random.randint(jax.random.fold_in(KEY, 54), (S,), 0, Nc)
    mask = jnp.ones(B)
    lr = jnp.float32(0.05)
    v0, c0, l0 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr, impl="ref")
    v1, c1, l1 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr,
                               impl="pallas_fused")
    np.testing.assert_allclose(l0, l1, rtol=3e-5)
    np.testing.assert_allclose(v0, v1, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(c0, c1, rtol=2e-4, atol=1e-6)


def _step_inputs(Nv, Nc, B, S, d, dtype=jnp.float32, kbase=60, dup=False):
    vert, ctx = _rand((Nv, d), k=kbase, dtype=dtype), _rand((Nc, d),
                                                            k=kbase + 1,
                                                            dtype=dtype)
    iv = jax.random.randint(jax.random.fold_in(KEY, kbase + 2), (B,), 0, Nv)
    ic = jax.random.randint(jax.random.fold_in(KEY, kbase + 3), (B,), 0, Nc)
    inn = jax.random.randint(jax.random.fold_in(KEY, kbase + 4), (S,), 0, Nc)
    if dup:
        # force heavy duplication: vertex 3 and context 5 repeat across the
        # batch, and a negative collides with a positive context row
        iv = iv.at[::3].set(3)
        ic = ic.at[::4].set(5)
        inn = inn.at[0].set(5)
    mask = (jax.random.uniform(jax.random.fold_in(KEY, kbase + 5), (B,))
            > 0.15).astype(jnp.float32)
    return vert, ctx, iv, ic, inn, mask


@pytest.mark.parametrize("dtype,rtol,atol", [
    (jnp.float32, 2e-4, 1e-6),
    # ref applies updates in bf16 sequentially; the fused kernel combines
    # duplicates in f32 then applies once — bf16 rounding differs
    (jnp.bfloat16, 3e-2, 3e-3),
])
@pytest.mark.parametrize("dup", [False, True])
def test_sgns_fused_update_matches_step_ref(dtype, rtol, atol, dup):
    """The fully-fused pipelined update kernel (gather + grads + in-kernel
    SGD apply) against the full sgns_step oracle: loss AND updated tables."""
    Nv, Nc, d, B, S = 70, 90, 64, 64, 8
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, dtype,
                                                dup=dup)
    lr = jnp.float32(0.05)
    v0, c0, l0 = ref.sgns_step_ref(vert, ctx, iv, ic, inn, mask, lr)
    v1, c1, l1 = sgns.sgns_fused_update(vert, ctx, iv, ic, inn, mask, lr,
                                        block_b=16, interpret=True)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v0, np.float32),
                               np.asarray(v1, np.float32), rtol=rtol,
                               atol=atol)
    np.testing.assert_allclose(np.asarray(c0, np.float32),
                               np.asarray(c1, np.float32), rtol=rtol,
                               atol=atol)


@pytest.mark.parametrize("impl", ["pallas_fused", "pallas_fused2"])
@pytest.mark.parametrize("B,block_b", [(37, 8), (97, 32), (64, 64), (5, 256)])
def test_sgns_step_fused_odd_batch(impl, B, block_b):
    """Both fused branches pad odd B to the block size; the padded (index 0,
    mask 0) rows must not corrupt row 0."""
    Nv, Nc, d, S = 40, 50, 32, 4
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, kbase=70)
    iv = iv.at[0].set(0)   # make row 0 a real update target too
    lr = jnp.float32(0.05)
    v0, c0, l0 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr, impl="ref")
    v1, c1, l1 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr,
                               impl=impl, block_b=block_b)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=2e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=2e-4,
                               atol=1e-6)


def test_sgns_step_fused2_duplicate_scatter_accumulate():
    """Duplicate idx_v / idx_c (and idx_c∩idx_n collisions) must accumulate
    like the oracle's scatter-add — this is what verifies the fused branch
    needs no standalone scatter passes."""
    Nv, Nc, d, B, S = 30, 35, 32, 48, 8
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, kbase=80,
                                                dup=True)
    lr = jnp.float32(0.1)
    v0, c0, l0 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr, impl="ref")
    v1, c1, l1 = ops.sgns_step(vert, ctx, iv, ic, inn, mask, lr,
                               impl="pallas_fused2", block_b=16)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=3e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=3e-4,
                               atol=1e-6)


# --------------------------------------------------------------------------
# segment-sum duplicate-combine: parity vs the equality-matrix reference
# path (and the sgns_step oracle) across dtypes, odd B, heavy duplicates,
# and batch sizes past the old (B, B) wall.
# --------------------------------------------------------------------------
def _fused_both_combines(vert, ctx, iv, ic, inn, mask, lr, block_b):
    out = {}
    for combine in ("eq", "segsum"):
        out[combine] = sgns.sgns_fused_update(
            vert, ctx, iv, ic, inn, mask, lr, block_b=block_b,
            combine=combine, interpret=True)
    return out["eq"], out["segsum"]


@pytest.mark.parametrize("dtype,rtol,atol", [
    # both combines sum duplicate grads in f32 and apply one table-dtype
    # add; only the f32 summation ORDER differs, so f32 parity is tight and
    # bf16 can differ by at most the final-cast ulp
    (jnp.float32, 2e-6, 1e-7),
    (jnp.bfloat16, 1e-2, 1e-3),
])
@pytest.mark.parametrize("B,block_b", [(48, 16), (64, 64), (96, 32)])
def test_fused_update_segsum_matches_eq(dtype, rtol, atol, B, block_b):
    """segsum vs eq on heavy duplicates (incl. an idx_c/idx_n collision)."""
    Nv, Nc, d, S = 70, 90, 64, 8
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, dtype,
                                                kbase=100, dup=True)
    lr = jnp.float32(0.07)
    (v1, c1, l1), (v2, c2, l2) = _fused_both_combines(
        vert, ctx, iv, ic, inn, mask, lr, block_b)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v1, np.float32),
                               np.asarray(v2, np.float32), rtol=rtol,
                               atol=atol)
    np.testing.assert_allclose(np.asarray(c1, np.float32),
                               np.asarray(c2, np.float32), rtol=rtol,
                               atol=atol)


def test_fused_update_segsum_all_same_index():
    """Worst case for the combine: every position scatters to ONE vertex row
    and one ctx row (which the negatives also hit) — a single B-long run."""
    Nv, Nc, d, B, S = 40, 50, 32, 128, 8
    vert, ctx, *_ = _step_inputs(Nv, Nc, B, S, d, kbase=110)
    iv = jnp.full((B,), 7, jnp.int32)
    ic = jnp.full((B,), 9, jnp.int32)
    inn = jnp.full((S,), 9, jnp.int32)
    mask = jnp.ones(B)
    lr = jnp.float32(0.05)
    v0, c0, l0 = ref.sgns_step_ref(vert, ctx, iv, ic, inn, mask, lr)
    v2, c2, l2 = sgns.sgns_fused_update(vert, ctx, iv, ic, inn, mask, lr,
                                        block_b=32, combine="segsum",
                                        interpret=True)
    np.testing.assert_allclose(float(l0), float(l2), rtol=1e-4)
    # a 128-term f32 sum reassociated: modest tolerance vs the oracle
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v2), rtol=1e-3,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c2), rtol=1e-3,
                               atol=1e-5)


@pytest.mark.parametrize("B", [37, 97])
def test_sgns_step_fused2_segsum_odd_batch_padding(B, monkeypatch):
    """Odd B through ops.sgns_step with the combine forced to segsum: the
    padded (index 0, mask 0) tail must fold into row 0's run harmlessly."""
    from repro.kernels import ops
    monkeypatch.setattr(
        ops, "plan_fused_update",
        lambda *a, **kw: ops.FusedPlan(block_b=16, combine="segsum",
                                       chunk_rows=1 << 30))
    Nv, Nc, d, S = 40, 50, 32, 4
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, kbase=120)
    iv = iv.at[0].set(0)   # make row 0 a real update target too
    lr = jnp.float32(0.05)
    v0, c0, l0 = ref.sgns_step_ref(vert, ctx, iv, ic, inn, mask, lr)
    v1, c1, l1 = ops.sgns_step.__wrapped__(
        vert, ctx, iv, ic, inn, mask, lr, impl="pallas_fused2")
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=2e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=2e-4,
                               atol=1e-6)


@settings(max_examples=12, deadline=None)
@given(B=st.integers(2, 72), S=st.integers(1, 12),
       stride=st.integers(1, 4))
def test_fused_update_segsum_matches_eq_property(B, S, stride):
    """Property sweep: random geometry + a duplication stride; single-tile
    launch (block_b=B) so any B is legal."""
    Nv, Nc, d = 30, 35, 32
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, kbase=130)
    iv = iv.at[::stride].set(3)
    ic = ic.at[::stride].set(5)
    inn = inn.at[0].set(5)
    lr = jnp.float32(0.05)
    (v1, c1, l1), (v2, c2, l2) = _fused_both_combines(
        vert, ctx, iv, ic, inn, mask, lr, B)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=2e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=2e-6,
                               atol=1e-7)


@pytest.mark.slow
def test_fused_update_segsum_B8192_no_quadratic_intermediate():
    """The acceptance gate: exact parity at B = 8192 (4x past the old ~2k
    equality-matrix cap) AND no (B, B) tensor anywhere in the lowered HLO."""
    import functools
    Nv = Nc = 4096
    d, B, S = 64, 8192, 16
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, kbase=140)
    mask = jnp.ones(B)
    lr = jnp.float32(0.05)
    fn = functools.partial(sgns.sgns_fused_update, block_b=256,
                           combine="segsum", interpret=True)
    hlo = jax.jit(fn).lower(vert, ctx, iv, ic, inn, mask, lr).as_text()
    assert f"{B},{B}" not in hlo, "O(B^2) combine intermediate leaked back in"
    v0, c0, l0 = ref.sgns_step_ref(vert, ctx, iv, ic, inn, mask, lr)
    v1, c1, l1 = fn(vert, ctx, iv, ic, inn, mask, lr)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=3e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=3e-4,
                               atol=1e-5)


@pytest.mark.slow
def test_sgns_step_fused2_chunked_launches_B5120(monkeypatch):
    """B past the plan's VMEM chunk limit: ops.sgns_step must split into
    sequential fused launches that match a ref oracle applied with the SAME
    chunk boundaries (chunking = coarser-grained sequential SGD). The plan
    is made against a 16 MiB budget so that 5120 rows need two launches."""
    import functools
    from repro.kernels import ops
    Nv = Nc = 1024
    d, B, S = 128, 5120, 16
    monkeypatch.setattr(ops, "plan_fused_update", functools.partial(
        ops.plan_fused_update, vmem_budget=16 * 2**20))
    plan = ops.plan_fused_update(B, d, S, jnp.float32)
    assert plan.chunk_rows < B, plan    # the point of the test
    vert, ctx, iv, ic, inn, mask = _step_inputs(Nv, Nc, B, S, d, kbase=150)
    lr = jnp.float32(0.05)
    v1, c1, l1 = ops.sgns_step.__wrapped__(vert, ctx, iv, ic, inn, mask, lr,
                                           impl="pallas_fused2")
    v0, c0, loss0 = vert, ctx, 0.0
    for s in range(0, B, plan.chunk_rows):
        e = min(s + plan.chunk_rows, B)
        v0, c0, lc = ref.sgns_step_ref(v0, c0, iv[s:e], ic[s:e], inn,
                                       mask[s:e], lr)
        loss0 += float(lc)
    np.testing.assert_allclose(loss0, float(l1), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=3e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(c0), np.asarray(c1), rtol=3e-4,
                               atol=1e-5)


def test_scatter_add_rows_per_block_dup_flags():
    """Duplicates ACROSS blocks (none within) stay correct — the sequential
    grid serializes blocks, so only intra-block collisions need the slow
    path. Also: padding sentinels must not fake a collision with real 0s."""
    N, d, rb = 40, 64, 8
    tbl = _rand((N, d), k=94)
    # 4 blocks, each a clean 0..7 permutation -> every row duplicated 4x
    idx = jnp.concatenate([jnp.arange(8, dtype=jnp.int32)] * 4)
    upd = _rand((32, d), k=95)
    out = sgns.scatter_add_rows(tbl, idx, upd, rows_per_block=rb,
                                interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.scatter_add_rows_ref(tbl, idx, upd)),
        rtol=1e-5, atol=1e-6)
    # odd B: last block is padded; real index 0 in it must not be treated
    # as colliding with the pad positions
    idx3 = jnp.zeros(29, jnp.int32).at[:14].set(jnp.arange(1, 15))
    out3 = sgns.scatter_add_rows(tbl, idx3, upd[:29], rows_per_block=rb,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(out3),
        np.asarray(ref.scatter_add_rows_ref(tbl, idx3, upd[:29])),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("N,d,B,rb", [(50, 64, 20, 8), (30, 32, 9, 4),
                                      (64, 128, 64, 16)])
def test_gather_rows_blocked_matches_rowwise(N, d, B, rb):
    tbl = _rand((N, d), k=90)
    idx = jax.random.randint(jax.random.fold_in(KEY, 91), (B,), 0, N)
    blocked = sgns.gather_rows(tbl, idx, rows_per_block=rb, interpret=True)
    rowwise = sgns.gather_rows_rowwise(tbl, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(blocked), np.asarray(rowwise))


@pytest.mark.parametrize("dup", [False, True])
@pytest.mark.parametrize("rb", [4, 8])
def test_scatter_add_rows_blocked_matches_rowwise(dup, rb):
    N, d, B = 40, 64, 30   # B deliberately not a multiple of rb
    tbl = _rand((N, d), k=92)
    if dup:
        idx = jnp.zeros(B, jnp.int32).at[B // 2:].set(3)
    else:
        idx = jnp.asarray(np.random.default_rng(1).permutation(N)[:B])
    upd = _rand((B, d), k=93)
    blocked = sgns.scatter_add_rows(tbl, idx, upd, rows_per_block=rb,
                                    interpret=True)
    rowwise = sgns.scatter_add_rows_rowwise(tbl, idx, upd, interpret=True)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(rowwise),
                               rtol=1e-5, atol=1e-6)


def test_sgns_grads_is_true_gradient():
    """dv/dc/dn must equal autodiff gradients of the SGNS loss."""
    B, d, S = 32, 16, 8
    v, c, n = _rand((B, d), k=20), _rand((B, d), k=21), _rand((S, d), k=22)
    mask = jnp.ones(B)

    def loss_fn(v, c, n):
        return ref.sgns_grads_ref(v, c, n, mask)[0]

    gv, gc, gn = jax.grad(loss_fn, argnums=(0, 1, 2))(v, c, n)
    _, dv, dc, dn = ref.sgns_grads_ref(v, c, n, mask)
    np.testing.assert_allclose(gv, dv, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gc, dc, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(gn, dn, rtol=1e-4, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(B=st.integers(1, 64), d=st.sampled_from([8, 32, 128]),
       S=st.integers(1, 16))
def test_sgns_mask_zeroes_padding(B, d, S):
    """Property: fully-masked batches produce zero loss and zero grads."""
    v, c, n = _rand((B, d), k=30), _rand((B, d), k=31), _rand((S, d), k=32)
    loss, dv, dc, dn = ref.sgns_grads_ref(v, c, n, jnp.zeros(B))
    assert float(loss) == 0.0
    assert float(jnp.abs(dv).max()) == 0.0
    assert float(jnp.abs(dn).max()) == 0.0

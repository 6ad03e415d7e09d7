"""HLO cost model: exact on scans (the reason it exists) and on plain dots."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_cost import analyze_hlo


def _xla_cost(comp):
    """compiled.cost_analysis() returned a one-element list on older jax."""
    ca = comp.cost_analysis()
    return ca[0] if isinstance(ca, (list, tuple)) else ca


def test_plain_matmul_matches_xla():
    g = jax.jit(lambda a, b: a @ b)
    comp = g.lower(jnp.zeros((128, 256), jnp.float32),
                   jnp.zeros((256, 64), jnp.float32)).compile()
    r = analyze_hlo(comp.as_text())
    assert r["flops"] == _xla_cost(comp)["flops"] == 2 * 128 * 256 * 64


def test_scan_flops_multiplied_by_trip_count():
    L, B, D, F = 6, 32, 64, 96

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w @ w.T), ()
        x, _ = jax.lax.scan(body, x, ws)
        return x

    ws = jnp.zeros((L, D, F))
    x = jnp.zeros((B, D))
    comp = jax.jit(f).lower(ws, x).compile()
    r = analyze_hlo(comp.as_text())
    expected = L * (2 * B * D * F + 2 * B * F * D)
    assert abs(r["flops"] - expected) / expected < 0.01
    # XLA's own count misses the trip multiplication
    assert _xla_cost(comp)["flops"] < r["flops"]


def test_collectives_counted_inside_scans():
    devs = jax.device_count()
    mesh = jax.make_mesh((1, devs), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(ws, x):
        def body(x, w):
            return x @ w, ()
        return jax.lax.scan(body, x, ws)[0]

    L, D = 5, 64
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((8, D), jnp.float32)
    j = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "model", None)),
                                 NamedSharding(mesh, P())))
    with mesh:
        comp = j.lower(ws, x).compile()
    r = analyze_hlo(comp.as_text())
    if devs > 1:
        assert r["collectives"]["total"] > 0
    assert np.isfinite(r["bytes"]) and r["bytes"] > 0


def test_chip_peaks_keyed_by_device_kind():
    """Peaks come from the published table for the device kind JAX reports;
    a kind that is not in it raises instead of defaulting to v5e."""
    import pytest
    from repro.launch import roofline
    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16 * 10**9)
    assert v5e.ici_bw == 1600e9 / 8          # 1,600 Gbit/s
    assert roofline.LINK_BW == v5e.ici_bw
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v9000")

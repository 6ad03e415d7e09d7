"""Blocked online-softmax (flash) attention Pallas kernel for TPU.

The transformer pool's perf-critical hot spot: q tiles stay resident in
VMEM while k/v tiles stream past; the running (max, denominator,
accumulator) update means the (Sq, Skv) score matrix is never materialized
in HBM — the memory term that dominates every dense train/prefill row in
EXPERIMENTS.md §Roofline.

Layout: q (B, H, Sq, hd), k/v (B, Hkv, Skv, hd) — GQA is handled in the
BlockSpec index maps (kv head = h // (H // Hkv)), no broadcast
materialization. Causal masking and sliding windows are applied from global
tile offsets. Validated against :func:`repro.kernels.ref_attention.mha_ref`
in interpret mode; TPU is the target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                  scale: float, causal: bool, window: int,
                  tile_q: int, tile_k: int, num_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)              # (Tq, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (Tk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = iq * tile_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * tile_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[...]                                # (Tq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                           # (Tq, Tk)
    alpha = jnp.exp(m_prev - m_new)                  # (Tq, 1)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ik == num_k - 1)
    def _finish():
        denom = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "tile_q",
                                             "tile_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    tile_q: int = 128, tile_k: int = 128,
                    interpret: bool = False):
    """q: (B,H,Sq,hd), k/v: (B,Hkv,Skv,hd) -> (B,H,Sq,hd)."""
    B, H, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    G = H // Hkv
    tq, tk = min(tile_q, Sq), min(tile_k, Skv)
    assert Sq % tq == 0 and Skv % tk == 0, (Sq, tq, Skv, tk)
    nq, nk = Sq // tq, Skv // tk
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        tile_q=tq, tile_k=tk, num_k=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, tq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, tk, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, tk, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),    # running max
            pltpu.VMEM((tq, 1), jnp.float32),    # running denominator
            pltpu.VMEM((tq, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def mha_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """Pure-jnp oracle. Same layout as :func:`flash_attention`."""
    B, H, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = H // Hkv
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / np.sqrt(hd)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)
                      ).astype(q.dtype)

"""Roofline terms from a compiled dry-run artifact, and the chip table.

Peaks are keyed by ``jax.Device.device_kind``; a kind that is not in
:data:`CHIPS` is an error, never a silent default. The dry-run (which
compiles for a described chip, no hardware attached) models
:data:`DRYRUN_KIND`.

Methodology:
  * compute term   = per-device HLO FLOPs / peak  (cost_analysis runs on the
    post-SPMD per-device module, so no extra /chips)
  * memory term    = per-device HLO bytes accessed / HBM bw
  * collective term = Σ (result bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute in the per-device
    module) / the chip's published interconnect bandwidth. Result-shape
    bytes are a lower bound on the bytes a device moves for that op (ring
    all-reduce moves ~2x); we report the raw sum and note the factor.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks (bytes and operations per second)."""

    bf16_flops: float
    int8_ops: float
    hbm_bytes: int
    hbm_bw: float
    ici_bw: float           # chip-to-chip interconnect, all links together
    source: str


CHIPS = {
    # JAX reports a v5e chip as "TPU v5 lite"
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16 * 10**9,
        hbm_bw=819e9, ici_bw=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI'),
}
DRYRUN_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of one chip; unknown kinds raise."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIPS)}") from None


PEAK_FLOPS = CHIPS[DRYRUN_KIND].bf16_flops   # the dry-run's modeled chip
HBM_BW = CHIPS[DRYRUN_KIND].hbm_bw
LINK_BW = CHIPS[DRYRUN_KIND].ici_bw

# Scoped VMEM every Pallas launch of the embedding kernels requests
# (pltpu.CompilerParams.vmem_limit_bytes; a v5e core has 128 MiB, and the
# compiler's default scope is 16 MiB), and the budget below it that the
# launch planners (kernels.ops.plan_fused_update, embed_serve.topk
# .choose_block_n) size a launch's modeled working set to. The gap is
# headroom for what the models leave out (compiler temporaries, spills).
VMEM_LIMIT_BYTES = 48 * 2**20
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES * 3 // 4

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-gather.3 = bf16[2,4096,512]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+(" + "|".join(_COLLECTIVES)
    + r")[ (]")
# tuple-result collectives:  = (bf16[..], bf16[..]) all-to-all(
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+(" + "|".join(_COLLECTIVES) + r")[ (]")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives, bucketed by op kind."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _shape_bytes(dtype, dims)
            continue
        m = _TUPLE_RE.search(line)
        if m:
            shapes, kind = m.groups()
            for dt, dims in _SHAPE_RE.findall(shapes):
                out[kind] += _shape_bytes(dt, dims)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_terms(flops: float, bytes_accessed: float,
                   coll_bytes: float) -> dict:
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    collective_s = coll_bytes / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom.replace("_s", "")
    return terms


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (inference).

    Embedding arch: the "model" touched per sample is two d-vectors per
    (pair x (1 + negatives)) — 6*2d per trained pair, not 6*N_total."""
    if getattr(cfg, "arch_type", "") == "embedding":
        # samples per episode: filled in by the caller via shape.global_batch?
        # use block geometry: P^2 * k * block_cap samples
        samples = 256 * 256 * cfg.subparts * cfg.block_cap
        return 6.0 * 2 * cfg.dim * (1 + cfg.negatives) * samples
    n_active = active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens


def active_params(cfg) -> float:
    """Forward-active parameter count (MoE: top_k + shared experts only)."""
    if getattr(cfg, "arch_type", "") == "embedding":
        return 2.0 * cfg.num_nodes * cfg.dim
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    hd = cfg.resolved_head_dim
    total = 2 * V * d  # embed + head
    types = cfg.layer_types()
    for i in range(L):
        if types[i] == "A":
            if cfg.mla:
                qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                total += (d * cfg.q_lora_rank
                          + cfg.q_lora_rank * cfg.num_heads * qk
                          + d * cfg.kv_lora_rank + d * cfg.qk_rope_head_dim
                          + cfg.kv_lora_rank * cfg.num_heads
                          * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                          + cfg.num_heads * cfg.v_head_dim * d)
            else:
                total += d * hd * (cfg.num_heads + 2 * cfg.num_kv_heads) \
                    + cfg.num_heads * hd * d
        else:
            di = cfg.d_inner_ssm
            total += d * (2 * di + 2 * cfg.ssm_state + cfg.ssm_heads) + di * d
        if cfg.is_moe_layer(i):
            active_e = cfg.moe_top_k + cfg.moe_num_shared
            total += 3 * d * cfg.moe_d_ff * active_e + d * cfg.moe_num_experts
        elif cfg.d_ff:
            total += 3 * d * cfg.d_ff
    if cfg.is_encdec:
        total += cfg.encoder_layers * (
            d * hd * (cfg.num_heads + 2 * cfg.num_kv_heads)
            + cfg.num_heads * hd * d + 3 * d * cfg.d_ff)
        total += L * (d * hd * (cfg.num_heads + 2 * cfg.num_kv_heads)
                      + cfg.num_heads * hd * d)  # cross-attention
    return float(total)

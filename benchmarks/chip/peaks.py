"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

A kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # JAX reports a v5e chip as "TPU v5 lite"
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bw": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  'bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s',
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

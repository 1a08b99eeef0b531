"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind that is not in the table is an error, never a default: a
share of a peak is only meaningful against the chip that ran.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s per chip.
_V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {
    "TPU v5 lite": _V5E,   # the kind JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/harness/"
                       f"peaks.py with their source") from None

"""Peaks of each device the benchmark may run on, keyed by JAX's
``device_kind``. A device missing here is an error, never a default.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s, from Google Cloud's documentation page "TPU v5e".
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.py with its source") from None

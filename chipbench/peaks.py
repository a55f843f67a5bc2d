"""Peak rates of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device that is not here is an error, never a default: a share of
another chip's peak is a wrong number, not an approximate one.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         "add them to chipbench/peaks.py with their source"
                         ) from None

"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``benchmarks/roofline.py``. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
at 819 GB/s. A kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

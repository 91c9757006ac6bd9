"""Published peak rates, keyed by ``jax.Device.device_kind``.

A copy of the program's ``launch/roofline.PEAKS`` entry, kept here so the
yardstick does not move with the program.  A kind that is not in the
table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak rates for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])

"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``, and the least time a piece of work can take on one.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
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


def peaks(device_kind: str) -> dict:
    """The peaks of a device kind; an unknown kind is an error, never a
    default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(device_kind: str, flops: float, nbytes: float) -> float:
    """The roofline bound: the larger of the work at peak bf16 compute and
    the bytes at peak HBM bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])

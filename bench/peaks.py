"""Published peaks of each accelerator, keyed by ``device.device_kind``.

A device that is not in the table is an error, not a default: a roofline or
utilization against a guessed peak means nothing.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    """Roofline floor of one piece of work: the larger of its operations
    over the bf16 peak and its bytes over HBM bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])

"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
dense bf16, 16 GB HBM at 819 GB/s). A device kind missing here is an
error, not a missing utilization: add it with its source.
"""

from __future__ import annotations

__all__ = ["PEAKS", "peak"]

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device_kind {device_kind!r}; "
                       f"add it to benchmarks/peaks.py with its "
                       f"source") from None

"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default borrowed from another chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to bench/peaks.py with their source") from None

"""Peaks of the chips the benchmark may run on, keyed by ``device_kind``.

One table; an unknown kind is an error, never a default. Source of the
v5e row: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; add a "
            f"row with its source to benchmark/peaks.py") from None

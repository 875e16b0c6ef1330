"""Peaks of one chip, keyed by the `device_kind` JAX reports.

Source for `TPU v5 lite`: Google Cloud TPU documentation, "TPU v5e" system
architecture: 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect. A kind that is not in the table is an error, never
a default: a share of the wrong peak is a wrong number.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
    },
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.py with its source")
    return PEAKS[device_kind][what]

"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip. A device that is not in this table is an
error, never a default.
"""
from __future__ import annotations

from typing import Dict

SOURCE = 'Google Cloud documentation, "TPU v5e"'

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bw": 819e9,
        "ici_bw": 1600e9 / 8,        # bytes/s per chip, all links together
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}); add it with its published source"
        ) from None

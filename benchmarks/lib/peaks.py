"""The table of chip peaks, keyed by ``device_kind``.  A kind that is not
here is an error: nothing falls back to a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page: 197
TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    flops_per_s: float  # bf16 matrix peak
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": ChipPeaks(197e12, 819e9, 16e9),
    "TPU v5e": ChipPeaks(197e12, 819e9, 16e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no peaks for device_kind {device_kind!r}; known "
            f"kinds are {sorted(PEAKS)} (add a row with its source, never a "
            "default)") from None

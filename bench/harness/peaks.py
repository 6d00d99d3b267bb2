"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip. A kind that is not in
the table is an error, never a default: every share of a peak the benchmark
prints names the chip it was measured on.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s
    hbm_bytes_per_s: float     # B/s
    hbm_bytes: float           # B


SOURCE = 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM, 819 GB/s'

_V5E = Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)

PEAKS = {
    "TPU v5 lite": _V5E,       # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)} ({SOURCE})"
        )
    return PEAKS[device_kind]

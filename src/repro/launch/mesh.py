"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
device initialization.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests and elastic re-mesh use this). Axes are
    ``Auto``: the model code places activations with
    ``with_sharding_constraint`` and lets GSPMD propagate the rest."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def dp_axes_for(mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a production mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks — roofline denominators and the HBM a
    serving configuration is sized against."""

    flops_bf16: float                 # FLOP/s
    hbm_bw: float                     # B/s
    hbm_bytes: float                  # B
    ici_bw_per_link: float            # B/s per link


# Keyed by jax ``Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect (four links of 400 Gbit/s = 50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw_per_link=50e9
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"CHIP_PEAKS (known: {sorted(CHIP_PEAKS)})"
        )
    return CHIP_PEAKS[device_kind]

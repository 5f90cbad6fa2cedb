"""Published per-chip peaks — the ONE table every utilisation or
roofline figure in this repo divides by.

Keyed by the exact ``jax.Device.device_kind`` string. A device that is
not in the table is an error, never a default: a utilisation computed
against a guessed peak is a wrong number under a device metric's name.
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = ["ChipPeaks", "PEAKS", "chip_peaks"]


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float       # FLOP/s
    int8_ops: float         # OP/s
    hbm_bytes_per_s: float  # B/s
    hbm_bytes: float        # B
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def chip_peaks(device=None) -> ChipPeaks:
    """Peaks of ``device`` (default: ``jax.devices()[0]``); raises
    ``LookupError`` for a ``device_kind`` the table does not list."""
    kind = (device if device is not None else jax.devices()[0]).device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {kind!r} (known: "
            f"{sorted(PEAKS)}); add a sourced row to "
            "paddle_tpu/device/peaks.py — a utilisation is never "
            "computed against a guessed peak") from None

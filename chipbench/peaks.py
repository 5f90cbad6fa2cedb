"""Published per-chip peaks: the yardstick's own copy of the table.

Keyed by the exact ``jax.Device.device_kind``. A device that is not in
the table is an error, never a default. (The program keeps a table of
its own in ``paddle_tpu/device/peaks.py``; later PRs may change the
program and not the yardstick, so nothing here reads it.)
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s per chip
    hbm_bytes_per_s: float  # B/s per chip
    hbm_bytes: float        # B per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {kind!r} (known: "
            f"{sorted(PEAKS)}); add a sourced row to chipbench/peaks.py"
        ) from None

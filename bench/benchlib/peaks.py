"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device kind that is not here is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s
    hbm_bytes_per_s: float     # bytes/s
    source: str


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": Peaks(197e12, 819e9,
                         "Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

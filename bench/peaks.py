"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float      # dense bf16 matrix-unit peak
    hbm_bytes_per_s: float
    source: str


_V5E = Peak(flops_per_s=197e12, hbm_bytes_per_s=819e9,
            source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                   "bf16, 16 GB HBM at 819 GB/s per chip")

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def for_device(kind: str) -> Peak:
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]

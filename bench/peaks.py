"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip.  A device that is not
in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


TABLE = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(TABLE)}") from None

"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect. JAX reports the chip as
"TPU v5 lite". A device that is not in the table is an error: a
roofline or utilization against a guessed peak would mean nothing.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float  # FLOP/s per chip
    hbm_bytes_per_s: float


TABLE = {"TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9)}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None

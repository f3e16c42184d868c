"""Peak rates of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error: a share of a peak needs the peak.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e",
per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_bf16: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9,
                        source="Google Cloud documentation, TPU v5e"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A TPU v5e reports its kind as ``"TPU v5 lite"``.  A kind that is not in
the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float        # FLOP/s per chip
    hbm_bytes_per_s: float   # bytes/s per chip
    hbm_bytes: float         # HBM capacity per chip


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

"""Peak rates per chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB HBM2 at 819 GB/s.
A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops: float          # FLOP/s (bf16)
    hbm_bytes_s: float    # bytes/s
    source: str


_V5E = Peak(197e12, 819e9,
            'Google Cloud documentation, "TPU v5e" system architecture')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

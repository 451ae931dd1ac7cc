"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, never a default: a share of
the wrong chip's peak is worse than no number.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float          # FLOP/s, dense bf16 matmul
    hbm_bw: float              # B/s
    hbm_bytes: int             # device memory
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9,
        source="Google Cloud documentation, 'TPU v5e': per chip 197 "
               "TFLOP/s bf16, 16 GB HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; raises for a device not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add a row with its source") from None

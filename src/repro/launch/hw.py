"""Declared device facts: per-chip peaks keyed by ``device_kind``.

A device that is not in the table is an error, not a default: a roofline
against the wrong chip's peaks is worse than none.  Look a device up with
``peaks(jax.devices()[0].device_kind)``.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float          # FLOP/s, dense bf16 matmul
    hbm_bw: float              # B/s
    hbm_bytes: int             # device memory
    ici_bw: float              # B/s per chip-to-chip link (of four)
    source: str


# ``device_kind`` as JAX reports it -> published per-chip peaks
PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9,
        ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e' (per chip: 197 "
               "TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI)"),
}

# the chip the multi-pod dry run models (launch/dryrun.py)
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; raises for a device not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no declared peaks for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add a row with its source") from None


# element sizes of HLO dtypes, for byte counts parsed from HLO text
BYTES = {
    "f32": 4, "f16": 2, "bf16": 2, "f64": 8,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

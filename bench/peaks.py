"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

A copy of the program's ``config.HW_BY_KIND`` row, kept with the
benchmark so that the yardstick cannot move with the program.  A kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s
    int8_ops: float            # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float      # chip-to-chip, all links
    source: str


TABLE = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9, ici_bits_per_s=1600e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r} "
                         f"(known: {sorted(TABLE)})") from None

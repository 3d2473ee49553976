"""Bytes a product must move, and the chip's peaks, for roofline shares.

Whatever implements the product, the roofline reads the same work,
computed from the operands alone:

    bytes = 8·nnz(A) + 8·FLOP + 8·NNZ(C) + 8·(m + 1)

A (column index and value, 4 bytes each) is read once, one B entry is read
for each intermediate product (FLOP counts them), C is written once, and
C's m + 1 row pointers are written as 8-byte integers.  The compute bound,
2·FLOP at about 0.25 FLOP per byte, never binds on a chip whose peak is
hundreds of FLOP per byte of bandwidth, so the share is bandwidth's.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def spgemm_bytes(m: int, nnz_a: int, flop: int, nnz_c: int) -> int:
    return 8 * nnz_a + 8 * flop + 8 * nnz_c + 8 * (m + 1)


def peak(device_kind: str, key: str = "hbm_bytes_per_s") -> float:
    """A published peak of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return float(table[device_kind][key])

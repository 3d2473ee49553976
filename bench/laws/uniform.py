"""Uniform law: every row holds exactly ``nnz_per_row`` distinct columns,
drawn uniformly over the square matrix's columns.

JGD_Homology/m133-b3 holds exactly 4 entries in each of its rows; with
uniform columns almost no two intermediate products of A·A meet, so the
compression ratio FLOP / NNZ(C) is about 1.
"""
from __future__ import annotations

import numpy as np


def make(rows: int, params: dict, rng: np.random.Generator):
    """Return ``(rpt, col)`` of a ``rows`` x ``rows`` pattern, columns sorted
    within each row (every column, where there are fewer than the law's
    degree)."""
    d = min(int(params["nnz_per_row"]), rows)
    cols = rng.integers(0, rows, size=(rows, d), dtype=np.int64)
    cols.sort(axis=1)
    while True:
        bad = np.flatnonzero((cols[:, 1:] == cols[:, :-1]).any(axis=1))
        if not bad.size:
            break
        redraw = rng.integers(0, rows, size=(bad.size, d), dtype=np.int64)
        redraw.sort(axis=1)
        cols[bad] = redraw
    rpt = np.arange(rows + 1, dtype=np.int64) * d
    return rpt, cols.reshape(-1).astype(np.int32)

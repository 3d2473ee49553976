"""Power-law law: Pareto row degrees with a stated mean, hub-biased columns.

The same law as the system's own ``power_law`` generator (the webbase-1M
analogue used at bring-up), kept here so that the benchmark's operands do
not change when the program's generators do.  Unlike that generator it
holds the stated mean exactly: the Pareto draws are scaled until the
degrees, clipped to [1, 50 * avg_nnz], sum to ``round(avg_nnz * rows)``,
each is rounded up or down at random in proportion to its fraction (the
total kept), and a column drawn twice in a row is drawn again rather than
merged.
"""
from __future__ import annotations

import numpy as np


def _degrees(rows: int, avg: float, alpha: float, rng) -> np.ndarray:
    cap = min(rows, int(50 * avg))
    raw = rng.pareto(alpha, size=rows) + 1.0
    target = int(round(avg * rows))
    lo, hi = 0.0, cap / raw.min()
    for _ in range(200):           # sum(clip(s * raw)) is monotone in s
        mid = (lo + hi) / 2
        if np.clip(mid * raw, 1, cap).sum() < target:
            lo = mid
        else:
            hi = mid
    deg = np.clip(hi * raw, 1, cap)
    base = np.floor(deg).astype(np.int64)
    frac = deg - base
    extra = int(target - base.sum())
    if extra > 0:
        up = rng.choice(rows, size=extra, replace=False, p=frac / frac.sum())
        base[up] += 1
    return base


def make(rows: int, params: dict, rng: np.random.Generator):
    """Return ``(rpt, col)`` of a ``rows`` x ``rows`` pattern, columns sorted
    within each row."""
    deg = _degrees(rows, float(params["avg_nnz"]), float(params["alpha"]), rng)

    def draw(n):
        u = rng.random(n)
        return (u * u * rows).astype(np.int64).clip(0, rows - 1)

    r = np.repeat(np.arange(rows, dtype=np.int64), deg)
    c = draw(r.size)
    while True:
        order = np.lexsort((c, r))
        c = c[order]                               # r stays sorted
        dup = np.flatnonzero(c[1:] == c[:-1]) + 1
        dup = dup[r[dup] == r[dup - 1]]
        if not dup.size:
            break
        c[dup] = draw(dup.size)
    rpt = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(deg, out=rpt[1:])
    return rpt, c.astype(np.int32)

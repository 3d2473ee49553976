"""Plain numpy SpGEMM, the reference that decides ``correct``.

Row-wise expansion: every intermediate product A[i,k]·B[k,j] is listed,
the products are sorted by (i, j) and summed.  Nothing of the system under
test is imported: operands and results are plain ``(rpt, col, val)``
arrays.  Rows go in chunks of about ``chunk`` products, so the reference
fits in host memory at any size the benchmark runs.
"""
from __future__ import annotations

import numpy as np


def _ranges(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenation of ``[starts[i], starts[i] + counts[i])``."""
    total = int(counts.sum())
    if not total:
        return np.zeros(0, dtype=np.int64)
    offs = np.cumsum(counts) - counts
    out = np.arange(total, dtype=np.int64)
    out -= np.repeat(offs, counts)
    out += np.repeat(starts.astype(np.int64), counts)
    return out


def flop(a, b) -> int:
    """Intermediate products of A·B: sum over A's entries of nnz(B[k,:])."""
    a_rpt, a_col, _ = a
    b_rpt = b[0]
    return int(np.diff(b_rpt)[a_col].sum())


def spgemm(a, b, ncols: int, *, round_to=None, chunk: int = 1 << 22):
    """``C = A·B`` as ``(rpt, col, val)``; values summed in float64.

    ``round_to`` (a numpy dtype such as ``ml_dtypes.bfloat16``) computes in
    that precision instead: operands and each product are rounded to it,
    sums accumulate in float32 and are rounded to it.  That is the
    control: the reference one precision step below what the configuration
    states (float32)."""
    a_rpt, a_col, a_val = (np.asarray(x) for x in a)
    b_rpt, b_col, b_val = (np.asarray(x) for x in b)
    m = a_rpt.size - 1
    acc = np.float32 if round_to is not None else np.float64
    if round_to is not None:
        a_val = a_val.astype(round_to)
        b_val = b_val.astype(round_to)
    deg_b = np.diff(b_rpt).astype(np.int64)
    row_flop = np.bincount(np.repeat(np.arange(m), np.diff(a_rpt)),
                           weights=deg_b[a_col], minlength=m)
    cum = np.concatenate([[0], np.cumsum(row_flop)])
    rows_out, cols_out, vals_out = [], [], []
    r0 = 0
    while r0 < m:
        r1 = int(np.searchsorted(cum, cum[r0] + chunk, side="right")) - 1
        r1 = min(m, max(r0 + 1, r1))
        ia = np.arange(a_rpt[r0], a_rpt[r1], dtype=np.int64)
        row_a = np.repeat(np.arange(r0, r1, dtype=np.int64),
                          np.diff(a_rpt[r0:r1 + 1]))
        k = a_col[ia].astype(np.int64)
        ib = _ranges(b_rpt[k], deg_b[k])
        rep = deg_b[k]
        row = np.repeat(row_a, rep)
        col = b_col[ib].astype(np.int64)
        prod = (np.repeat(a_val[ia], rep).astype(acc)
                * b_val[ib].astype(acc))
        if round_to is not None:
            prod = prod.astype(round_to).astype(acc)
        key = row * ncols + col
        order = np.argsort(key, kind="stable")
        key, prod = key[order], prod[order]
        if key.size:
            first = np.concatenate([[True], key[1:] != key[:-1]])
            starts = np.flatnonzero(first)
            sums = np.add.reduceat(prod, starts)
            if round_to is not None:
                sums = sums.astype(round_to).astype(acc)
            rows_out.append(key[starts] // ncols)
            cols_out.append(key[starts] % ncols)
            vals_out.append(sums)
        r0 = r1
    rows = np.concatenate(rows_out) if rows_out else np.zeros(0, np.int64)
    rpt = np.zeros(m + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=m), out=rpt[1:])
    col = (np.concatenate(cols_out) if cols_out
           else np.zeros(0, np.int64)).astype(np.int32)
    val = np.concatenate(vals_out) if vals_out else np.zeros(0, acc)
    return rpt, col, val

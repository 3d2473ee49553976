"""The comparison that decides ``correct``.

Every request due in the window is compared with the plain reference
(:mod:`reference`) of its operand pair.  Three numbers, each with a limit:

- ``unanswered``: requests with no result (never finished, shed, failed);
  limit 0.
- ``structure_mismatch``: answers whose row pointers or column indices
  differ from the reference's; limit 0 (the structure is exact).
- ``value_rel_err``: the largest |answer - reference| / |reference| over
  every entry of every answer with the right structure; the limit is the
  configuration's ``check.value_rel_err``, set between the program's
  readings and the control's (``PERF.md``).
"""
from __future__ import annotations

import numpy as np

TINY = np.finfo(np.float32).tiny


def compare(answers, refs, value_limit: float) -> tuple[dict, int]:
    """``answers``: ``[(key, (rpt, col, val) or None)]``; ``refs[key]`` =
    reference ``(rpt, col, val)``.  Returns the three numbers and how many
    requests fail a limit."""
    unanswered = mismatch = wrong = 0
    worst = 0.0
    for key, got in answers:
        if got is None:
            unanswered += 1
            continue
        rpt, col, val = (np.asarray(x) for x in got)
        r_rpt, r_col, r_val = refs[key]
        if not (np.array_equal(rpt, r_rpt) and np.array_equal(col, r_col)):
            mismatch += 1
            continue
        if val.size:
            ref = np.asarray(r_val, dtype=np.float64)
            err = np.abs(val.astype(np.float64) - ref) / np.maximum(
                np.abs(ref), TINY)
            err[~np.isfinite(err)] = np.inf       # a NaN answer is wrong
            worst = max(worst, float(err.max()))
            wrong += bool(err.max() > value_limit)
    numbers = {"unanswered": unanswered, "structure_mismatch": mismatch,
               "value_rel_err": worst}
    return numbers, unanswered + mismatch + wrong


def limits(config: dict) -> dict:
    return {"unanswered": 0, "structure_mismatch": 0,
            "value_rel_err": float(config["check"]["value_rel_err"])}


def verdict(numbers: dict, lim: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value": n, "limit": l}})``."""
    table = {k: {"value": numbers[k], "limit": lim[k]} for k in lim}
    return all(numbers[k] <= lim[k] for k in lim), table

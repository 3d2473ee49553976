"""The one traffic generator: operand pools and request order from a
configuration file, a traffic file and the seed.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

- ``loop``: ``"closed"`` (one client sends its next product when the last
  one is answered) or ``"open"`` (requests are due on a schedule, whether
  or not earlier ones are answered);
- ``sizes``: operand sizes as ``{"shift": k, "weight": w}``; a request of
  that size has ``rows // 2**k`` rows of the configuration's law, and sizes
  are drawn in proportion to ``w``;
- ``pool_per_size``: distinct operands made in set-up for each size; the
  requests of a size cycle through them;
- ``peak_after_answers``: the window's answers after which the chip's
  peak memory is read (``harness.PeakAt``);
- open loops: ``rate_per_s``, the mean arrival rate.

Every seed gets the same work: the same operand structures (drawn from
the size and the member's index alone) and, in an open loop, the same
schedule of arrivals and sizes.  The seed draws the operands' values and
the order in which each size's pool is cycled.  (Structures drawn from the
seed made the service's pow2-quantised executor shapes, and so its compile
work and time per product, differ from seed to seed on the power law; an
arrival order drawn from the seed moved the 95th percentile at 0.8 of the
knee by a quarter from seed to seed.)
"""
from __future__ import annotations

import importlib.util
import itertools
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def _law(name: str):
    path = BENCH / "laws" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_law_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def size_rows(config: dict, traffic: dict, rows: int | None = None) -> list[int]:
    """Rows of each of the traffic's operand sizes."""
    base = int(rows if rows is not None else config["rows"])
    return [max(1, base >> int(s["shift"])) for s in traffic["sizes"]]


def _rng(seed: int, *keys: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *keys])


def make_operand(config: dict, rows: int, seed: int, size: int, member: int):
    """One square operand A as ``(rpt, col, val)`` numpy arrays: the
    structure of member ``member`` of size ``size``, values from ``seed``."""
    rpt, col = _law(config["law"]).make(
        rows, config["law_params"], np.random.default_rng([size, member]))
    v = config["values"]
    val = _rng(seed, size, member).uniform(
        v["low"], v["high"], col.size).astype(v["dtype"])
    return rpt, col, val


def make_pools(config: dict, traffic: dict, seed: int,
               rows: int | None = None) -> list[list[tuple]]:
    """``pools[size][member]`` = ``(rpt, col, val)``."""
    n = int(traffic["pool_per_size"])
    return [[make_operand(config, r, seed, s, m) for m in range(n)]
            for s, r in enumerate(size_rows(config, traffic, rows))]


def _split(n: int, weights: list[float]) -> list[int]:
    """``n`` into parts proportional to ``weights`` (largest remainder)."""
    w = np.asarray(weights, dtype=np.float64)
    exact = n * w / w.sum()
    parts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - parts), kind="stable")[: n - parts.sum()]:
        parts[i] += 1
    return [int(p) for p in parts]


def _members(traffic: dict, sizes, seed: int):
    """(size, member) for each request of ``sizes``: each size cycles
    through its pool in an order drawn from the seed."""
    pool = int(traffic["pool_per_size"])
    order = [_rng(seed, s, 0x0DE7).permutation(pool)
             for s in range(len(traffic["sizes"]))]
    seen: dict = {}
    for s in sizes:
        n = seen.get(s, 0)
        seen[s] = n + 1
        yield int(s), int(order[s][n % pool])


def closed_order(traffic: dict, seed: int):
    """Endless (size, member) sequence of a closed loop: sizes in a fixed
    cycle by weight, each size cycling through its pool."""
    cycle = [i for i, s in enumerate(traffic["sizes"])
             for _ in range(int(s["weight"]))]
    yield from _members(traffic, itertools.chain.from_iterable(
        itertools.repeat(cycle)), seed)


def open_schedule(traffic: dict, seconds: float, seed: int):
    """``[(due_s, size, member)]`` of an open loop over ``seconds``.

    ``round(rate * seconds)`` arrivals; the gaps between them are the
    quantiles of the exponential distribution at that rate (a Poisson
    process's gaps) in a shuffled order, and the sizes are shuffled too;
    both shuffles are the same for every seed."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(0x7AFF1C)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    counts = _split(n, [s["weight"] for s in traffic["sizes"]])
    sizes = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    return [(float(d), s, m)
            for d, (s, m) in zip(due, _members(traffic, sizes, seed))]

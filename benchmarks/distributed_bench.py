"""Distributed SpGEMM: binned-routed plan/execute vs the legacy global-pad
shard path, per suite family, on a 4-device host mesh.

The acceptance metric for the unified pipeline (DESIGN.md §6): the power-law
family's distributed numeric phase must beat the legacy global-pad shard
path (the binned buffers are what the PR 1/2 lane reductions buy at pod
scale), uniform families must not regress materially, and the plan cache
must serve a second same-signature pair with ZERO executor retraces
(the serving scenario) — measured and checked here.

Standalone (sets the device-count env before jax init):

    PYTHONPATH=src python benchmarks/distributed_bench.py [--quick]

Emits ``dist.*`` CSV rows and writes ``BENCH_distributed.json`` at the repo
root (the perf-trajectory artifact committed per PR).  ``--quick`` shrinks
the matrices for CI.
"""
from __future__ import annotations

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse
import json
import sys

import jax
import numpy as np

from repro.sparse import random as sprand
from repro.sparse.formats import CSR
from repro.core import oracle
from repro import compile_cache
from repro.core import plan as plan_mod

try:
    from .common import timeit, emit, reset_records, write_bench_json
    from . import legacy_distributed as distributed
except ImportError:   # invoked as a script: python benchmarks/distributed_bench.py
    from common import timeit, emit, reset_records, write_bench_json
    import legacy_distributed as distributed

SHARDS = 4
_LAST: dict = {}


def _cases(quick: bool):
    s = 4 if quick else 1
    return [
        ("er", sprand.erdos_renyi(2000 // s, 2000 // s, 4, seed=61),
         sprand.erdos_renyi(2000 // s, 2000 // s, 3, seed=62)),
        ("pl", sprand.power_law(2000 // s, 2000 // s, 5, 1.5, seed=11),
         sprand.power_law(2000 // s, 2000 // s, 4, 1.6, seed=12)),
        ("band", sprand.banded(2000 // s, 2000 // s, 12, 16, seed=13),
         sprand.banded(2000 // s, 2000 // s, 10, 14, seed=14)),
        ("fem", sprand.banded(1200 // s, 1200 // s, 48, 32, seed=51),
         sprand.banded(1200 // s, 1200 // s, 40, 30, seed=52)),
    ]


def _revalue(m: CSR, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    return CSR(rpt=m.rpt.copy(), col=m.col.copy(),
               val=rng.standard_normal(m.nnz).astype(np.float32),
               shape=m.shape)


def run(quick: bool = False):
    _LAST.clear()
    if len(jax.devices()) < SHARDS:
        raise SystemExit(f"distributed_bench needs {SHARDS} devices, found "
                         f"{len(jax.devices())}")
    shards = SHARDS
    mesh = jax.make_mesh((shards,), ("data",))
    for fam, a, b in _cases(quick):
        # -- legacy global-pad shard path -------------------------------- #
        lplan = distributed.plan_distributed(a, b, num_shards=shards)
        t_legacy = timeit(lambda: jax.block_until_ready(
            distributed.distributed_spgemm(a, b, mesh, lplan)[3]))
        legacy_slots = int(lplan.row_table.shape[1] * lplan.row_capacity)

        # -- unified binned-routed plan/execute -------------------------- #
        cache = plan_mod.PlanCache()
        t_plan = timeit(lambda: plan_mod.plan_spgemm(a, b, mesh=mesh),
                        warmup=1, iters=3)
        plan = plan_mod.plan_spgemm(a, b, mesh=mesh)
        t_binned = timeit(lambda: plan_mod.execute(plan, a, b, cache=cache))

        # correctness cross-check against the exact symbolic structure
        res = plan_mod.execute(plan, a, b, cache=cache)
        c = plan_mod.reassemble(plan, res)
        _, z = oracle.exact_structure(a, b)
        assert c.nnz == z, (fam, c.nnz, z)

        # -- serving: same structure, new values, cache-served ----------- #
        a2, b2 = _revalue(a, 91), _revalue(b, 92)
        traces_before = cache.stats()["traces"]
        plan2 = plan_mod.plan_spgemm(a2, b2, mesh=mesh)
        same_key = plan2.key == plan.key
        t_cached = timeit(lambda: plan_mod.execute(plan2, a2, b2, cache=cache))
        retraces = cache.stats()["traces"] - traces_before

        speedup = t_legacy / max(t_binned, 1e-12)
        emit(f"dist.{fam}.legacy_numeric.us", t_legacy * 1e6, "global-pad")
        emit(f"dist.{fam}.binned_numeric.us", t_binned * 1e6, "binned-routed")
        emit(f"dist.{fam}.numeric_speedup.x", speedup, "legacy/binned")
        emit(f"dist.{fam}.plan.us", t_plan * 1e6, "plan_spgemm")
        emit(f"dist.{fam}.cache_numeric.us", t_cached * 1e6, "cache-served")
        emit(f"dist.{fam}.retraces.n", retraces, "serving pair")
        _LAST[fam] = dict(
            shards=shards,
            legacy_us=round(t_legacy * 1e6, 1),
            binned_us=round(t_binned * 1e6, 1),
            cached_us=round(t_cached * 1e6, 1),
            speedup=round(speedup, 3),
            plan_us=round(t_plan * 1e6, 1),
            retraces=int(retraces),
            cache=cache.stats(),
            same_key=bool(same_key),
            imbalance=round(float(plan.partition.imbalance), 4),
            shard_slots=plan.shard_slots(),
            legacy_shard_slots=legacy_slots,
            slots_reduction=round(legacy_slots / max(1, plan.shard_slots()), 3),
            overflow=int(res.shard_overflow.sum()),
            lane_reduction=round(plan.binning.lane_reduction, 3),
        )


def summary() -> dict:
    """Machine-readable results of the last run() (for the JSON artifact)."""
    return dict(_LAST)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="CI-sized matrices (rows/4)")
    args = p.parse_args(argv)
    compile_cache.configure()
    reset_records()
    run(quick=args.quick)
    out = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "BENCH_distributed.json"))
    write_bench_json(out, extra=dict(distributed=summary(), quick=args.quick))
    print(json.dumps(summary(), indent=1))
    print(f"wrote {out}")
    ok = True
    for fam, s in summary().items():
        if s["retraces"] != 0 or not s["same_key"]:
            print(f"FAIL: {fam} serving pair retraced "
                  f"({s['retraces']} traces, same_key={s['same_key']})")
            ok = False
        if s["overflow"]:
            print(f"FAIL: {fam} dropped {s['overflow']} entries")
            ok = False
    if args.quick:
        return 0 if ok else 1   # CI smoke: timings are dispatch-dominated
    # full-scale acceptance gates (ISSUE 3)
    if summary()["pl"]["speedup"] < 1.0:
        print("FAIL: power-law distributed numeric phase slower than the "
              f"legacy global-pad path ({summary()['pl']['speedup']}x)")
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

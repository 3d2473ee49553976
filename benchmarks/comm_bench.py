"""Communication / memory-footprint bench: column-partitioned B vs the
replicated-B executor (DESIGN.md §8), per suite family, on a 4-device host
mesh.

The acceptance metric for the panel-gathered numeric phase (ISSUE 5): on
the power-law family, the per-device B index+value footprint must drop by
≥ ~``n_panels``× vs the replicated executor (measured as the true gathered
payload — pow2 capacity padding is reported separately), with ZERO
retraces on a steady-state repeated multiply (same structure, new values —
compile-count-pinned like ``distributed_bench``).

Standalone (sets the device-count env before jax init):

    PYTHONPATH=src python benchmarks/comm_bench.py [--quick]

Emits ``comm.*`` CSV rows and writes ``BENCH_comm.json`` at the repo root
(the perf-trajectory artifact committed per PR).  ``--quick`` shrinks the
matrices for CI.
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
except ImportError:   # invoked as a script: python benchmarks/comm_bench.py
    from common import timeit, emit, reset_records, write_bench_json

SHARDS = 4
_LAST: dict = {}


def _cases(quick: bool):
    s = 4 if quick else 1
    return [
        ("er", sprand.erdos_renyi(2000 // s, 2000 // s, 8, seed=61),
         sprand.erdos_renyi(2000 // s, 2000 // s, 6, seed=62)),
        ("pl", sprand.power_law(2000 // s, 2000 // s, 8, 1.5, seed=11),
         sprand.power_law(2000 // s, 2000 // s, 6, 1.6, seed=12)),
        ("band", sprand.banded(2000 // s, 2000 // s, 12, 16, seed=13),
         sprand.banded(2000 // s, 2000 // s, 10, 14, seed=14)),
    ]


def _revalue(m: CSR, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    return CSR(rpt=m.rpt.copy(), col=m.col.copy(),
               val=rng.standard_normal(m.nnz).astype(np.float32),
               shape=m.shape)


def run(quick: bool = False):
    _LAST.clear()
    if len(jax.devices()) < SHARDS:
        raise SystemExit(
            f"comm_bench needs {SHARDS} devices, found {len(jax.devices())}"
            "; on CPU the XLA_FLAGS default above gives four unless "
            "XLA_FLAGS was already set without "
            "--xla_force_host_platform_device_count=4")
    shards = SHARDS
    mesh = jax.make_mesh((shards,), ("data",))
    for fam, a, b in _cases(quick):
        rec = {}
        # replicated-B reference executor (timing); its per-device footprint
        # comes from the panel plans' own comm_stats accounting so the bench
        # can never diverge from the plan's acceptance metric
        rep = plan_mod.plan_spgemm(a, b, mesh=mesh)
        rep_bytes = None
        cache = plan_mod.PlanCache()
        t_rep = timeit(lambda: plan_mod.execute(rep, a, b, cache=cache))
        for n_panels in dict.fromkeys((2, shards)):  # dedup at shards == 2
            pcache = plan_mod.PlanCache()
            plan = plan_mod.plan_spgemm(a, b, mesh=mesh, n_panels=n_panels)
            t_pan = timeit(lambda: plan_mod.execute(plan, a, b,
                                                    cache=pcache))
            res = plan_mod.execute(plan, a, b, cache=pcache)
            c = plan_mod.reassemble(plan, res)
            _, z = oracle.exact_structure(a, b)
            assert c.nnz == z, (fam, n_panels, c.nnz, z)

            # steady state: same structure, new values, cache-served
            a2, b2 = _revalue(a, 91), _revalue(b, 92)
            traces_before = pcache.stats()["traces"]
            plan2 = plan_mod.plan_spgemm(a2, b2, mesh=mesh,
                                         n_panels=n_panels)
            same_key = plan2.key == plan.key
            t_cached = timeit(lambda: plan_mod.execute(plan2, a2, b2,
                                                       cache=pcache))
            retraces = pcache.stats()["traces"] - traces_before

            comm = plan.comm_stats()
            rep_bytes = comm["replicated_b_bytes"]   # same cap_b every plan
            tag = f"comm.{fam}.p{n_panels}"
            emit(f"{tag}.per_device_b.bytes", comm["per_device_b_bytes"],
                 "panel-gathered")
            emit(f"{tag}.footprint_reduction.x",
                 comm["footprint_reduction"], "replicated/panel padded")
            emit(f"{tag}.payload_reduction.x", comm["payload_reduction"],
                 "B nnz / max gathered")
            emit(f"{tag}.gathered.bytes", comm["gathered_bytes_total"],
                 "all-to-all volume")
            emit(f"{tag}.numeric.us", t_pan * 1e6, "panel-gathered")
            emit(f"{tag}.cache_numeric.us", t_cached * 1e6, "cache-served")
            emit(f"{tag}.retraces.n", retraces, "serving pair")
            rec[f"p{n_panels}"] = dict(
                comm=comm,
                numeric_us=round(t_pan * 1e6, 1),
                cached_us=round(t_cached * 1e6, 1),
                retraces=int(retraces),
                same_key=bool(same_key),
                overflow=int(res.shard_overflow.sum()),
            )
        emit(f"comm.{fam}.replicated_b.bytes", rep_bytes, "legacy layout")
        emit(f"comm.{fam}.replicated_numeric.us", t_rep * 1e6,
             "replicated-B")
        rec["replicated"] = dict(b_bytes=int(rep_bytes),
                                 numeric_us=round(t_rep * 1e6, 1))
        _LAST[fam] = rec


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
                                       "BENCH_comm.json"))
    write_bench_json(out, extra=dict(comm=summary(), quick=args.quick))
    print(json.dumps(summary(), indent=1))
    print(f"wrote {out}")
    ok = True
    npan = SHARDS
    for fam, rec in summary().items():
        for k, s in rec.items():
            if k == "replicated":
                continue
            if s["retraces"] != 0 or not s["same_key"]:
                print(f"FAIL: {fam}.{k} steady-state pair retraced "
                      f"({s['retraces']} traces, same_key={s['same_key']})")
                ok = False
            if s["overflow"]:
                print(f"FAIL: {fam}.{k} dropped {s['overflow']} entries")
                ok = False
            if s["comm"]["per_device_b_bytes"] \
                    >= rec["replicated"]["b_bytes"]:
                print(f"FAIL: {fam}.{k} panel footprint not below the "
                      "replicated operand")
                ok = False
    if args.quick:
        return 0 if ok else 1   # CI smoke: timings are dispatch-dominated
    # full-scale acceptance gate (ISSUE 5): ~n_panels× B footprint drop on pl
    pl = summary()["pl"][f"p{npan}"]["comm"]
    if pl["payload_reduction"] < 0.75 * npan:
        print(f"FAIL: power-law per-device B payload reduced only "
              f"{pl['payload_reduction']}x (need ≥ ~{npan}x)")
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

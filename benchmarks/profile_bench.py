"""Measured route-profile bench: microbenchmark, persist, re-plan (§11).

Runs :func:`repro.core.profiles.microbenchmark` on the local device, saves
the resulting :class:`RouteProfile` JSON, reloads it through the normal
activation path, and re-plans the accumulator-bench families under the
measured numbers.  This is the end-to-end check that the paper's cost
model consumes *measured* per-route throughput rather than the analytic
constants it boots with:

  * the saved profile must survive a save → load round trip with
    identical route choices;
  * under the measured profile, the power-law family must route at least
    one hub bucket to the propagation-blocking BIN accumulator while the
    banded / FEM families stay SPA-dominant and mid-degree ER stays on
    ESC (the measured numbers must not scramble the regime map);
  * the admission controller's ``est_seconds`` must reprice from the
    profile's measured flops / bytes-per-second.

Standalone::

    PYTHONPATH=src python benchmarks/profile_bench.py [--quick]

Emits ``profile.*`` CSV rows and writes ``BENCH_profiles.json`` at the
repo root (committed per PR).  ``--quick`` runs the 4-cell CI grid
instead of the 10-cell full grid.  The planned matrices are full-scale
either way — planning is a structural pass with no numeric phase, and
shrinking them would shrink the column spans below the regime the route
gates describe (SPA survives at span ≤1024 and the pl hub question
degenerates to measurement noise).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro import compile_cache
from repro.core import binning, profiles
from repro.serve import admission
from repro.sparse import random as sprand

try:
    from .common import emit, reset_records, write_bench_json
except ImportError:      # invoked as a script: python benchmarks/profile_bench.py
    from common import emit, reset_records, write_bench_json

_LAST: dict = {}


def _cases():
    # always full-scale: build_plan is structural (no numeric phase), and
    # the gates are statements about full-scale column spans
    return [
        ("band", sprand.banded(2000, 2000, 24, 30, seed=13),
         sprand.banded(2000, 2000, 20, 26, seed=14)),
        ("fem", sprand.banded(1200, 1200, 48, 32, seed=51),
         sprand.banded(1200, 1200, 40, 30, seed=52)),
        ("er", sprand.erdos_renyi(2000, 2000, 10, seed=15),
         sprand.erdos_renyi(2000, 2000, 8, seed=16)),
        ("pl", sprand.power_law(3000, 3000, 5, 1.5, seed=11),
         sprand.power_law(3000, 3000, 4, 1.6, seed=12)),
    ]


def _admission_probe():
    """Fixed workload so est_seconds is comparable analytic vs measured."""
    structure = np.full(256, 8.0)
    flopr = np.full(256, 16.0)
    return admission.estimate(256, structure, flopr, 0.5,
                              nnz_a=2048, nnz_b=2048, nrows_b=256)


def run(quick: bool = False, out_dir: str | None = None):
    _LAST.clear()
    profiles.clear()

    # -- cold baseline: analytic constants ------------------------------- #
    cold_routes = {fam: binning.build_plan(a, b).route_rows()
                   for fam, a, b in _cases()}
    cold_est = _admission_probe().est_seconds

    # -- measure, persist, reload through the activation path ------------- #
    t0 = time.perf_counter()
    prof = profiles.microbenchmark(quick=quick)
    bench_s = time.perf_counter() - t0
    out_dir = out_dir or os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(out_dir, "route_profile.json")
    profiles.save(prof, path)
    profiles.clear()
    loaded = profiles.load(path)            # activates on success
    assert loaded is not None, f"saved profile failed to reload: {path}"
    assert loaded.to_json() == prof.to_json(), "profile JSON round trip drifted"
    st = profiles.status()
    assert st["source"] == "measured" and st["warning"] is None, st

    emit("profile.microbenchmark.s", bench_s, "wall time of measurement pass")
    emit("profile.cells.n", len(prof.cells), "grid cells measured")
    emit("profile.flops.g", prof.flops / 1e9, "measured dense-flop throughput")
    emit("profile.bytes_per_s.g", prof.bytes_per_s / 1e9,
         "measured stream bandwidth")

    # -- re-plan under the measured profile ------------------------------- #
    warm_routes = {}
    for fam, a, b in _cases():
        routes = binning.build_plan(a, b).route_rows()
        warm_routes[fam] = routes
        total = max(1, sum(routes.values()))
        for r, n in routes.items():
            emit(f"profile.{fam}.rows_{r}.n", n, "rows on route (measured)")
        emit(f"profile.{fam}.spa_fraction.x", routes["spa"] / total, "")
        emit(f"profile.{fam}.bin_fraction.x", routes["bin"] / total, "")

    # -- admission repricing ----------------------------------------------#
    warm_est = _admission_probe().est_seconds
    emit("profile.admission_est_cold.us", cold_est * 1e6, "analytic constants")
    emit("profile.admission_est_measured.us", warm_est * 1e6,
         "active-profile throughput")

    _LAST.update(
        quick=quick,
        device_kind=prof.device_kind,
        n_cells=len(prof.cells),
        microbenchmark_s=round(bench_s, 3),
        flops=prof.flops,
        bytes_per_s=prof.bytes_per_s,
        profile_path=path,
        routes_analytic=cold_routes,
        routes_measured=warm_routes,
        admission_est_cold_s=cold_est,
        admission_est_measured_s=warm_est,
    )
    profiles.clear()


def summary() -> dict:
    """Machine-readable results of the last run() (for the JSON artifacts)."""
    return dict(_LAST)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="4-cell CI grid instead of the 10-cell full grid")
    args = p.parse_args(argv)
    compile_cache.configure()
    reset_records()
    run(quick=args.quick)
    out = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "BENCH_profiles.json"))
    write_bench_json(out, extra=dict(profiles=summary(), quick=args.quick))
    print(json.dumps(summary(), indent=1))
    print(f"wrote {out}")

    # gates: the measured profile must preserve the regime map — these hold
    # under the --quick CI grid too (the planned matrices are full-scale)
    s = summary()
    warm = s["routes_measured"]
    ok = True
    if warm["pl"]["bin"] == 0:
        print("FAIL: measured profile routes no pl rows to BIN"); ok = False
    if warm["pl"]["spa"] > 0:
        print("FAIL: pl must not route SPA under the measured profile")
        ok = False
    for fam in ("band", "fem"):
        total = max(1, sum(warm[fam].values()))
        if warm[fam]["spa"] / total < 0.5:
            print(f"FAIL: {fam} lost SPA dominance under the measured "
                  f"profile: {warm[fam]}"); ok = False
    if not (s["admission_est_cold_s"] > 0 and s["admission_est_measured_s"] > 0):
        print("FAIL: admission estimates must be positive"); ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

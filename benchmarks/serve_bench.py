"""SpGEMM service scheduler bench: throughput + tail latency + containment.

Drives :class:`repro.serve.spgemm_service.SpgemmService` (DESIGN.md §10)
with mixed 5-family traffic and measures the serving economics:

  * **steady-state throughput** — requests/s through the synchronous loop
    after template warmup (every repeat template must hit the plan cache:
    retrace count gated to ZERO);
  * **tail latency** — p50/p99/max per-request seconds from the request
    history timestamps, per family and mixed;
  * **containment bands** — a load storm against a short queue must shed
    (not hang), a deadline storm must expire (not execute), and a fault
    storm (all injectable classes) must leave every request terminal with
    the queue drained; terminal-state counts are gated to bands;
  * **recovered throughput** — a straggler storm against a
    DispatchBudget-armed service must serve every request DEGRADED with
    the recovery ledger attached, retrace nothing on the second storm,
    and keep throughput within :data:`RECOVERY_BAND` of the armed
    no-fault baseline (DESIGN.md §12).

Standalone::

    PYTHONPATH=src python benchmarks/serve_bench.py [--quick]

Emits ``serve.*`` CSV rows and writes ``BENCH_serve.json`` at the repo
root (committed per PR).  ``--quick`` shrinks matrices + request counts
for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro import compile_cache
from repro.core import faults, plan as plan_mod
from repro.serve.spgemm_service import (RequestState, ServiceConfig,
                                        SpgemmService)
from repro.sparse import random as sprand

try:
    from .common import emit, reset_records, write_bench_json
except ImportError:   # invoked as a script
    from common import emit, reset_records, write_bench_json

_LAST: dict = {}


def _gen(fam: str, m: int, seed: int):
    if fam == "er":
        return (sprand.erdos_renyi(m, m, 4, seed=seed),
                sprand.erdos_renyi(m, m, 3, seed=seed + 50))
    if fam == "pl":
        return (sprand.power_law(m, m, 5, 1.5, seed=seed),
                sprand.power_law(m, m, 4, 1.6, seed=seed + 50))
    if fam == "rmat":
        return (sprand.rmat(m, m, 5 * m, seed=seed),
                sprand.rmat(m, m, 4 * m, seed=seed + 50))
    if fam == "band":
        return (sprand.banded(m, m, 12, 16, seed=seed),
                sprand.banded(m, m, 10, 14, seed=seed + 50))
    if fam == "fem":
        return (sprand.banded(m // 2, m // 2, 48, 32, seed=seed),
                sprand.banded(m // 2, m // 2, 40, 30, seed=seed + 50))
    raise ValueError(fam)


FAMILIES = ("er", "pl", "rmat", "band", "fem")

# recovered serving replays failed waves unit-by-unit host-side, so it is
# legitimately slower than the fused steady state — the band is a collapse
# detector (a broken ledger or executor-cache miss storm), not a target
RECOVERY_BAND = 0.04


def _traffic(m: int, reps: int):
    """Mixed request stream: ``reps`` rounds over all 5 families."""
    pairs = [(fam, *_gen(fam, m, seed=1000 + 10 * i))
             for i, fam in enumerate(FAMILIES)]
    return [(fam, a, b) for _ in range(reps) for fam, a, b in pairs]


def _latencies(reqs) -> dict:
    # service latency = time-to-RESULT: only DONE/DEGRADED requests count
    # (shed/expired tickets finish fast by design and would drag the
    # percentiles down exactly when the service is most overloaded)
    served = (RequestState.DONE, RequestState.DEGRADED)
    lat = np.asarray([r.latency for r in reqs
                      if r.state in served and r.latency is not None])
    if not lat.size:
        return dict(p50_ms=0.0, p99_ms=0.0, max_ms=0.0)
    return dict(p50_ms=round(float(np.percentile(lat, 50)) * 1e3, 3),
                p99_ms=round(float(np.percentile(lat, 99)) * 1e3, 3),
                max_ms=round(float(lat.max()) * 1e3, 3))


def _steady_state(m: int, reps: int) -> dict:
    """Warm every family's template, then time ``reps`` repeat rounds —
    the zero-retrace serving contract, measured end to end."""
    svc = SpgemmService(ServiceConfig(queue_capacity=16 * reps,
                                      max_batch=8))
    warm = _traffic(m, 1)
    for _, a, b in warm:
        svc.submit(a, b)
    svc.drain()
    # templates may have grown during warmup: one more round settles keys
    for _, a, b in warm:
        svc.submit(a, b)
    svc.drain()
    traces0 = svc.stats()["plan_cache"]["traces"]

    stream = _traffic(m, reps)
    t0 = time.perf_counter()
    reqs = [svc.submit(a, b) for _, a, b in stream]
    svc.drain()
    wall = time.perf_counter() - t0
    st = svc.stats()
    return dict(
        requests=len(reqs),
        wall_s=round(wall, 4),
        throughput_rps=round(len(reqs) / wall, 2),
        retraces=st["plan_cache"]["traces"] - traces0,
        waves=st["waves"],
        batched_per_wave=round(st["batched_requests"] / max(st["waves"], 1),
                               2),
        done=sum(r.state == RequestState.DONE for r in reqs),
        **_latencies(reqs),
    )


def _overload(m: int, reps: int) -> dict:
    """Storm a short queue: the overflow must shed typed, the admitted
    remainder must all complete, and nothing may hang."""
    svc = SpgemmService(ServiceConfig(queue_capacity=8, max_batch=8))
    reqs = [svc.submit(a, b) for _, a, b in _traffic(m, reps)]
    svc.drain()
    st = svc.stats()
    term = st["terminal"]
    return dict(requests=len(reqs), shed=term["SHED"], done=term["DONE"],
                queue_depth=st["queue"]["depth"],
                in_flight=st["in_flight"],
                # both views, from the service's own stats: served-only
                # percentiles stay honest under shedding, terminal
                # percentiles show how fast the rejection path triages
                latency=st["latency"],
                terminal_latency=st["terminal_latency"])


def _deadline_storm(m: int) -> dict:
    """Every queued-behind request carries an already-hopeless deadline:
    the service must expire them at the next scheduling point instead of
    executing stale work."""
    t = [0.0]
    svc = SpgemmService(ServiceConfig(), clock=lambda: t[0])
    fam, a, b = "er", *_gen("er", m, seed=77)
    live = svc.submit(a, b)
    doomed = [svc.submit(a, b, deadline=0.5) for _ in range(10)]
    t[0] = 1.0
    svc.drain()
    return dict(expired=sum(r.state == RequestState.EXPIRED for r in doomed),
                doomed=len(doomed),
                live_done=live.state == RequestState.DONE)


def _fault_storm(m: int, reps: int) -> dict:
    """Chaos rounds (capacity / sketch / executor faults) — every request
    terminal, queue drained, failures typed."""
    svc = SpgemmService(ServiceConfig(queue_capacity=16 * reps,
                                      breaker_cooldown=0.0))
    storms = [dict(capacity_scale=0.2), dict(sketch_scale=0.05),
              dict(fail_executor={"unit": "local"})]
    reqs = []
    for i, storm in enumerate(storms * max(1, reps // 3)):
        reqs.extend(svc.submit(a, b) for _, a, b in _traffic(m, 1))
        with faults.inject(seed=i, **storm):
            svc.drain()
    st = svc.stats()
    return dict(requests=len(reqs),
                terminal=dict(st["terminal"]),
                all_terminal=all(r.done for r in reqs),
                typed_errors=all(r.error is None
                                 or isinstance(r.error, ValueError)
                                 for r in reqs),
                queue_depth=st["queue"]["depth"],
                requeues=st["requeues"])


def _recovery_storm(m: int, reps: int) -> dict:
    """Straggler storm: every local dispatch reports a simulated 30 s
    delay against a ``floor_s=0.25`` DispatchBudget, so the watchdog fires
    on every wave and the per-unit recovery path serves the whole stream.
    Measured against a no-fault baseline on the SAME armed service — the
    watchdog itself must not slow the steady state."""
    svc = SpgemmService(ServiceConfig(
        queue_capacity=64 * reps, max_batch=8,
        dispatch_budget=plan_mod.DispatchBudget(multiple=50.0,
                                                floor_s=0.25)))
    warm = _traffic(m, 1)
    for _ in range(2):                 # second round settles grown templates
        for _, a, b in warm:
            svc.submit(a, b)
        svc.drain()

    stream = _traffic(m, reps)
    t0 = time.perf_counter()
    base = [svc.submit(a, b) for _, a, b in stream]
    svc.drain()
    base_wall = time.perf_counter() - t0

    # storm 1 compiles the recovery executors (bucket-retry family) …
    t0 = time.perf_counter()
    reqs = [svc.submit(a, b) for _, a, b in stream]
    with faults.inject(delay_executor={"unit": "local"}, delay_s=30.0):
        svc.drain()
    wall = time.perf_counter() - t0
    # … so storm 2 must hit the cache: ZERO retraces (gated)
    traces1 = svc.stats()["plan_cache"]["traces"]
    reqs2 = [svc.submit(a, b) for _, a, b in stream]
    with faults.inject(delay_executor={"unit": "local"}, delay_s=30.0):
        svc.drain()
    st = svc.stats()
    recovered = [r for r in reqs + reqs2
                 if r.state == RequestState.DEGRADED]
    base_rps = len(base) / max(base_wall, 1e-9)
    rec_rps = len(reqs) / max(wall, 1e-9)
    return dict(
        requests=len(reqs) + len(reqs2),
        recovered=len(recovered),
        with_ledger=sum(1 for r in recovered if r.stats.get("recoveries")),
        clean_done=sum(r.state == RequestState.DONE for r in base),
        base_rps=round(base_rps, 2),
        recovered_rps=round(rec_rps, 2),
        band_x=round(rec_rps / max(base_rps, 1e-9), 4),
        storm_retraces=st["plan_cache"]["traces"] - traces1,
        breaker_trips=sum(b["trips"] for b in st["breakers"]),
        **_latencies(reqs + reqs2),
    )


def run(quick: bool = False):
    _LAST.clear()
    m = 400 if quick else 1500
    reps = 4 if quick else 10
    _LAST["steady"] = _steady_state(m, reps)
    _LAST["overload"] = _overload(m, reps)
    _LAST["deadline"] = _deadline_storm(m)
    _LAST["faults"] = _fault_storm(m, reps)
    _LAST["recovery"] = _recovery_storm(m, max(1, reps // 2))
    s = _LAST["steady"]
    emit("serve.steady.throughput.rps", s["throughput_rps"],
         "mixed 5-family repeat traffic, warmed templates")
    emit("serve.steady.p99.ms", s["p99_ms"], "per-request latency")
    emit("serve.steady.retraces.n", s["retraces"],
         "steady-state repeat traffic (gated to 0)")
    emit("serve.steady.batch.x", s["batched_per_wave"],
         "requests per dispatch wave")
    emit("serve.overload.shed.n", _LAST["overload"]["shed"],
         "typed sheds under queue storm")
    emit("serve.deadline.expired.n", _LAST["deadline"]["expired"],
         "hopeless deadlines expired, not executed")
    emit("serve.faults.requeues.n", _LAST["faults"]["requeues"],
         "escalated capacity requeues under chaos")
    rc = _LAST["recovery"]
    emit("serve.recovery.throughput.rps", rc["recovered_rps"],
         "straggler storm served via per-unit recovery")
    emit("serve.recovery.band.x", rc["band_x"],
         f"recovered / armed-baseline throughput (gate >= {RECOVERY_BAND})")
    emit("serve.recovery.retraces.n", rc["storm_retraces"],
         "second straggler storm (gated to 0)")


def summary() -> dict:
    return dict(_LAST)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quick", action="store_true",
                   help="CI-sized matrices + request counts")
    args = p.parse_args(argv)
    compile_cache.configure()
    reset_records()
    run(quick=args.quick)
    out = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "BENCH_serve.json"))
    write_bench_json(out, extra=dict(serve=summary(), quick=args.quick))
    print(json.dumps(summary(), indent=1))
    print(f"wrote {out}")

    ok = True
    s = summary()
    if s["steady"]["retraces"] != 0:
        print(f"FAIL: steady-state traffic retraced "
              f"{s['steady']['retraces']} executors")
        ok = False
    if s["steady"]["done"] != s["steady"]["requests"]:
        print("FAIL: steady-state traffic must complete clean")
        ok = False
    ov = s["overload"]
    if ov["shed"] + ov["done"] != ov["requests"] or ov["queue_depth"] \
            or ov["in_flight"]:
        print(f"FAIL: overload storm leaked requests: {ov}")
        ok = False
    if ov["shed"] == 0:
        print("FAIL: overload storm must shed against an 8-slot queue")
        ok = False
    dl = s["deadline"]
    if dl["expired"] != dl["doomed"] or not dl["live_done"]:
        print(f"FAIL: deadline storm mis-triaged: {dl}")
        ok = False
    fl = s["faults"]
    if not (fl["all_terminal"] and fl["typed_errors"]
            and fl["queue_depth"] == 0):
        print(f"FAIL: fault storm containment: {fl}")
        ok = False
    rc = s["recovery"]
    if rc["clean_done"] * 2 != rc["requests"]:
        print(f"FAIL: armed no-fault baseline must complete clean: {rc}")
        ok = False
    if rc["recovered"] != rc["requests"] \
            or rc["with_ledger"] != rc["recovered"]:
        print(f"FAIL: straggler storm must recover EVERY request DEGRADED "
              f"with the ledger attached: {rc}")
        ok = False
    if rc["storm_retraces"] != 0:
        print(f"FAIL: second straggler storm retraced "
              f"{rc['storm_retraces']} executors")
        ok = False
    if rc["breaker_trips"] != 0:
        print(f"FAIL: recovered requests tripped a breaker: {rc}")
        ok = False
    if rc["band_x"] < RECOVERY_BAND:
        print(f"FAIL: recovered throughput band {rc['band_x']} "
              f"< {RECOVERY_BAND}")
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Find an open-loop cell's knee: the highest arrival rate the service
sustains without a growing backlog.

    python3 bench/sweep.py --workload m133b3.tenants --seed 7 \\
        --seconds 15 --rates 5 10 20 30 40

One process sets the cell up once and then runs the traffic at each rate
in turn.  Each line gives the rate offered and completed, the median and
95th-percentile latency from the due time, the requests still unanswered
when the last one was due, and the slope of latency against due time (a
backlog that grows makes it positive).  The cell's traffic file then takes
0.8 of the knee as its fixed rate.  Without a TPU it exits non-zero after
rehearsing, as ``run.py --rehearse`` does.
"""
import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def point(sent, t0, t1) -> dict:
    done = [s for s in sent if s.result is not None]
    due = np.array([s.due for s in done])
    lat = np.array([s.finished_at - s.due for s in done])
    last_due = max(s.due for s in sent)
    return {"requests": len(sent), "answered": len(done),
            "completed_per_s": len(done) / (t1 - t0),
            "p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "backlog_at_last_due": int(sum(
                s.finished_at is None or s.finished_at > last_due
                for s in sent)),
            "latency_slope": float(np.polyfit(due - t0, lat, 1)[0]),
            "late_max_s": max(s.submitted - s.due for s in sent),
            "states": dict(collections.Counter(s.state for s in sent))}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--rehearse", type=int, default=0, metavar="ROWS")
    args = p.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness
    from repro import compile_cache
    cell, config, traffic = harness.cell_files(harness.benchmark(),
                                               args.workload)
    compile_cache.configure()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print("FAIL: no TPU", file=sys.stderr)
        return 1
    clock = time.perf_counter
    c = harness.set_up(config, traffic, chips=int(cell["chips"]),
                       seed=args.seed, rows=args.rehearse or None,
                       clock=clock, log=harness._stderr)
    for i, rate in enumerate(args.rates):
        sent, t0, t1 = harness.open_window(
            c.svc, c.mats, dict(traffic, rate_per_s=rate), args.seconds,
            args.seed + i, clock)
        print(json.dumps({"rate_per_s": rate, **point(sent, t0, t1)}),
              flush=True)
    return 0 if on_chip and not args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())

"""Bring-up smoke test: the SpGEMM service end to end on a TPU.

Drives the service's main path once through the entry points a user calls
(``SpgemmService.submit/drain`` → ``plan_spgemm`` → ``execute`` →
``reassemble``) on million-row operands generated from ``--seed``, and
checks every result against the host oracle ``core.oracle.spgemm``.

    python chip_smoke.py              # one chip: the service path
    python chip_smoke.py --chips 4    # four chips: the distributed path only
    JAX_PLATFORMS=cpu python chip_smoke.py --small   # CPU rehearsal

The last line of a passing run is one JSON object naming the device.  Any
failed check, or a platform other than ``tpu``, exits non-zero without it:
``--small`` runs the same path at a CPU-friendly size and then fails the
platform check, so a rehearsal can never pass as a chip run.  For the
four-chip rehearsal on CPU add
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Operand sizes (rows of square A; each request computes A·A, the paper's
# protocol).  Sized by compiling the service's executors for a described
# v5e: er at 2^20 rows needs ~5.3 GiB (2.0 output + 3.2 temporaries), pl at
# 2^19 rows ~8.5 GiB (its 1024-slot hub capacity pads every output row), so
# the fullest executor takes about half of the 16 GiB of HBM.  pl at 2^20
# would need ~17 GiB.
FULL = dict(er=1 << 20, pl=1 << 19)
SMALL = dict(er=1 << 13, pl=1 << 12)
MIN_HBM_SHARE = 0.25          # the fullest executor must use at least this
RTOL = ATOL = 1e-5            # float32 accumulation-order tolerance


def fail(msg: str) -> bool:
    print(f"FAIL: {msg}", file=sys.stderr)
    return False


def make_operand(family: str, rows: int, seed: int):
    from repro.sparse import random as sprand
    if family == "er":       # the paper's uniform family (m133-b3 analogue)
        return sprand.erdos_renyi(rows, rows, 8, seed=seed)
    # pl_100k_d4 / webbase-1M analogue
    return sprand.power_law(rows, rows, 4, 1.8, seed=seed)


def revalue(m, seed: int):
    """Same structure, new positive values: the zero-retrace serving pair."""
    import numpy as np
    from repro.sparse.formats import CSR
    rng = np.random.default_rng(seed)
    return CSR(rpt=m.rpt.copy(), col=m.col.copy(),
               val=rng.uniform(0.5, 1.5, m.nnz).astype(np.float32),
               shape=m.shape)


def check_against(name: str, got, ref) -> bool:
    """Structure exact, values to float32 tolerance."""
    import numpy as np
    if got.shape != ref.shape:
        return fail(f"{name}: shape {got.shape} != {ref.shape}")
    if not np.array_equal(got.rpt, ref.rpt):
        return fail(f"{name}: rpt differs from the reference")
    if not np.array_equal(got.col, ref.col):
        return fail(f"{name}: col differs from the reference")
    if not np.allclose(got.val, ref.val, rtol=RTOL, atol=ATOL):
        err = float(np.max(np.abs(got.val - ref.val)))
        return fail(f"{name}: values differ (max abs err {err})")
    return True


def split_seconds(plan, a, b, cache) -> tuple[float, float]:
    """Seconds of one warm ``execute`` (to ``block_until_ready``) and of
    the ``reassemble`` of its result."""
    import jax
    from repro.core import plan as plan_mod
    t0 = time.perf_counter()
    out = jax.block_until_ready(plan_mod.execute(plan, a, b, cache=cache))
    t1 = time.perf_counter()
    plan_mod.reassemble(plan, out)
    return t1 - t0, time.perf_counter() - t1


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lowering, XLA compile)."""

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def service_phase(sizes: dict, seed: int, clock: CompileClock) -> bool:
    import jax
    from repro.core import oracle, profiles
    from repro.core import plan as plan_mod
    from repro.serve.spgemm_service import (RequestState, ServiceConfig,
                                            SpgemmService)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    hbm = stats.get("bytes_limit")
    budget = int(hbm) if hbm else ServiceConfig().device_budget_bytes
    print(f"device memory: bytes_limit={hbm} -> service "
          f"device_budget_bytes={budget}")

    operands = {}
    for i, (fam, rows) in enumerate(sizes.items()):
        t0 = time.perf_counter()
        operands[fam] = make_operand(fam, rows, seed + i)
        a = operands[fam]
        print(f"operand {fam}: rows={a.nrows} nnz={a.nnz} "
              f"generated in {time.perf_counter() - t0:.3f}s")

    cache = plan_mod.PlanCache()
    svc = SpgemmService(ServiceConfig(device_budget_bytes=budget,
                                      seed=seed), cache=cache)
    jobs = []                  # (family, first-of-its-template?, request)
    for fam, a in operands.items():
        jobs.append((fam, True, svc.submit(a, a)))
    for i, (fam, a) in enumerate(operands.items()):
        a2 = revalue(a, seed + 100 + i)
        jobs.append((fam, False, svc.submit(a2, a2)))
    t0 = time.perf_counter()
    svc.drain()
    print(f"drain: {len(jobs)} requests in {time.perf_counter() - t0:.3f}s "
          f"(compile {clock.seconds:.3f}s, "
          f"{clock.cache_hits} persistent-cache hits)")

    ok = True
    st = svc.stats()
    if st["requeues"] or st["terminal"][RequestState.SHED]:
        ok = fail(f"service requeued or shed: {st['terminal']}, "
                  f"requeues={st['requeues']}")
    traces = st["plan_cache"]["traces"]
    if traces != len(operands):
        ok = fail(f"{traces} executor traces for {len(operands)} templates: "
                  "the revalued pairs did not reuse their executors")
    print(f"plan cache: {st['plan_cache']}")

    hbm_use = {}
    for fam, first, req in jobs:
        tag = f"{fam}{'' if first else ' (new values)'} request {req.id}"
        if req.state != RequestState.DONE:
            ok = fail(f"{tag} ended {req.state}: {req.error}")
            continue
        if req.stats["recoveries"] or req.stats["degradations"]:
            ok = fail(f"{tag} was recovered or degraded: {req.stats}")
        t_exec = next(t for s, t in req.history
                      if s == RequestState.EXECUTING)
        exec_s = req.finished_at - t_exec
        plan = req.plan
        ref = oracle.spgemm(req.a, req.b)
        rel = abs(plan.predicted_nnz - ref.nnz) / max(1, ref.nnz)
        bstats = plan.binning.stats()
        print(f"{tag}: {'cold, includes compile' if first else 'steady'} "
              f"request {exec_s:.3f}s (execute + reassemble to host), "
              f"latency {req.latency:.3f}s, retries {req.stats['retries']}")
        print(f"  buckets={bstats['num_buckets']} "
              f"routes={bstats['route_rows']} "
              f"predicted NNZ(C)={plan.predicted_nnz:.1f} true={ref.nnz} "
              f"rel_err={rel:.6f}")
        ok = check_against(tag, req.result, ref) and ok
        if not first:
            execute_s, reassemble_s = split_seconds(plan, req.a, req.b, cache)
            print(f"  steady split, re-run outside the service: execute "
                  f"{execute_s:.3f}s (dispatch to block_until_ready), "
                  f"reassemble {reassemble_s:.3f}s (device to host CSR)")
        if first:
            compiled = plan_mod.lower_local(plan, req.a, req.b,
                                            cache=cache).compile()
            ma = compiled.memory_analysis()
            need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            hbm_use[fam] = need
            print(f"  executor memory_analysis: args="
                  f"{ma.argument_size_in_bytes} out={ma.output_size_in_bytes}"
                  f" temp={ma.temp_size_in_bytes} total={need} bytes"
                  + (f" = {need / hbm:.3f} of HBM" if hbm else ""))
    if cache.traces != traces:
        ok = fail("lowering for memory_analysis retraced an executor")
    if hbm and hbm_use:
        fam, need = max(hbm_use.items(), key=lambda kv: kv[1])
        print(f"fullest executor: {fam}, {need / hbm:.3f} of HBM "
              f"(size chosen for {MIN_HBM_SHARE:.2f}-0.60)")
        if need / hbm < MIN_HBM_SHARE and sizes == FULL:
            ok = fail(f"fullest executor uses {need / hbm:.3f} of HBM, "
                      f"under {MIN_HBM_SHARE}: not a real-sized run")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    src = profiles.status()
    print(f"route profile: {src}"
          + (" — no profile has been measured for this device, so routing "
             "and admission price with the analytic model"
             if src["source"] == "analytic" else ""))
    return ok


def distributed_phase(sizes: dict, seed: int, clock: CompileClock) -> bool:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import oracle
    from repro.core import plan as plan_mod
    devices = jax.devices()
    if len(devices) < 4:
        return fail(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices[:4]), ("data",))
    a = make_operand("er", sizes["er"], seed)
    print(f"operand er: rows={a.nrows} nnz={a.nnz}; mesh {dict(mesh.shape)} "
          f"on devices {[d.id for d in mesh.devices.flat]}")
    ref = oracle.spgemm(a, a)
    cache = plan_mod.PlanCache()
    local = plan_mod.plan_spgemm(a, a, seed=seed)
    plan_mod.execute(local, a, a, cache=cache)      # compile
    execute_s, reassemble_s = split_seconds(local, a, a, cache)
    print(f"single-device steady: execute {execute_s:.3f}s, reassemble "
          f"{reassemble_s:.3f}s")
    c_local = plan_mod.reassemble(local, plan_mod.execute(local, a, a,
                                                          cache=cache))
    ok = check_against("single-device", c_local, ref)
    for n_panels in (0, 2):
        tag = f"row-sharded x{n_panels} panels" if n_panels else "row-sharded"
        plan = plan_mod.plan_spgemm(a, a, mesh=mesh, n_panels=n_panels,
                                    seed=seed)
        times = []
        for _ in range(2):     # cold (compiles), then steady
            t0 = time.perf_counter()
            out = jax.block_until_ready(plan_mod.execute(plan, a, a,
                                                         cache=cache))
            times.append(time.perf_counter() - t0)
        print(f"{tag}: execute cold {times[0]:.3f}s steady {times[1]:.3f}s; "
              f"buckets={len(plan.binning.buckets)} "
              f"predicted NNZ(C)={plan.predicted_nnz:.1f} true={ref.nnz}")
        if plan.recoveries or plan.degradations:
            ok = fail(f"{tag}: recovered or degraded")
        for i, arr in enumerate(out.cols):
            shards = getattr(arr, "addressable_shards", None)
            if shards is None:
                ok = fail(f"{tag}: bucket {i} output is not a device array")
                continue
            place = sorted((sh.device.id, sh.index[0].start or 0)
                           for sh in shards)
            if len({d for d, _ in place}) != 4 or \
                    len({r for _, r in place}) != 4:
                ok = fail(f"{tag}: bucket {i} output shards are not on 4 "
                          f"distinct devices: {place}")
            elif i == 0:
                print(f"  bucket 0 output shard (device id, first slab): "
                      f"{place}")
        c = plan_mod.reassemble(plan, out)
        if not (np.array_equal(c.rpt, c_local.rpt)
                and np.array_equal(c.col, c_local.col)):
            ok = fail(f"{tag}: rpt/col not bitwise equal to single-device")
        else:
            print(f"  rpt/col bitwise equal to single-device execute")
        ok = check_against(tag, c, ref) and ok
    print(f"compile {clock.seconds:.3f}s in all")
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the distributed path, on four chips")
    p.add_argument("--small", action="store_true",
                   help="CPU rehearsal size; always fails the platform check")
    args = p.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"FAIL: the repository's sources are not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = compile_cache.configure()
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={len(devices)}")
    print(f"compile cache: {cache_dir}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.small:
        fail(f"no TPU: JAX found {dev.platform!r} (use --small to rehearse)")
        return 1
    sizes = SMALL if args.small else FULL
    clock = CompileClock()
    phase = distributed_phase if args.chips == 4 else service_phase
    if not phase(sizes, args.seed, clock):
        return 1
    if not on_tpu:
        fail(f"platform check: ran on {dev.platform!r}, not 'tpu'")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

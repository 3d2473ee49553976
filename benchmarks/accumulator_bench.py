"""SPA vs ESC vs BIN accumulator backends on the suite families (§5, §11).

The acceptance metric for the hybrid accumulator backend: on the dense-ish
regimes that degree binning left at ~1× (banded / FEM / mid-degree ER — all
compact column spaces with wide gather buffers) the planner must select the
SPA route and the symbolic phase must run ≥2× faster than the sort route;
power-law families keep their narrow buckets on ESC (no SPA), route their
hub buckets to propagation-blocking BIN, and the widest hub bucket must run
strictly faster on BIN than on ESC.  Symbolic ``z*``/``f*`` must be
bitwise-equal across routes and numeric outputs allclose with identical
overflow accounting — measured and checked here on every family.

Emits ``accum.*`` CSV rows and writes ``BENCH_accumulators.json`` at the
repo root (the perf-trajectory artifact committed per PR).  ``--quick``
shrinks the matrices for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.sparse import random as sprand
from repro.core import binning, csr, predictor, spgemm
from repro.core.flop import flop_per_row

try:
    from .common import timeit, emit, reset_records, write_bench_json
except ImportError:      # invoked as a script: python benchmarks/accumulator_bench.py
    from common import timeit, emit, reset_records, write_bench_json

_LAST: dict = {}


def _cases(quick: bool):
    s = 4 if quick else 1
    return [
        # band/fem mirror the suite's band_40k_d24 / fem_24k_d56 regimes
        ("band", sprand.banded(2000 // s, 2000 // s, 24, 30, seed=13),
         sprand.banded(2000 // s, 2000 // s, 20, 26, seed=14)),
        ("fem", sprand.banded(1200 // s, 1200 // s, 48, 32, seed=51),
         sprand.banded(1200 // s, 1200 // s, 40, 30, seed=52)),
        ("er", sprand.erdos_renyi(2000 // s, 2000 // s, 10, seed=15),
         sprand.erdos_renyi(2000 // s, 2000 // s, 8, seed=16)),
        ("pl", sprand.power_law(3000 // s, 3000 // s, 5, 1.5, seed=11),
         sprand.power_law(3000 // s, 3000 // s, 4, 1.6, seed=12)),
    ]


def run(quick: bool = False):
    _LAST.clear()
    for fam, a, b in _cases(quick):
        ad, bd = csr.to_device(a), csr.to_device(b)
        plans = {r: binning.build_plan(a, b, route=r)
                 for r in ("auto", "esc", "spa", "bin")}
        routes = plans["auto"].route_rows()
        # the paper's 0.003·M sampling gives single-digit rows at bench
        # scale — far below timer resolution; an inflated sample keeps the
        # per-sample phase cost measurable (counts stay route-invariant)
        rows = predictor.draw_sample_rows(
            jax.random.PRNGKey(0), a.nrows, min(512, a.nrows))

        # -- symbolic phase (the z*/f* counting pass), per route ---------- #
        sym_us, counts = {}, {}
        for mode, use_kernel in (("jnp", False), ("kernel", True)):
            for r in ("esc", "spa", "bin", "auto"):
                fn = lambda r=r, uk=use_kernel: jax.block_until_ready(
                    predictor.binned_symbolic_counts(
                        ad, bd, rows, plans[r], use_kernel=uk)[0])
                # symbolic runs are sub-ms: extra iters keep the ratio stable
                sym_us[(mode, r)] = timeit(fn, warmup=2, iters=7) * 1e6
                z, f = predictor.binned_symbolic_counts(
                    ad, bd, rows, plans[r], use_kernel=use_kernel)
                counts[(mode, r)] = (int(z), int(f))
        zf = set(counts.values())
        assert len(zf) == 1, f"z*/f* not route-invariant on {fam}: {counts}"

        # -- numeric phase, per route ------------------------------------- #
        floprc, _ = flop_per_row(ad, bd)
        pred = predictor.proposed_predict_binned(ad, bd, rows, plans["esc"])
        num_us, outs = {}, {}
        for r in ("esc", "spa", "bin", "auto"):
            balloc = predictor.BinnedAllocationPlan.from_prediction(
                plans[r], np.asarray(pred.structure), np.asarray(floprc),
                safety=1.5)
            num_us[r] = timeit(lambda r=r, al=balloc: jax.block_until_ready(
                spgemm.spgemm_binned(ad, bd, plans[r], alloc=al).overflow)) * 1e6
            outs[r] = spgemm.spgemm_binned(ad, bd, plans[r], alloc=balloc)
        for r in ("spa", "bin", "auto"):
            np.testing.assert_array_equal(np.asarray(outs["esc"].col),
                                          np.asarray(outs[r].col))
            np.testing.assert_allclose(np.asarray(outs["esc"].val),
                                       np.asarray(outs[r].val),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_array_equal(np.asarray(outs["esc"].row_nnz),
                                          np.asarray(outs[r].row_nnz))
            assert int(outs["esc"].overflow) == int(outs[r].overflow)

        # -- pl hub bucket: the regime BIN exists for ---------------------- #
        # time the widest auto bucket alone, esc vs bin, through the same
        # per-bucket dispatch the executors use (routed_spgemm_rows)
        hub = None
        if fam == "pl":
            bk = max(plans["auto"].buckets, key=lambda k: k.width)
            span = bk.span or binning.ceil_pow2(b.ncols)
            tile, ntiles = binning.bin_tile(span, binning.DEFAULT_LANE_BUDGET)
            hrows = jnp.asarray(bk.rows)
            cap = binning.ceil_pow2(int(min(span, bk.width)))
            blk_esc = binning._pick_block_rows(
                bk.width, binning.DEFAULT_LANE_BUDGET,
                binning.DEFAULT_MAX_BLOCK_ROWS)
            hub_t = {}
            for r, tn, nt, blk in (("esc", 0, 0, blk_esc),
                                   ("bin", tile, ntiles, bk.block_rows)):
                fn = lambda r=r, tn=tn, nt=nt, blk=blk: jax.block_until_ready(
                    spgemm.routed_spgemm_rows(
                        ad, bd, hrows, row_capacity=cap, deg_a=bk.deg_a,
                        deg_b=bk.deg_b, block_rows=blk, route=r, tile_n=tn,
                        n_tiles=nt, span=span).overflow)
                hub_t[r] = timeit(fn) * 1e6
            hub_speedup = hub_t["esc"] / max(hub_t["bin"], 1e-9)
            emit(f"accum.{fam}.hub_bin_speedup.x", hub_speedup,
                 f"esc/bin on widest bucket w={bk.width}")
            hub = dict(width=int(bk.width), rows=int(bk.n_rows),
                       esc_us=round(hub_t["esc"], 1),
                       bin_us=round(hub_t["bin"], 1),
                       speedup=round(hub_speedup, 3))

        sym_speedup_jnp = sym_us[("jnp", "esc")] / max(sym_us[("jnp", "auto")], 1e-9)
        sym_speedup_kernel = (sym_us[("kernel", "esc")] /
                              max(sym_us[("kernel", "auto")], 1e-9))
        num_speedup = num_us["esc"] / max(num_us["auto"], 1e-9)
        for (mode, r), us in sym_us.items():
            emit(f"accum.{fam}.symbolic_{mode}_{r}.us", us, r)
        for r, us in num_us.items():
            emit(f"accum.{fam}.numeric_{r}.us", us, r)
        emit(f"accum.{fam}.symbolic_speedup_kernel.x", sym_speedup_kernel,
             "esc/auto")
        emit(f"accum.{fam}.symbolic_speedup_jnp.x", sym_speedup_jnp,
             "esc/auto")
        emit(f"accum.{fam}.numeric_speedup.x", num_speedup, "esc/auto")
        _LAST[fam] = dict(
            routes=routes,
            spa_fraction=round(routes["spa"] / max(1, sum(routes.values())), 3),
            bin_fraction=round(routes["bin"] / max(1, sum(routes.values())), 3),
            hub=hub,
            z_star=zf.pop()[0],
            symbolic_us={f"{m}_{r}": round(v, 1)
                         for (m, r), v in sym_us.items()},
            numeric_us={r: round(v, 1) for r, v in num_us.items()},
            symbolic_speedup_kernel=round(sym_speedup_kernel, 3),
            symbolic_speedup_jnp=round(sym_speedup_jnp, 3),
            numeric_speedup=round(num_speedup, 3),
            overflow=int(outs["esc"].overflow),
        )


def summary() -> dict:
    """Machine-readable results of the last run() (for the JSON artifacts)."""
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
                                       "BENCH_accumulators.json"))
    write_bench_json(out, extra=dict(accumulators=summary(),
                                     quick=args.quick))
    print(json.dumps(summary(), indent=1))
    print(f"wrote {out}")
    if args.quick:
        return 0      # CI smoke: equivalence checked, timings are
                      # dispatch-overhead-dominated at quick scale
    # sanity gates mirroring the PR acceptance criteria (full scale only)
    ok = True
    for fam, s in summary().items():
        if fam == "pl":
            if s["spa_fraction"] > 0:
                print(f"FAIL: {fam} expected no SPA routing"); ok = False
            if s["routes"]["bin"] == 0:
                print(f"FAIL: {fam} expected hub buckets on BIN"); ok = False
            if s["hub"] is None or s["hub"]["speedup"] <= 1.0:
                print(f"FAIL: {fam} widest hub bucket must be strictly "
                      f"faster on BIN than ESC: {s['hub']}"); ok = False
        if s["spa_fraction"] > 0.5 and s["symbolic_speedup_kernel"] < 2.0:
            print(f"FAIL: {fam} SPA-routed but kernel symbolic speedup "
                  f"{s['symbolic_speedup_kernel']}x < 2x"); ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

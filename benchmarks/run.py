"""Benchmark harness: one module per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (plus table sections as
comment/CSV blocks) and writes ``BENCH_kernels.json`` at the repo root —
the machine-readable perf trajectory tracked across PRs.
Usage: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import os
import sys
import traceback

# before JAX starts: four CPU devices for the 4-device sections (the flag
# only affects the CPU backend; on a chip host JAX sees its own devices)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

from . import common  # noqa: E402  (after the device-count flag)


def _four_device_section(mod):
    """A 4-device bench run in this process — the process that holds the
    chips — writing its own BENCH_*.json, or reported as not run."""

    def run() -> None:
        import jax
        n = len(jax.devices())
        if n < mod.SHARDS:
            print(f"not run: {mod.__name__} needs {mod.SHARDS} devices and "
                  f"this process has {n}")
            return
        kept = list(common.RECORDS)    # its records go to its own artifact
        try:
            rc = mod.main([])
        finally:
            common.RECORDS[:] = kept
        if rc:
            raise RuntimeError(f"{mod.__name__} failed its gates (exit {rc})")

    return run


def main() -> None:
    from . import (table2_suite, table3_accuracy, fig2_overhead,
                   kernels_bench, binning_bench, accumulator_bench,
                   roofline_bench, moe_capacity_bench, partition_bench,
                   distributed_bench, comm_bench)
    sections = [
        ("table2 (suite stats)", table2_suite.run),
        ("table3 (625-case accuracy)", table3_accuracy.run),
        ("fig2 (prediction overhead)", fig2_overhead.run),
        ("kernels (pallas microbench)", kernels_bench.run),
        ("binning (binned vs global-pad)", binning_bench.run),
        ("accumulators (spa vs esc routes)", accumulator_bench.run),
        ("roofline (dry-run cells)", roofline_bench.run),
        ("moe capacity (beyond-paper)", moe_capacity_bench.run),
        ("partition (load balance)", partition_bench.run),
        ("distributed (plan/execute vs legacy)",
         _four_device_section(distributed_bench)),
        ("comm (panel-gathered B vs replicated)",
         _four_device_section(comm_bench)),
    ]
    from repro import compile_cache
    compile_cache.configure()
    common.reset_records()
    failed = 0
    for name, fn in sections:
        print(f"\n## {name}")
        try:
            fn()
        except Exception:
            failed += 1
            traceback.print_exc()
    out = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")
    common.write_bench_json(os.path.abspath(out),
                            extra=dict(binning=binning_bench.summary(),
                                       accumulators=accumulator_bench.summary()))
    print(f"\nwrote {os.path.abspath(out)}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()

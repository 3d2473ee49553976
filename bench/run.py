"""SpGEMM service benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the cell's chips.  The
cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json``; what each name means lies in files under ``bench/``
(see ``harness.py``).  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``; last, ``check``: each number compared
beside its limit).  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.

    JAX_PLATFORMS=cpu python3 bench/run.py --workload <cell> --rehearse 4096 ...

rehearses a cell end to end on the CPU with operands of 4096 rows, and
then fails the platform check all the same.  A cell of several chips is
rehearsed on as many virtual CPU devices, set before JAX starts:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
SRC = BENCH.parent / "src"


def fail(msg: str) -> int:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", type=int, default=0, metavar="ROWS",
                   help="CPU rehearsal at this many rows; always fails")
    args = p.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    if not (SRC / "repro").is_dir():
        return fail(f"the system under test is not in this checkout ({SRC})")
    sys.path.insert(0, str(SRC))
    import harness
    bench = harness.benchmark()
    cell, config, traffic = harness.cell_files(bench, args.workload)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro import compile_cache
    cache_dir = compile_cache.configure()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={len(devices)} "
          f"compile cache {cache_dir}", file=sys.stderr, flush=True)
    on_chip = dev.platform == "tpu" and len(devices) >= int(cell["chips"])
    if not on_chip and not args.rehearse:
        return fail(f"cell {args.workload} needs {cell['chips']} TPU chip(s);"
                    f" JAX found {len(devices)} {dev.platform!r} device(s)")
    try:
        result = harness.run_cell(
            args.workload, cell, config, traffic, seed=args.seed,
            seconds=args.seconds, traced=bool(args.trace), t_start=T_START,
            bench=bench, rows=args.rehearse or None)
    except harness.TooFewDevices as e:
        return fail(f"cell {args.workload}: {e}")
    if args.rehearse or not on_chip:
        print(f"rehearsal result: {json.dumps(result)}", file=sys.stderr)
        return fail(f"rehearsal on {dev.platform!r}: not a measurement")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

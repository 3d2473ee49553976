"""The control's readings at a cell's own size.

    python3 bench/control.py --workload m133b3.batch --seeds 11 12 13

The control is the plain reference computed one precision step below what
the configuration states (bfloat16 operands and products, float32 sums,
bfloat16 results; the configuration states float32), put in the program's
place: for each seed it answers every operand of the cell's pools, and the
answers go through the same comparison as a run's (``check.compare``).
Each line gives the numbers compared beside their limits.  It needs no
chip: everything runs on the host.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import ml_dtypes

    import check
    import harness
    import loads
    import reference
    _, config, traffic = harness.cell_files(harness.benchmark(), args.workload)
    sizes = loads.size_rows(config, traffic)
    lim = check.limits(config)
    correct_any = False
    for seed in args.seeds:
        pools = loads.make_pools(config, traffic, seed)
        answers, refs = [], {}
        for s, pool in enumerate(pools):
            for m, a in enumerate(pool):
                refs[(s, m)] = reference.spgemm(a, a, sizes[s])
                answers.append(((s, m), reference.spgemm(
                    a, a, sizes[s], round_to=ml_dtypes.bfloat16)))
        numbers, failed = check.compare(answers, refs, lim["value_rel_err"])
        correct, table = check.verdict(numbers, lim)
        correct_any |= correct
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct, "failed": failed,
                          "answers": len(answers), "check": table}),
              flush=True)
    return 1 if correct_any else 0


if __name__ == "__main__":
    sys.exit(main())

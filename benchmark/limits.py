"""The readings a limit of the comparison is set from, several seeds in one process.

    python -m benchmark.limits --workload <name> --seeds 12 --seconds <s> [--first-seed n] [--rehearse]

One set-up, then for each seed a short window at the cell's own load, the comparison of
its answers with the reference (the sound reading: the largest of each number), and the
control: the reference computed in float32, the nearest precision below the exact
decimals and float64 the configuration states, put in the program's place for the same
statements (its smallest reading has to lie above the limit).  The benchmark's own runs
never run the control.  Prints one JSON line per seed and a summary line last.
"""

import argparse
import json
import sys

import numpy as np

from benchmark import run
from benchmark.harness import compare
from benchmark.harness.loader import BenchmarkError, Cell


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_400_000_000)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        bench = run.Bench(Cell(args.workload), args.rehearse)
    except BenchmarkError as e:
        print(f"benchmark.limits: {e}", file=sys.stderr)
        return 2
    sound, control = [], []
    try:
        bench.setup(args.first_seed)
        tables = None
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            ctx = bench.window(seed, args.seconds, trace=0)
            tables = tables or bench.host_tables()
            checked = bench.sample(ctx.records, seed)
            failed = bench.check(tables, checked)
            s = compare.worst([r["numbers"] for r in checked if r["numbers"]])
            control_failed = bench.check(tables, checked, control_dtype=np.float32)
            c = compare.worst([r["numbers"] for r in checked if r["numbers"]])
            sound.append(s)
            control.append(c)
            run.say(seed=seed, statements=len(ctx.records), compared=len(checked),
                    failed=failed, sound=s, control=c, control_failed=control_failed,
                    control_correct=control_failed == 0)
    finally:
        bench.close()
    summary = {k: {"sound_largest": max(s[k] for s in sound),
                   "control_smallest": min(c[k] for c in control),
                   "limit": compare.LIMITS[k]} for k in compare.LIMITS}
    print(json.dumps({"workload": args.workload, "seeds": args.seeds, "device": bench.device,
                      "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

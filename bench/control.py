"""Readings that the correctness limit of a configuration is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30 \
        --fault alter_token

For each seed, in one process: the cell's set-up and a window of its own
traffic, as in a benchmark run; then the reference over the sample that a
run compares, once for what the program served and once for the control,
the same reference computed in float8 (the family's ``served_gaps``).
Prints, per seed, the widest gap of each (the program's is the lower
reading, the control's the upper) and a JSON summary as the last line.  With
``--fault``, one of ``faults.FAULTS`` breaks the timed path and the
program's gap and ``correct`` are those of the broken path.  Not part of a
benchmark run.
"""

import time

T_START = time.monotonic()

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from bench import faults

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default=None)
    args = ap.parse_args(argv)

    from bench import harness, traffic

    cell = harness.load_cell(args.workload)
    bench = harness.Bench(cell)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        eng = bench.serve(seed)
        tr = traffic.generate(cell.mix, seed=seed, seconds=args.seconds,
                              vocab=bench.dims.vocab, batch=bench.batch)
        fault = faults.FAULTS[args.fault] if args.fault else None
        win = bench.window(eng, tr, args.seconds, fault=fault)
        bench.free(eng)
        del eng
        checks, correct, gaps = harness.check(bench, win, seed, control=True)
        row = {"seed": seed, "fault": args.fault, "correct": correct,
               "program": float(gaps["program"].max()),
               "control": float(gaps["control"].max()),
               "tokens": int(gaps["program"].size),
               "program_p99": float(sorted(gaps["program"])[
                   int(0.99 * (gaps["program"].size - 1))]),
               "counters_zero": all(v["value"] == 0 for k, v in checks.items()
                                    if k != "logit_gap")}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "lower": max(r["program"] for r in rows),
                      "upper": min(r["control"] for r in rows),
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

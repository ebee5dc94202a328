"""Find the knee of an open-loop cell: the highest offered rate it keeps up
with, on every seed.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seeds 1,2,3 \
        --seconds 45 [--overlay 3,3]

One process: the cell's set-up once, then one window per rate and seed
(the cell's mix with ``rate_rps`` replaced, its order drawn from the
seed), each drained before the next.  For each window it prints the
requests due and completed in the window, the backlog (due and not yet
finished) at the middle and at the end of the window, the programs
compiled and the overlay reclaims inside it.  A window keeps pace when its
backlog at the end is no larger than at the middle and all its requests
finished in the drain.  The knee is the highest rate at which every window
keeps pace, at that rate and at every lower one: one lucky seed at a high
rate does not set it.  ``--overlay rows,cols`` serves on another fabric
than the configuration's.  Not part of a benchmark run: its result is
written into the mix file by hand.
"""

import time

T_START = time.monotonic()

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog(win, t: float) -> int:
    obs = win.obs
    n = 0
    for rid, due in obs.due.items():
        if due > t:
            continue
        req, times = obs.reqs[rid], obs.times[rid]
        if not (req.done and times and times[-1] <= t):
            n += 1
    return n


def knee(rows: list[dict]) -> float | None:
    """The highest rate whose windows, and every lower rate's, all keep
    pace."""
    best = None
    for rate in sorted({r["rate_rps"] for r in rows}):
        if not all(r["keeps_pace"] for r in rows if r["rate_rps"] == rate):
            break
        best = rate
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--overlay", default=None,
                    help="rows,cols of the fabric, in place of the config's")
    args = ap.parse_args(argv)

    from bench import harness, traffic

    cell = harness.load_cell(args.workload)
    if args.overlay:
        rows_, cols_ = (int(x) for x in args.overlay.split(","))
        cell.config["overlay"] = dict(cell.config["overlay"], rows=rows_,
                                      cols=cols_)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench = harness.Bench(cell)
    eng = bench.serve(seeds[0])
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            mix = dict(cell.mix, rate_rps=rate)
            tr = traffic.generate(mix, seed=seed, seconds=args.seconds,
                                  vocab=bench.dims.vocab, batch=bench.batch)
            win = bench.window(eng, tr, args.seconds)
            end = win.t0 + args.seconds
            e2e = harness._end_to_end(win.obs, win.t0, win.t_last, 0.0)
            done_in = sum(1 for rid, ts in win.obs.times.items()
                          if win.obs.reqs[rid].done and ts and ts[-1] <= end)
            row = {"rate_rps": rate, "seed": seed, "due": len(win.obs.due),
                   "completed_in_window": done_in,
                   "backlog_mid": backlog(win, win.t0 + args.seconds / 2),
                   "backlog_end": backlog(win, end),
                   "ttft_p90_ms": e2e["ttft_p90_ms"],
                   "itl_p95_ms": e2e["itl_p95_ms"],
                   "out_tokens_per_s": e2e["out_tokens_per_s"],
                   "compiled_in_window": win.compiled,
                   "cache_hits_in_window": win.cache_hits,
                   "reclaims_in_window": win.reclaims,
                   "unfinished_after_drain": sum(
                       1 for r in win.obs.reqs.values() if not r.done)}
            row["keeps_pace"] = (row["backlog_end"] <= row["backlog_mid"]
                                 and not row["unfinished_after_drain"])
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload,
                      "overlay": cell.config["overlay"],
                      "knee_rps": knee(rows), "rows": rows}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Set-up makes
the weights from the seed on the device, warms every program the cell's
traffic uses, then the window serves the traffic for ``--seconds``; after
it, the reference checks a sample of what was served.  The last line of
standard output is the result as one JSON object; with ``--trace 1`` its
metrics are the cell's per-layer metrics, read from a profile of the end
of the window.  Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits non-zero.
"""

import time

T_START = time.monotonic()

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _finite(result: dict) -> dict:
    """JSON has no infinity: a metric that is not finite is left out."""
    result["metrics"] = {k: v for k, v in result["metrics"].items()
                         if math.isfinite(v["value"])}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run(
            harness.RunArgs(args.workload, args.seed, args.seconds,
                            bool(args.trace)), t_start=T_START)
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(_finite(result)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

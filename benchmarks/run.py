"""Run every benchmark; print ``name,us_per_call,derived`` CSV.

``--json [PATH]`` additionally writes the rows as structured JSON (default
``BENCH_<utc-timestamp>.json``) so the per-PR perf trajectory can be
tracked mechanically — each entry is ``{"name", "us_per_call", "derived"}``
plus a run-level header with the timestamp and benchmark module list.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    try:
        value: float | None = float(us)
    except ValueError:
        value = None
    entry: dict = {"name": name, "us_per_call": value, "derived": derived}
    # structured fields: benchmarks emit space-separated k=v tokens in the
    # derived column (e.g. goodput=131.0 ttft_p99_ms=108) — surface them as
    # typed JSON so perf tracking can read them without re-parsing strings
    fields: dict = {}
    for tok in derived.split():
        if "=" not in tok:
            continue
        k, v = tok.split("=", 1)
        try:
            fields[k] = float(v) if "." in v or "e" in v.lower() else int(v)
        except ValueError:
            fields[k] = v
    if fields:
        entry["fields"] = fields
    return entry


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="also write results as JSON (default "
                         "BENCH_<timestamp>.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-size smoke mode: every benchmark's code paths "
                         "execute in seconds (CI rot guard); numbers are "
                         "meaningless")
    args = ap.parse_args(argv)

    from benchmarks import (branch_speculation, chaos_serving,
                            dispatch_overhead, download_pipeline,
                            fig3_vmul_reduce, fleet_serving, isa_mix,
                            pr_overhead, relocation, residency_churn,
                            tile_granularity, warm_restart)
    modules = [fig3_vmul_reduce, pr_overhead, download_pipeline, isa_mix,
               tile_granularity, branch_speculation, residency_churn,
               relocation, dispatch_overhead, fleet_serving, chaos_serving,
               warm_restart]
    print("name,us_per_call,derived")
    rows: list[str] = []
    failed = 0
    for mod in modules:
        try:
            for line in mod.main(smoke=args.smoke):
                print(line)
                rows.append(line)
        except Exception:
            failed += 1
            print(f"{mod.__name__},ERROR,", file=sys.stdout)
            rows.append(f"{mod.__name__},ERROR,")
            traceback.print_exc()

    if args.json is not None:
        path = args.json or time.strftime("BENCH_%Y%m%d_%H%M%S.json",
                                          time.gmtime())
        payload = {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "modules": [m.__name__ for m in modules],
            "failed_modules": failed,
            "results": [_parse_row(r) for r in rows],
        }
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {path}", file=sys.stderr)

    # hard-exit: CPython teardown of lingering daemon threads (scheduler
    # workers, XLA pools) can SIGABRT after all output is done, which would
    # flake the CI bench-smoke gate on a run that actually succeeded
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(1 if failed else 0)


if __name__ == "__main__":
    main()

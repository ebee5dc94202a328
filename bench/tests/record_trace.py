"""Record the profile kept as ``data/tiny_v5e_spans.xplane.pb``: a few ticks
of the tiny configuration served on one chip, each tick inside the
harness's ``bench.tick`` span with the engine wrapped as a traced run wraps
it, and the program's own ``engine.*`` and ``overlay.*`` spans inside.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Needs a TPU: the device operations are the point.
"""

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TICKS = 14
# prompts over one 16-token chunk and under it, so the recorded ticks hold
# prefill chunks, installs and decodes
PROMPTS = (40, 20, 9, 33)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    out = Path(argv[0])

    from bench import harness, trace_reduce
    from repro.serving import Request

    data = ROOT / "bench" / "tests" / "data"
    bench = harness.Bench(harness.load_cell("tiny-open", data, data))
    jax = bench.jax
    eng = bench.serve(seed=1)
    annotate = jax.profiler.TraceAnnotation
    harness._wrap_engine(eng, harness.Observer(eng), annotate)
    for rid, n in enumerate(PROMPTS):
        eng.submit(Request(rid=rid, prompt=[(7 * rid + k) % 500 + 1
                                            for k in range(n)],
                           max_new_tokens=12))
    trace_dir = Path(tempfile.mkdtemp(prefix="record-trace-"))
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        for _ in range(TICKS):
            with annotate("bench.tick"):
                eng.step()
        jax.profiler.stop_trace()
        shutil.copyfile(trace_reduce.find_xplane(trace_dir), out)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"recorded {TICKS} ticks to {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""The program's own spans in a profile, and the window's idle time split
by them.

``bench/trace_reduce.py`` reads the benchmark's ``bench.*`` spans.  The
program records its own on the same clock: ``engine.*``, one per phase of
an engine tick (``serving/engine.py``, ``serving/loop.py``), ``overlay.*``
around a dispatch (``core/overlay.py``), and ``python.gc`` where a caller
marks garbage collections.  This module reads them from the same
``.xplane.pb``, only on the host line that carries the ``bench.tick``
spans (a worker thread's span never claims a gap), and adds:

* the program spans inside the window (the extent of the ``bench.*``
  spans, as ``trace_reduce`` has it);
* the time inside the union of ``engine.step`` spans, and the device busy
  time within it;
* ``idle_by_span``: every idle stretch of the window split by the
  innermost span of either family that covers it, summed per name,
  longest first;
* ``idle_gaps``: the longest idle gaps, each named after the innermost
  span of either family at its middle.

Nothing of ``trace_reduce``'s reduction changes: its ``Raw`` is loaded as
it loads it, and every number it gives stays its own.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import statistics
import sys
import tempfile
from pathlib import Path

from bench import trace_reduce as tr

PREFIXES = ("engine.", "overlay.", "python.gc")
STEP = "engine.step"
DECODE = "engine.decode"
CHUNK = "engine.prefill_chunk"
DISPATCH = "overlay.dispatch"
# where the harness writes a traced run's profile (``tempfile.mkdtemp``)
TRACE_DIR_PREFIX = "bench-trace-"

Span = tuple[float, float, str]


def host_spans(lines: dict[object, list[Span]]) -> list[Span]:
    """Program spans of the host lines (key -> events) that carry a
    ``bench.tick`` span, sorted."""
    out = []
    for events in lines.values():
        if any(name == tr.TICK for _, _, name in events):
            out.extend(e for e in events if e[2].startswith(PREFIXES))
    return sorted(out)


def load_program(path: Path) -> list[Span]:
    """The program's spans in the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    lines = {(plane.name, line.name): [(e.start_ns, e.end_ns, e.name)
                                       for e in line.events]
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines}
    return host_spans(lines)


def extent(spans: list[Span]) -> tuple[float, float]:
    return min(a for a, _, _ in spans), max(b for _, b, _ in spans)


@dataclasses.dataclass
class Program:
    spans: list[Span]                 # program spans inside the window
    step_s: float                     # union of engine.step spans
    step_busy_s: float                # device busy inside that union
    idle_by_span: list[list]          # [[span, idle seconds], ...]
    idle_gaps: list[list]             # [[span, seconds], ...] longest first


def _pieces(spans: list[Span], w0: float, w1: float) -> list[Span]:
    """``[w0, w1]`` cut at every span boundary, each piece named after the
    innermost span that covers it: the one opened last (spans of one
    thread nest), ``"none"`` where none does."""
    spans = sorted(spans)
    cuts = sorted({w0, w1} | {t for a, b, _ in spans for t in (a, b)
                              if w0 < t < w1})
    out, active, j = [], [], 0
    for p, q in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= p:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] > p]
        inner = max(active, key=lambda s: (s[0], -s[1]), default=None)
        out.append((p, q, inner[2] if inner else "none"))
    return out


def reduce_program(raw: tr.Raw, program: list[Span]) -> Program:
    """The program's spans over the window of ``raw`` (``trace_reduce``'s
    ``bench.*`` spans and device operations)."""
    if not raw.spans:
        raise ValueError("the trace holds no bench.* host spans")
    w0, w1 = extent(raw.spans)
    merged = tr.union(raw.ops)
    inside = [(max(a, w0), min(b, w1), n) for a, b, n in program
              if b > w0 and a < w1]
    steps = tr.union(s for s in inside if s[2] == STEP)
    pieces = _pieces(list(raw.spans) + inside, w0, w1)
    idle: dict[str, float] = {}
    for p, q, name in pieces:
        free = (q - p) - tr.covered(merged, p, q)
        if free > 0:
            idle[name] = idle.get(name, 0.0) + free * 1e-9
    starts = [p for p, _, _ in pieces]
    longest = sorted(tr.gaps(merged, w0, w1), key=lambda g: g[0] - g[1])[:10]
    gaps = [[pieces[bisect.bisect_right(starts, (a + b) / 2) - 1][2],
             (b - a) * 1e-9] for a, b in longest]
    return Program(
        spans=inside,
        step_s=sum(b - a for a, b in steps) * 1e-9,
        step_busy_s=sum(tr.covered(merged, a, b) for a, b in steps) * 1e-9,
        idle_by_span=sorted(([n, s] for n, s in idle.items()),
                            key=lambda x: -x[1]),
        idle_gaps=gaps)


def within(inner: list[Span], outer: list[Span]) -> list[Span]:
    """The spans of ``inner`` that lie inside some span of ``outer``."""
    outer = sorted(outer)
    starts = [a for a, _, _ in outer]
    out = []
    for s in inner:
        i = bisect.bisect_right(starts, s[0]) - 1
        if i >= 0 and s[1] <= outer[i][1]:
            out.append(s)
    return out


def named(prog: Program, name: str) -> list[Span]:
    return [s for s in prog.spans if s[2] == name]


def decode_dispatch_us(prog: Program) -> float | None:
    """Median duration of an ``overlay.dispatch`` inside ``engine.decode``,
    in us."""
    inner = within(named(prog, DISPATCH), named(prog, DECODE))
    if not inner:
        return None
    return statistics.median((b - a) * 1e-3 for a, b, _ in inner)


def chunk_share(prog: Program) -> float | None:
    """Share of the window's ``engine.step`` spans that hold an
    ``engine.prefill_chunk``, in %."""
    steps = named(prog, STEP)
    if not steps:
        return None
    starts = [a for a, _, _ in steps]
    hit = {bisect.bisect_right(starts, a) - 1
           for a, _, _ in within(named(prog, CHUNK), steps)}
    return 100.0 * len(hit) / len(steps)


# ---------------------------------------------------------------------------
# the profile of the run being read
# ---------------------------------------------------------------------------
def of_run(ctx) -> Program | None:
    """The program's spans in the profile of the run that ``ctx`` (the
    harness's ``MetricContext``) reads: the profile under the harness's
    trace directory whose ``bench.*`` spans span ``ctx.trace``'s window.
    ``None`` where the run was not traced or the program records no
    ``engine.step`` span."""
    if ctx.trace is None:
        return None
    prog = _reduced(ctx.trace.window_s)
    return prog if prog is not None and named(prog, STEP) else None


@functools.lru_cache(maxsize=1)
def _reduced(window_s: float) -> Program | None:
    root = Path(tempfile.gettempdir())
    dirs = sorted(root.glob(TRACE_DIR_PREFIX + "*"),
                  key=lambda d: d.stat().st_mtime, reverse=True)
    for d in dirs:
        try:
            path = tr.find_xplane(d)
        except FileNotFoundError:
            continue
        raw = tr.load(path)
        if not raw.spans:
            continue
        w0, w1 = extent(raw.spans)
        if (w1 - w0) * 1e-9 != window_s:
            continue
        prog = reduce_program(raw, load_program(path))
        print(f"[bench] program spans: idle by span "
              f"{[[n, round(s, 6)] for n, s in prog.idle_by_span]}; "
              f"idle gaps {[[n, round(s, 6)] for n, s in prog.idle_gaps]}",
              file=sys.stderr, flush=True)
        return prog
    return None

"""From a profiler trace (``.xplane.pb``) to the numbers per-layer metrics read.

* device busy: the union of the intervals in which an operation ran on the
  first TPU, clipped to the traced window (the extent of the benchmark's
  own host spans, ``bench.*``);
* device time per host span: the busy time inside each ``bench.tick``;
* the device operations that took most time, by name;
* idle gaps: the stretches of the window in which no operation ran, each
  named after the innermost ``bench.*`` host span that covers its middle
  (what the host was doing), longest first.

The profiler puts host and device events on one clock; nothing here reads
the program's own clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
SPAN_PREFIX = "bench."
TICK = "bench.tick"


def peaks_for(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclasses.dataclass
class Raw:
    ops: list[tuple[float, float, str]]      # device: (start_ns, end_ns, name)
    spans: list[tuple[float, float, str]]    # host bench.* spans


DEVICE = "/device:TPU:0"


def op_name(hlo: str) -> str:
    """``"%fusion.7 = bf16[4,8192]{1,0:T(4,128)} fusion(...)"`` ->
    ``"fusion.7 bf16[4,8192]"``: the instruction's own name and result
    type, without layouts (the trace names an op by its whole HLO text)."""
    head, _, rest = hlo.partition(" = ")
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])[:60]
    return f"{head.lstrip('%')} {shape}".strip()


def op_of(name: str) -> str:
    """The instruction name of a ``module/op`` name, e.g. ``rmsnorm.15``."""
    return name.rsplit("/", 1)[-1].split(" ", 1)[0]


def load(path: Path) -> Raw:
    """Device operations of the first TPU, each named ``module/op`` after
    the XLA module it ran in, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name == DEVICE and line.name == "XLA Ops":
                ops.extend((e.start_ns, e.end_ns, op_name(e.name))
                           for e in line.events)
            elif plane.name == DEVICE and line.name == "XLA Modules":
                modules.extend((e.start_ns, e.end_ns, e.name.split("(")[0])
                               for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    modules.sort()
    starts = [m[0] for m in modules]
    named = []
    for a, b, name in ops:
        i = bisect.bisect_right(starts, a) - 1
        mod = modules[i][2] if i >= 0 and a < modules[i][1] else "?"
        named.append((a, b, f"{mod}/{name}"))
    named.sort()
    spans.sort()
    return Raw(named, spans)


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b, *_ in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: list[tuple[float, float]], a: float, b: float) -> float:
    """Length of ``[a, b]`` that the merged intervals cover."""
    i = max(0, bisect.bisect_right(merged, (a, float("inf"))) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        lo, hi = max(a, merged[i][0]), min(b, merged[i][1])
        if hi > lo:
            total += hi - lo
        i += 1
    return total


def gaps(merged, a: float, b: float) -> list[tuple[float, float]]:
    out, t = [], a
    for lo, hi in merged:
        if hi <= a:
            continue
        if lo >= b:
            break
        if lo > t:
            out.append((t, lo))
        t = max(t, hi)
    if t < b:
        out.append((t, b))
    return out


def _innermost(spans, t: float) -> str:
    best = None
    for a, b, name in spans:
        if a > t:
            break
        if b >= t and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "none"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    tick_busy_s: list[float]                  # per bench.tick span, in order
    tick_ops: list[dict[str, list[float]]]    # per tick: op name -> durations
    top_ops: list[list]                       # [[name, seconds], ...]
    idle_gaps: list[list]                     # [[host span, seconds], ...]


def reduce_raw(raw: Raw) -> Reduced:
    if not raw.spans:
        raise ValueError("the trace holds no bench.* host spans")
    w0 = min(a for a, _, _ in raw.spans)
    w1 = max(b for _, b, _ in raw.spans)
    merged = union(raw.ops)
    busy = covered(merged, w0, w1)
    ticks = [(a, b) for a, b, n in raw.spans if n == TICK]
    tick_busy = [covered(merged, a, b) * 1e-9 for a, b in ticks]
    tick_ops: list[dict[str, list[float]]] = [{} for _ in ticks]
    starts = [a for a, _ in ticks]
    totals: dict[str, float] = {}
    for a, b, name in raw.ops:
        if b <= w0 or a >= w1:
            continue
        dur = (min(b, w1) - max(a, w0)) * 1e-9
        totals[name] = totals.get(name, 0.0) + dur
        i = bisect.bisect_right(starts, a) - 1
        if 0 <= i < len(ticks) and a < ticks[i][1]:
            tick_ops[i].setdefault(name, []).append((b - a) * 1e-9)
    top = sorted(([n, s] for n, s in totals.items()), key=lambda x: -x[1])
    longest = sorted(gaps(merged, w0, w1), key=lambda g: g[0] - g[1])[:10]
    idle = [[_innermost(raw.spans, (a + b) / 2), (b - a) * 1e-9]
            for a, b in longest]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9,
                   tick_busy_s=tick_busy, tick_ops=tick_ops,
                   top_ops=top[:10], idle_gaps=idle)


def reduce(path: Path) -> Reduced:
    return reduce_raw(load(path))

"""The reduction from a profile to busy time, per-tick device time, top
operations and idle gaps: on hand-made intervals, and on a small trace
recorded on a TPU v5e and kept as a fixture."""

from pathlib import Path

import pytest

from bench import trace_reduce as tr

FIXTURE = Path(__file__).resolve().parent / "data" / "tiny_v5e.xplane.pb"


def test_union_covered_gaps():
    merged = tr.union([(0, 10, "a"), (5, 15, "b"), (20, 30, "c")])
    assert merged == [(0, 15), (20, 30)]
    assert tr.covered(merged, 0, 40) == 25
    assert tr.covered(merged, 12, 22) == 5
    assert tr.gaps(merged, -5, 40) == [(-5, 0), (15, 20), (30, 40)]


def test_reduce_raw_by_hand():
    ms = 1e6
    spans = [(0, 10 * ms, "bench.tick"), (2 * ms, 6 * ms, "bench.decode"),
             (10 * ms, 20 * ms, "bench.wait_arrival"),
             (20 * ms, 30 * ms, "bench.tick")]
    ops = [(1 * ms, 4 * ms, "fusion.1"), (3 * ms, 5 * ms, "rmsnorm"),
           (22 * ms, 29 * ms, "fusion.1")]
    red = tr.reduce_raw(tr.Raw(ops, spans))
    assert red.window_s == pytest.approx(0.030)
    assert red.busy_s == pytest.approx(0.011)
    assert red.tick_busy_s == pytest.approx([0.004, 0.007])
    assert red.tick_ops[0]["rmsnorm"] == pytest.approx([0.002])
    assert red.top_ops[0][0] == "fusion.1"
    assert red.top_ops[0][1] == pytest.approx(0.010)
    # longest idle gap: 5-22 ms, its middle (13.5) inside bench.wait_arrival
    assert red.idle_gaps[0][0] == "bench.wait_arrival"
    assert red.idle_gaps[0][1] == pytest.approx(0.017)
    names = [g[0] for g in red.idle_gaps]
    assert "bench.decode" not in names[:1]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        tr.peaks_for("some other chip")
    assert tr.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_recorded_v5e_trace():
    """A small trace of the harness's spans over a tiny model on a v5e:
    eight ticks, device busy time checked against a brute-force union."""
    raw = tr.load(FIXTURE)
    red = tr.reduce_raw(raw)
    assert len(red.tick_busy_s) == 8
    assert 0 < red.busy_s < red.window_s
    w0 = min(a for a, _, _ in raw.spans)
    w1 = max(b for _, b, _ in raw.spans)
    # brute force: each op's interval clipped to the window, painted on a
    # 10 ns grid
    import numpy as np
    grid = np.zeros(int((w1 - w0) / 10) + 1, bool)
    for a, b, _ in raw.ops:
        lo, hi = max(a, w0), min(b, w1)
        if hi > lo:
            grid[int((lo - w0) / 10):int((hi - w0) / 10)] = True
    assert red.busy_s == pytest.approx(grid.sum() * 10e-9, rel=0.02)
    assert all(s <= t for s, t in zip(red.tick_busy_s, [red.window_s] * 8))
    ops = {tr.op_of(n) for _, _, n in raw.ops}
    assert any(o.startswith("rmsnorm") for o in ops)
    assert all("/" in n for _, _, n in raw.ops)
    assert {g[0] for g in red.idle_gaps} <= {"bench.tick", "bench.decode",
                                             "bench.prefill_chunk", "none"}

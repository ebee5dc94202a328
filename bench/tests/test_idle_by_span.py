"""The program's spans in a profile: read on the host line of the
benchmark's ticks, over the benchmark's window, with the window's idle time
split by the innermost span; on hand-made intervals, on two small traces
recorded on a TPU v5e, and through a traced run of the harness."""

import dataclasses
import shutil
import tempfile
import time

import pytest

from bench import harness
from bench import program_spans as ps
from bench import trace_reduce as tr
from bench.metrics import decode_dispatch_us, prefill_chunk_share, tick_idle
from conftest import DATA

BENCH_ONLY = DATA / "tiny_v5e.xplane.pb"        # the harness's spans only
WITH_PROGRAM = DATA / "tiny_v5e_spans.xplane.pb"
MS = 1e6


def test_idle_goes_to_the_innermost_program_span():
    spans = [(0, 10 * MS, "bench.tick")]
    program = [(0.5 * MS, 9.5 * MS, "engine.step"),
               (0.8 * MS, 4 * MS, "engine.decode"),
               (1.5 * MS, 3.5 * MS, "overlay.dispatch"),
               (6 * MS, 9 * MS, "engine.retire")]
    ops = [(2 * MS, 3 * MS, "fusion.1"), (4 * MS, 5 * MS, "fusion.2")]
    prog = ps.reduce_program(tr.Raw(ops, spans), program)
    assert dict(prog.idle_by_span) == pytest.approx({
        "bench.tick": 0.001, "engine.step": 0.0018, "engine.decode": 0.0012,
        "overlay.dispatch": 0.001, "engine.retire": 0.003})
    assert prog.idle_by_span[0][0] == "engine.retire"
    assert prog.step_s == pytest.approx(0.009)
    assert prog.step_busy_s == pytest.approx(0.002)
    # gaps 5-10, 0-2 and 3-4 ms; middles at 7.5, 1 and 3.5 ms
    assert prog.idle_gaps == [["engine.retire", pytest.approx(0.005)],
                              ["engine.decode", pytest.approx(0.002)],
                              ["engine.decode", pytest.approx(0.001)]]


def test_python_gc_wins_over_engine_step():
    spans = [(0, 10 * MS, "bench.tick")]
    program = [(0.5 * MS, 9.5 * MS, "engine.step"),
               (3 * MS, 7 * MS, "python.gc")]
    prog = ps.reduce_program(tr.Raw([], spans), program)
    assert dict(prog.idle_by_span) == pytest.approx({
        "bench.tick": 0.001, "engine.step": 0.005, "python.gc": 0.004})
    # a collection inside the launch of an executable wins over it
    program = [(0.5 * MS, 9.5 * MS, "engine.step"),
               (4 * MS, 8 * MS, "overlay.execute"),
               (4.5 * MS, 5.5 * MS, "python.gc")]
    prog = ps.reduce_program(tr.Raw([], spans), program)
    assert prog.idle_gaps == [["python.gc", pytest.approx(0.010)]]
    assert dict(prog.idle_by_span) == pytest.approx({
        "bench.tick": 0.001, "engine.step": 0.005, "overlay.execute": 0.003,
        "python.gc": 0.001})


def test_a_span_on_another_thread_claims_nothing():
    main = [(0, 10 * MS, "bench.tick"), (1 * MS, 9 * MS, "engine.step")]
    worker = [(0, 10 * MS, "overlay.dispatch"), (2 * MS, 3 * MS, "python.gc")]
    program = ps.host_spans({("/host:CPU", "main"): main,
                             ("/host:CPU", "worker"): worker})
    assert program == [(1 * MS, 9 * MS, "engine.step")]
    prog = ps.reduce_program(tr.Raw([], main[:1]), program)
    assert dict(prog.idle_by_span) == {"bench.tick": pytest.approx(0.002),
                                       "engine.step": pytest.approx(0.008)}


def test_program_spans_leave_the_harness_reduction_alone():
    spans = [(0, 10 * MS, "bench.tick"), (10 * MS, 20 * MS, "bench.tick")]
    ops = [(1 * MS, 4 * MS, "m/fusion.1"), (12 * MS, 19 * MS, "m/rmsnorm.1")]
    raw = tr.Raw(list(ops), list(spans))
    before = tr.reduce_raw(raw)
    prog = ps.reduce_program(raw, [(0.5 * MS, 9 * MS, "engine.step"),
                                   (11 * MS, 25 * MS, "engine.step")])
    assert tr.reduce_raw(raw) == before
    assert (raw.ops, raw.spans) == (ops, spans)
    # the window is the harness's: a program span past it is clipped
    assert prog.spans[-1][1] == 20 * MS
    assert sum(s for _, s in prog.idle_by_span) == pytest.approx(
        before.window_s - before.busy_s)


@pytest.mark.parametrize("fixture", [BENCH_ONLY, WITH_PROGRAM])
def test_recorded_traces_keep_the_harness_numbers(fixture):
    raw = tr.load(fixture)
    red = tr.reduce_raw(raw)
    prog = ps.reduce_program(raw, ps.load_program(fixture))
    assert tr.reduce_raw(tr.load(fixture)) == red
    idle = red.window_s - red.busy_s
    assert sum(s for _, s in prog.idle_by_span) == pytest.approx(idle,
                                                                 rel=0.01)
    assert prog.step_busy_s <= red.busy_s


def test_recorded_trace_names_gaps_and_ops_by_program():
    raw = tr.load(WITH_PROGRAM)
    prog = ps.reduce_program(raw, ps.load_program(WITH_PROGRAM))
    names = {n for _, _, n in prog.spans}
    assert {"engine.step", "engine.admit", "engine.prefill_chunk",
            "engine.install_stripe", "engine.decode", "engine.sample",
            "engine.device_get", "engine.retire", "overlay.dispatch",
            "overlay.execute"} <= names
    assert "overlay.fallback" not in names
    assert all(n.startswith(ps.PREFIXES) for n, _ in prog.idle_gaps)
    bare = sum(s for n, s in prog.idle_by_span
               if n in ("engine.step", "bench.tick", "none"))
    assert bare < sum(s for _, s in prog.idle_by_span) / 3
    modules = {n.split("/", 1)[0] for _, _, n in raw.ops}
    assert "jit_kernel" not in modules
    assert {"jit_phi3-mini-3.8b.decode",
            "jit_phi3-mini-3.8b.prefill_chunk"} <= modules
    assert 0 < 100 * (1 - prog.step_busy_s / prog.step_s) < 100
    assert ps.decode_dispatch_us(prog) > 0
    assert 0 < ps.chunk_share(prog) <= 100


def _ctx(trace=None):
    return harness.MetricContext(family=None, dims=None, config={}, peaks={},
                                 obs=None, trace=trace, traced_ticks=[])


def _program(spans, step_s=0.0, step_busy_s=0.0):
    return ps.Program(spans=sorted(spans), step_s=step_s,
                      step_busy_s=step_busy_s, idle_by_span=[], idle_gaps=[])


def test_tick_idle_reader(monkeypatch):
    monkeypatch.setattr(ps, "of_run", lambda ctx: _program([], 0.1, 0.09))
    assert tick_idle.read(_ctx()) == pytest.approx(10.0)
    monkeypatch.setattr(ps, "of_run", lambda ctx: None)
    assert tick_idle.read(_ctx()) is None


def test_decode_dispatch_us_reader(monkeypatch):
    spans = [(0, 1 * MS, "engine.decode"), (0.1 * MS, 0.55 * MS,
                                            "overlay.dispatch"),
             (2 * MS, 3 * MS, "engine.decode"), (2.1 * MS, 2.6 * MS,
                                                 "overlay.dispatch"),
             (4 * MS, 5 * MS, "engine.decode"), (4.1 * MS, 4.5 * MS,
                                                 "overlay.dispatch"),
             # a prefill chunk's dispatch is not the decode's
             (6 * MS, 9 * MS, "engine.prefill_chunk"),
             (6.1 * MS, 8.9 * MS, "overlay.dispatch")]
    monkeypatch.setattr(ps, "of_run", lambda ctx: _program(spans))
    assert decode_dispatch_us.read(_ctx()) == pytest.approx(450.0)
    monkeypatch.setattr(ps, "of_run", lambda ctx: _program(spans[-2:]))
    assert decode_dispatch_us.read(_ctx()) is None


def test_prefill_chunk_share_reader(monkeypatch):
    spans = [(k * 10 * MS, (k * 10 + 9) * MS, "engine.step")
             for k in range(4)]
    spans.append((11 * MS, 15 * MS, "engine.prefill_chunk"))
    monkeypatch.setattr(ps, "of_run", lambda ctx: _program(spans))
    assert prefill_chunk_share.read(_ctx()) == pytest.approx(25.0)
    monkeypatch.setattr(ps, "of_run", lambda ctx: None)
    assert prefill_chunk_share.read(_ctx()) is None


def test_of_run_finds_the_runs_own_profile(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    for name, src in (("bench-trace-a", BENCH_ONLY),
                      ("bench-trace-b", WITH_PROGRAM)):
        d = tmp_path / name / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copyfile(src, d / "host.xplane.pb")
    ps._reduced.cache_clear()
    try:
        assert ps.of_run(_ctx()) is None
        prog = ps.of_run(_ctx(tr.reduce(WITH_PROGRAM)))
        want = ps.reduce_program(tr.load(WITH_PROGRAM),
                                 ps.load_program(WITH_PROGRAM))
        assert prog == want
        # a run whose program records no span (an older program) reads
        # nothing
        assert ps.of_run(_ctx(tr.reduce(BENCH_ONLY))) is None
        assert tick_idle.read(_ctx(tr.reduce(BENCH_ONLY))) is None
    finally:
        ps._reduced.cache_clear()


NEW_METRICS = [
    {"name": "tick_idle.closed", "unit": "%"},
    {"name": "decode_dispatch_us.closed", "unit": "us"},
    {"name": "prefill_chunk_share.closed", "unit": "%"},
]


def test_traced_run_reports_the_program_metrics(monkeypatch):
    """A traced run of the tiny cell on the CPU: the readers find the
    run's profile and its program spans (the CPU has no device ops, so
    every tick is idle)."""
    load = harness.load_cell

    def with_new_metrics(name, root, data):
        cell = load(name, root, data)
        bm = dict(cell.benchmark)
        bm["per_layer"] = bm["per_layer"] + [
            dict(m, workloads=[name]) for m in NEW_METRICS]
        return dataclasses.replace(cell, benchmark=bm)

    monkeypatch.setattr(harness, "load_cell", with_new_metrics)
    monkeypatch.setattr(tr, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    ps._reduced.cache_clear()
    try:
        res = harness.run(harness.RunArgs("tiny-closed", 2**31 + 29, 1.5,
                                          True),
                          t_start=time.monotonic(), require_chip=False,
                          root=DATA, data=DATA)
    finally:
        ps._reduced.cache_clear()
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["tick_idle.closed"] == pytest.approx(100.0)
    assert m["decode_dispatch_us.closed"] > 0
    assert 0 < m["prefill_chunk_share.closed"] < 100
    assert res["correct"]

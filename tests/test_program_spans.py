"""The program's own profiler spans and executable names.

A tick of the serving engine and a call of an assembled accelerator each
record named ``TraceAnnotation`` spans (``engine.*``, ``overlay.*``) on
the profiler's clock, so a profile can put every idle stretch of the
device down to a phase of the program; and each executable is named after
the jitted function it runs, so its device ops say which one ran.
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.archs import smoke_config
from repro.core import Overlay
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Request

CFG = smoke_config("phi3-mini-3.8b")
PARAMS = pm.init(model_spec(CFG), jax.random.PRNGKey(0))
PREFIXES = ("engine.", "overlay.")


def _spans(trace_dir) -> list[tuple[float, float, str]]:
    """Every program span the profile in ``trace_dir`` holds, sorted."""
    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return sorted((e.start_ns, e.end_ns, e.name)
                  for plane in data.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith(PREFIXES))


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _inside(spans, outer, name):
    a, b, _ = outer
    return [s for s in _named(spans, name) if a <= s[0] and s[1] <= b]


def _warm_event_loop():
    ov = Overlay(6, 6)
    eng = EventLoopEngine(PARAMS, CFG, batch=2, max_len=32, chunk=4,
                          overlay=ov, tile_budget=2)
    eng.warmup((6, 3))
    for rid, n in enumerate((6, 3)):
        eng.submit(Request(rid=-1 - rid, prompt=list(range(1, n + 1)),
                           max_new_tokens=2))
    eng.run_until_drained()
    return ov, eng


def test_event_loop_tick_span_tree(tmp_path):
    ov, eng = _warm_event_loop()
    eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5, 9], max_new_tokens=3))
    eng.submit(Request(rid=1, prompt=[2, 7, 1], max_new_tokens=4))
    ticks0, chunks0 = eng.ticks, eng.prefill_ticks
    steps = 0
    with jax.profiler.trace(str(tmp_path)):
        while eng.queue or any(r is not None for r in eng.slot_req):
            eng.step()
            steps += 1
    spans = _spans(tmp_path)
    ov.close()

    assert len(_named(spans, "engine.step")) == steps
    assert eng.ticks - ticks0 == steps
    # prompts of 6 and 3 tokens at chunk 4: chunks 4+2 and 4 (padded)
    assert eng.prefill_ticks - chunks0 == len(
        _named(spans, "engine.prefill_chunk")) == 3
    assert len(_named(spans, "engine.install_stripe")) == 2
    for step in _named(spans, "engine.step"):
        assert len(_inside(spans, step, "engine.admit")) == 1
    for phase in ("engine.decode", "engine.prefill_chunk"):
        assert _named(spans, phase)
        for outer in _named(spans, phase):
            (disp,) = _inside(spans, outer, "overlay.dispatch")
            assert len(_inside(spans, disp, "overlay.execute")) == 1
    for phase in ("engine.sample", "engine.device_get", "engine.retire"):
        assert len(_named(spans, phase)) == len(_named(spans,
                                                       "engine.decode"))
    assert not _named(spans, "overlay.fallback")
    assert not _named(spans, "overlay.slow_path")


def test_event_loop_metrics_count_ticks():
    ov, eng = _warm_event_loop()
    m0 = eng.metrics()
    eng.submit(Request(rid=0, prompt=list(range(1, 10)), max_new_tokens=2))
    eng.run_until_drained()
    m1 = eng.metrics()
    ov.close()
    # 9 tokens at chunk 4: chunks of 4, 4 and 1; the tick of the last one
    # installs the stripe and decodes once, and one more tick decodes
    assert m1["prefill_ticks"] - m0["prefill_ticks"] == 3
    assert m1["ticks"] - m0["ticks"] == 4


def test_serve_engine_tick_spans(tmp_path):
    eng = EventLoopEngine(PARAMS, CFG, batch=2, max_len=32)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    eng.step()                      # compiles outside the profile
    eng.submit(Request(rid=1, prompt=[4, 5, 6], max_new_tokens=2))
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_drained()
    spans = _spans(tmp_path)
    steps = _named(spans, "engine.step")
    assert len(steps) == 2
    # the first tick admits request 1, prefills its one chunk, installs
    # its stripe and decodes both slots
    assert len(_inside(spans, steps[0], "engine.admit")) == 1
    assert len(_inside(spans, steps[0], "engine.prefill_chunk")) == 1
    assert len(_inside(spans, steps[0], "engine.install_stripe")) == 1
    for phase in ("engine.decode", "engine.sample", "engine.device_get",
                  "engine.retire"):
        assert len(_named(spans, phase)) == 2
    assert not any(n.startswith("overlay.") for _, _, n in spans)


def test_first_call_of_an_async_overlay_is_a_fallback_span(tmp_path):
    ov = Overlay(3, 3, async_downloads=True)
    try:
        f = ov.jit(lambda x: jnp.tanh(x) * 2.0 + 1.0, name="spans.fallback")
        x = jnp.arange(8.0)
        with jax.profiler.trace(str(tmp_path)):
            f(x)
        ov.drain()
    finally:
        ov.close()
    spans = _spans(tmp_path)
    (disp,) = _named(spans, "overlay.dispatch")
    (slow,) = _inside(spans, disp, "overlay.slow_path")
    assert len(_inside(spans, slow, "overlay.fallback")) == 1
    assert not _named(spans, "overlay.execute")


def _hlo_module(exe) -> str:
    return exe.as_text().split(",", 1)[0].removeprefix("HloModule ")


def test_executables_carry_their_jitted_names():
    ov, eng = _warm_event_loop()
    try:
        names = {}
        for jitted in (eng._decode, eng._prefill_chunk):
            for entry in jitted._entries.values():
                names.setdefault(jitted.name, set()).add(
                    _hlo_module(entry.acc.fn.func))
        assert names == {
            f"{CFG.name}.decode": {f"jit_{CFG.name}.decode"},
            f"{CFG.name}.prefill_chunk": {f"jit_{CFG.name}.prefill_chunk"},
        }
    finally:
        ov.close()


def test_specialized_executable_names_its_tier():
    ov = Overlay(3, 3)
    try:
        f = ov.jit(lambda x: jnp.tanh(x) * 2.0 + 1.0, name="spans.tier")
        x = jnp.arange(8.0)
        f(x)
        f.specialize(x)
        f(x)
        (entry,) = f._entries.values()
        rec = entry.record
        assert rec.tier == "specialized"
        exe = rec.fn.func.lower(*rec.fn.args, x).compile()
    finally:
        ov.close()
    assert _hlo_module(exe) == "jit_spans.tier.specialized"

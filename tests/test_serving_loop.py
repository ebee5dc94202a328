"""Event-loop serving engine, serving metrics, and dispatch histograms.

Covers the serving-under-load path (DESIGN.md §9): batched host I/O (one
device->host transfer per decode tick), ragged co-resident decode, chunked
power-of-two-bucketed prefill, SLO-aware admission/shedding, and the
dispatch-latency/route-cost histograms exported by the overlay, fabric and
fleet describe() surfaces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import smoke_config
from repro.core import FleetOverlay, Overlay
from repro.models import model as mdl
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Histogram, Request

CFG = smoke_config("phi3-mini-3.8b")
PARAMS = pm.init(model_spec(CFG), jax.random.PRNGKey(0))


def _reference_decode(prompt: list[int], max_new: int,
                      max_len: int = 32) -> list[int]:
    """Scalar-path batch-1 greedy decode — the ground truth every engine
    configuration must reproduce bit-exactly."""
    caches = mdl.init_cache(CFG, 1, max_len)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, caches = mdl.prefill(PARAMS, CFG, toks, caches)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(max_new):
        logits, caches = mdl.decode_step(
            PARAMS, CFG, jnp.asarray([[out[-1]]], jnp.int32), caches)
        out.append(int(jnp.argmax(logits[0])))
    return out


# ---------------------------------------------------------------------------
# batched host I/O
# ---------------------------------------------------------------------------
def test_decode_tick_performs_one_host_transfer(monkeypatch):
    """Regression: the decode tick used to read tokens/positions back with
    per-slot ``int(...)`` syncs (2 x batch device->host round-trips per
    tick).  The fused path must issue exactly ONE ``jax.device_get`` per
    tick, independent of batch size."""
    engine = EventLoopEngine(PARAMS, CFG, batch=3, max_len=32)
    for rid in range(3):
        engine.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                              max_new_tokens=8))
    for _ in range(3):                # admissions, one prefill chunk a tick
        engine.step()
    assert not engine._prefilling

    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    for _ in range(3):                # pure decode ticks, all slots live
        engine.step()
    assert len(calls) == 3            # one transfer per tick, not per slot


# ---------------------------------------------------------------------------
# ragged co-resident decode
# ---------------------------------------------------------------------------
def test_ragged_prompt_lengths_decode_at_correct_positions():
    """Regression: co-resident slots admitted with different prompt lengths
    must each decode against their own KV extent.  A shared scalar cache
    index made every slot decode at the longest prompt's position — short
    prompts attended to garbage KV entries."""
    prompts = [[1, 2, 3], list(range(1, 10))]          # lengths 3 and 9
    engine = EventLoopEngine(PARAMS, CFG, batch=2, max_len=32)
    for rid, p in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=p, max_new_tokens=4))
    done = {r.rid: r for r in engine.run_until_drained()}
    for rid, p in enumerate(prompts):
        assert done[rid].out == _reference_decode(p, 4), \
            f"slot with prompt length {len(p)} diverged"


# ---------------------------------------------------------------------------
# event loop: bit-identity, bucketing, fairness, shedding
# ---------------------------------------------------------------------------
def test_event_loop_matches_sync_engine_bit_exact():
    """Chunked bucketed prefill + interleaved decode must not change a
    single token: padded chunk positions are causally masked and then
    overwritten by decode before any query reaches them."""
    prompts = [[7] * 5, [3] * 2, list(range(1, 10)), [11] * 13, [5]]
    loop = EventLoopEngine(PARAMS, CFG, batch=2, max_len=32, chunk=4)
    for rid, p in enumerate(prompts):
        loop.submit(Request(rid=rid, prompt=list(p), max_new_tokens=3))
    got = {r.rid: r.out for r in loop.run_until_drained()}
    assert got == {rid: _reference_decode(p, 3)
                   for rid, p in enumerate(prompts)}


def test_event_loop_prefill_chunk_sizes_bounded_by_bucket_set():
    """Prompts of many distinct lengths must reach the prefill kernel in
    power-of-two chunk sizes only — the signature set the overlay compiles
    is {1, 2, ..., chunk}, not one entry per prompt length."""
    engine = EventLoopEngine(PARAMS, CFG, batch=2, max_len=32, chunk=4)
    sizes = set()
    inner = engine._prefill_chunk

    def recording(params, toks, c, last):
        sizes.add(toks.shape[1])
        return inner(params, toks, c, last)

    engine._prefill_chunk = recording
    for rid, n in enumerate([1, 2, 3, 5, 6, 7, 9, 12, 13]):
        engine.submit(Request(rid=rid, prompt=list(range(1, n + 1)),
                              max_new_tokens=2))
    engine.run_until_drained()
    assert sizes <= {1, 2, 4}                  # bucket set for chunk=4
    assert 4 in sizes                          # long prompts use full chunks


@pytest.mark.parametrize("max_len,chunk,n", [(22, 16, 21), (20, 64, 17)])
def test_event_loop_last_chunk_stays_inside_the_cache(max_len, chunk, n):
    """Where ``max_len`` is not a multiple of ``chunk``, the padded last
    chunk (8 at offset 16; 32 at offset 0) would pass the cache's end: it
    is cut to the room left, so the prompt's K/V lands where a whole-prompt
    prefill puts it."""
    prompt = [(7 * k) % 50 + 1 for k in range(n)]
    engine = EventLoopEngine(PARAMS, CFG, batch=1, max_len=max_len,
                             chunk=chunk)
    engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
    (done,) = engine.run_until_drained()
    assert done.out == _reference_decode(prompt, done.decode_steps,
                                         max_len=max_len)
    _, whole = mdl.prefill(PARAMS, CFG, jnp.asarray(prompt, jnp.int32)[None],
                           mdl.init_cache(CFG, 1, max_len))
    g, w = engine.caches["g0"]["0:dense"], whole["g0"]["0:dense"]
    for name in ("k", "v"):               # (layer, batch, head, seq, dim)
        np.testing.assert_array_equal(np.asarray(g[name])[..., :n, :],
                                      np.asarray(w[name])[..., :n, :])


def test_event_loop_warmup_downloads_every_accelerator_traffic_uses():
    """``warmup(prompt_lens)`` downloads the decode step plus the prefill
    chunk of every bucket those prompts use (12 -> 16; 40 -> 16, 16, 8), so
    serving them starts no download and touches no fallback."""
    ov = Overlay(3, 3)
    engine = EventLoopEngine(PARAMS, CFG, batch=2, max_len=64, chunk=16,
                             overlay=ov)
    engine.warmup((12, 40))
    assert ov.stats.downloads == 3             # decode + chunks {16, 8}
    for rid, n in enumerate((12, 40)):
        engine.submit(Request(rid=rid, prompt=list(range(1, n + 1)),
                              max_new_tokens=3))
    done = engine.run_until_drained()
    assert len(done) == 2
    assert ov.stats.downloads == 3
    assert ov.stats.fallback_calls == 0
    assert ov.stats.prefetch_hits == 3


def test_event_loop_fifo_and_recycling_under_oversubscription():
    """Sustained oversubscription through one slot: every request finishes
    (slot recycling) in submit order (FIFO within a priority class)."""
    engine = EventLoopEngine(PARAMS, CFG, batch=1, max_len=32, chunk=4)
    for rid in range(6):
        assert engine.submit(Request(rid=rid, prompt=[rid + 1, 2, 3],
                                     max_new_tokens=2))
    done = engine.run_until_drained()
    assert [r.rid for r in done] == list(range(6))
    assert not engine.shed


def test_event_loop_priority_classes_order_admission():
    engine = EventLoopEngine(PARAMS, CFG, batch=1, max_len=32, chunk=4)
    engine.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    engine.step()                              # rid 0 occupies the slot
    engine.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=2,
                          priority=5))
    engine.submit(Request(rid=2, prompt=[5, 6], max_new_tokens=2,
                          priority=0))
    done = engine.run_until_drained()
    assert [r.rid for r in done] == [0, 2, 1]  # low priority value first


def test_event_loop_sheds_on_queue_depth_and_reports():
    """Oversubmission beyond max_queue is shed at the API boundary with a
    reason — never silently dropped."""
    engine = EventLoopEngine(PARAMS, CFG, batch=1, max_len=32, chunk=4,
                             max_queue=2)
    results = [engine.submit(Request(rid=rid, prompt=[rid + 1, 2],
                                     max_new_tokens=2))
               for rid in range(5)]
    # slot empty until the first step: all 5 land in the queue bound of 2
    assert results == [True, True, False, False, False]
    assert [r.rid for r in engine.shed] == [2, 3, 4]
    assert all(r.shed and r.shed_reason == "queue_full" for r in engine.shed)
    done = engine.run_until_drained()
    finished = {r.rid for r in done}
    assert finished == {0, 1}
    assert finished | {r.rid for r in engine.shed} == set(range(5))
    assert engine.metrics()["shed_reasons"] == {"queue_full": 3}


def test_event_loop_sheds_expired_requests_with_fake_clock():
    """A request that outlives max_queue_delay while queued is shed at
    admission time instead of burning prefill on a timed-out client."""
    now = [0.0]
    engine = EventLoopEngine(PARAMS, CFG, batch=1, max_len=32, chunk=4,
                             max_queue_delay=0.5, clock=lambda: now[0])
    engine.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    engine.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=2))
    engine.step()                              # rid 0 admitted at t=0
    now[0] = 2.0                               # rid 1 exceeds its budget
    done = engine.run_until_drained()
    assert [r.rid for r in done] == [0]
    assert [(r.rid, r.shed_reason) for r in engine.shed] == \
        [(1, "queue_delay")]


def test_event_loop_sheds_on_predicted_delay():
    now = [0.0]
    engine = EventLoopEngine(PARAMS, CFG, batch=1, max_len=32, chunk=4,
                             max_queue_delay=0.5, clock=lambda: now[0])
    engine.tick_hist.record(2_000_000)         # measured ticks of 2s
    assert not engine.submit(Request(rid=0, prompt=[1, 2],
                                     max_new_tokens=2))
    assert engine.shed[0].shed_reason == "predicted_delay"


# ---------------------------------------------------------------------------
# serving metrics
# ---------------------------------------------------------------------------
def test_histogram_records_percentiles_and_summary():
    h = Histogram()
    assert h.percentile(0.5) == 0.0 and h.summary()["count"] == 0
    for v in [10, 20, 30, 1000]:
        h.record(v)
    assert h.count == 4
    assert h.mean() == 265.0
    # bucket upper bounds: monotone in q, >= the true value, clamped to max
    assert h.percentile(0.5) >= 20
    assert h.percentile(0.99) <= h.percentile(1.0) == 1000
    s = h.summary()
    assert set(s) == {"count", "mean", "p50", "p99", "max"}
    assert s["max"] == 1000


def test_histogram_clamps_percentile_to_observed_max():
    h = Histogram()
    h.record(1000)                             # bucket upper bound is 1023
    assert h.percentile(0.99) == 1000


# ---------------------------------------------------------------------------
# dispatch-latency / route-cost observability
# ---------------------------------------------------------------------------
def test_overlay_and_fabric_describe_dispatch_histograms():
    ov = Overlay(3, 3)
    fn = ov.jit(lambda x: x * 2.0 + 1.0, name="obs")
    x = jnp.arange(8, dtype=jnp.float32)
    fn(x)
    fn(x)
    d = ov.describe()
    assert d["dispatch_latency"]["count"] >= 2
    assert d["route_cost"]["count"] >= 1       # recorded at route binding
    res = list(d["fabric"]["residents"].values())
    assert all("route_cost" in r and "dispatch_latency" in r for r in res)
    assert any(r["dispatch_latency"]["count"] >= 2 for r in res)
    ov.close()


def test_fleet_describe_and_latency_aware_score():
    fleet = FleetOverlay(2, rows=3, cols=3)
    # cold fleet: no dispatches recorded -> latency term contributes 0
    cold = [fleet._member_score(i) for i in range(2)]
    assert cold[0] == cold[1]
    # member 0 measures slow dispatches, member 1 fast ones: the score must
    # deprioritize the slow member for new placements
    for _ in range(8):
        fleet.members[0].dispatch_hist.record(100_000)
        fleet.members[1].dispatch_hist.record(10)
    assert fleet._member_score(0) < fleet._member_score(1)
    d = fleet.describe()
    assert len(d["fleet"]["dispatch_p50_us"]) == 2
    assert d["fleet"]["dispatch_p50_us"][0] > d["fleet"]["dispatch_p50_us"][1]
    assert len(d["fleet"]["dispatch_p99_us"]) == 2
    fleet.close()

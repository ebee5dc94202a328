"""Async PR-download pipeline tests: fallback-then-swap semantics, prefetch
hit accounting, cost-aware reclaim, generation-guarded commits (an evicted
resident must stay evicted), and the deterministic synchronous mode."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import Overlay, PlacementPolicy, saxpy_graph
from repro.core.scheduler import DownloadScheduler


def _gate_downloads(ov):
    """Block the overlay's background compiles until the gate is set."""
    gate = threading.Event()
    orig = ov._compile_bitstream

    def gated(pending):
        gate.wait(30)
        return orig(pending)

    ov._compile_bitstream = gated
    return gate


# ---------------------------------------------------------------------------
# DownloadScheduler mechanics
# ---------------------------------------------------------------------------
def test_scheduler_runs_work_then_commit():
    s = DownloadScheduler()
    seen = []
    h = s.submit("k", lambda: 21, lambda r, dt: r * 2, on_done=lambda r, h: seen.append(r))
    assert s.drain(10)
    assert h.wait(10) and h.result == 42
    assert seen == [42]
    assert s.stats.completed == 1


def test_scheduler_coalesces_same_key():
    s = DownloadScheduler()
    gate = threading.Event()
    results = []
    s.submit("k", lambda: (gate.wait(10), "bits")[1], lambda r, dt: r,
             on_done=lambda r, h: results.append(r))
    s.submit("k", lambda: "never-runs", lambda r, dt: "never-commits",
             on_done=lambda r, h: results.append(r))
    assert s.stats.coalesced == 1 and s.stats.submitted == 1
    gate.set()
    assert s.drain(10)
    assert results == ["bits", "bits"]       # both observers, one download


def test_scheduler_cancel_queued_job_never_runs():
    s = DownloadScheduler(workers=1)
    gate = threading.Event()
    s.submit("a", lambda: gate.wait(10), lambda r, dt: r)
    observed = []
    s.submit("b", lambda: "ran", lambda r, dt: r, on_done=lambda r, h: observed.append(r))
    assert s.cancel("b")                      # still queued behind "a"
    gate.set()
    assert s.drain(10)
    assert observed == [None]
    assert s.stats.cancelled == 1


def test_scheduler_flush_stales_running_job():
    s = DownloadScheduler()
    gate = threading.Event()
    started = threading.Event()
    observed = []
    s.submit("k", lambda: (started.set(), gate.wait(10), "bits")[2],
             lambda r, dt: r, on_done=lambda r, h: observed.append(r))
    assert started.wait(10)                   # worker has the job RUNNING
    s.flush()
    gate.set()
    assert s.drain(10)
    assert observed == [None]                 # commit was forfeited
    assert s.stats.dropped_stale == 1 and s.stats.completed == 0


def test_low_lane_never_delays_normal_downloads():
    # the route-specialization invariant: with the single worker pinned by a
    # running download, a queued LOW job must yield to every download that
    # arrives after it — a pending download is never delayed by a
    # specialization
    s = DownloadScheduler(workers=1)
    gate = threading.Event()
    order = []

    def committer(name):
        return lambda r, dt: (order.append(name), name)[1]

    s.submit("A", lambda: gate.wait(10), committer("A"))
    s.submit("spec", lambda: "bits", committer("spec"), low=True)
    s.submit("B", lambda: "b", committer("B"))
    s.submit("C", lambda: "c", committer("C"))
    assert s.stats.low_jobs == 1 and s.stats.submitted == 4
    gate.set()
    assert s.drain(10)
    assert order == ["A", "B", "C", "spec"]


def test_priority_and_low_are_mutually_exclusive():
    s = DownloadScheduler()
    with pytest.raises(ValueError):
        s.submit("k", lambda: 1, lambda r, dt: r, priority=True, low=True)


def test_cancel_dequeues_low_lane_job():
    s = DownloadScheduler(workers=1)
    gate = threading.Event()
    s.submit("A", lambda: gate.wait(10), lambda r, dt: r)
    observed = []
    s.submit("spec", lambda: "never-runs", lambda r, dt: "never",
             on_done=lambda r, h: observed.append(r), low=True)
    assert s.cancel("spec")
    gate.set()
    assert s.drain(10)
    assert observed == [None]
    assert s.stats.cancelled == 1


def test_scheduler_failed_work_reports_error():
    s = DownloadScheduler()

    def boom():
        raise RuntimeError("no bitstream")

    h = s.submit("k", boom, lambda r, dt: r)
    assert s.drain(10)
    assert h.result is None and isinstance(h.error, RuntimeError)
    assert s.stats.failed == 1


# ---------------------------------------------------------------------------
# fallback-then-swap
# ---------------------------------------------------------------------------
def test_fallback_serves_then_swaps_to_downloaded_bitstream():
    ov = Overlay(3, 3, async_downloads=True)
    gate = _gate_downloads(ov)

    @ov.jit
    def rms(x, w):
        return jnp.sqrt(jnp.sum((x * w) ** 2) * (1.0 / x.size))

    x = jnp.linspace(0.0, 1.0, 512)
    w = jnp.linspace(1.0, 2.0, 512)
    ref = jnp.sqrt(jnp.sum((x * w) ** 2) / x.size)

    y_fallback = rms(x, w)                    # served while download blocked
    assert ov.stats.fallback_calls == 1
    assert len(ov.fabric) == 1                # regions held, download pending
    np.testing.assert_allclose(np.float32(y_fallback), np.float32(ref),
                               rtol=1e-6)

    gate.set()
    assert ov.drain(30)
    y_swapped = rms(x, w)                     # dispatches to the bitstream
    assert ov.stats.fallback_calls == 1       # no further fallback
    np.testing.assert_allclose(np.float32(y_swapped), np.float32(y_fallback),
                               rtol=1e-6)
    acc = rms.accelerator(x, w)
    assert acc is not None and ov.resident_current(acc)
    assert ov.fabric.download_cost(acc.resident_id) > 0.0


def test_async_numerics_match_sync_mode():
    def fn(x, w):
        return jnp.sum(jnp.sqrt((x * w) ** 2 + 1.0))

    x = jnp.linspace(0.5, 1.5, 256)
    w = jnp.linspace(0.9, 1.1, 256)

    sync = Overlay(3, 3)
    y_sync = sync.jit(fn)(x, w)

    asyn = Overlay(3, 3, async_downloads=True)
    jitted = asyn.jit(fn)
    y_fallback = jitted(x, w)
    assert asyn.drain(60)
    y_swapped = jitted(x, w)
    np.testing.assert_allclose(np.float32(y_fallback), np.float32(y_sync),
                               rtol=1e-6)
    np.testing.assert_allclose(np.float32(y_swapped), np.float32(y_sync),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# prefetch
# ---------------------------------------------------------------------------
def test_prefetch_hit_accounting_async():
    ov = Overlay(3, 3, async_downloads=True)

    @ov.jit
    def scale(x):
        return x * 3.0

    x = jnp.ones((64,))
    handle = scale.prefetch(x)
    assert handle is not None
    assert ov.stats.prefetches == 1
    assert ov.drain(60)

    y = scale(x)                              # demand lands on the prefetch
    np.testing.assert_allclose(y, x * 3.0)
    assert ov.stats.prefetch_hits == 1
    assert ov.stats.fallback_calls == 0       # never needed the fallback
    y2 = scale(x)                             # later hits aren't re-counted
    assert ov.stats.prefetch_hits == 1
    assert scale.prefetch(x) is None          # already resident: no-op


def test_prefetch_sync_mode_pays_download_eagerly():
    ov = Overlay(3, 3)                        # deterministic mode
    jitted = ov.jit(lambda x: x + 2.0, name="inc")
    x = jnp.ones((32,))
    assert jitted.prefetch(x) is None         # completed inline
    assert ov.stats.prefetches == 1
    assert ov.stats.downloads == 1
    assert ov.scheduler.describe()["submitted"] == 0   # no background job
    np.testing.assert_allclose(jitted(x), x + 2.0)
    assert ov.stats.prefetch_hits == 1


def test_overlay_level_prefetch_delegates_to_wrapper():
    ov = Overlay(3, 3, async_downloads=True)
    jitted = ov.jit(lambda x: x * 7.0, name="x7")
    x = jnp.ones((16,))
    assert ov.prefetch(jitted, x) is not None
    assert ov.drain(60)
    np.testing.assert_allclose(jitted(x), x * 7.0)
    assert ov.stats.prefetch_hits == 1
    other = Overlay(3, 3, async_downloads=True)
    with pytest.raises(ValueError):
        other.prefetch(jitted, x)


def test_close_stops_downloads_but_keeps_serving():
    ov = Overlay(3, 3, async_downloads=True)
    jitted = ov.jit(lambda x: x - 3.0, name="dec3")
    x = jnp.ones((16,))
    ov.close()
    np.testing.assert_allclose(jitted(x), x - 3.0)   # fallback, no crash
    assert ov.stats.fallback_calls == 1
    assert ov.scheduler.describe()["submitted"] == 0


def test_fallback_calls_keep_resident_recency_fresh():
    # a hot accelerator mid-download must not look like the LRU victim
    ov = Overlay(3, 3, async_downloads=True)
    gate = _gate_downloads(ov)
    jitted = ov.jit(lambda x: x * 2.0, name="hot")
    x = jnp.ones((16,))
    jitted(x)                                  # admit; download blocked
    (res,) = ov.fabric.residents.values()
    admitted_at = res.last_used
    jitted(x)                                  # fallback call while in flight
    assert ov.fabric.get(res.rid).last_used > admitted_at
    gate.set()
    assert ov.drain(30)


def test_reconfigure_prefetches_known_signatures():
    ov = Overlay(3, 3, async_downloads=True)
    jitted = ov.jit(lambda x: x * 5.0, name="x5")
    x = jnp.ones((32,))
    jitted(x)
    assert ov.drain(60)
    ov.reconfigure(policy=PlacementPolicy.STATIC)      # flush + re-prefetch
    assert ov.drain(60)
    assert len(ov.fabric) == 1                # signature re-downloaded
    fallback_before = ov.stats.fallback_calls
    np.testing.assert_allclose(jitted(x), x * 5.0)
    assert ov.stats.fallback_calls == fallback_before  # swap already landed
    assert ov.stats.prefetch_hits >= 1


# ---------------------------------------------------------------------------
# cost-aware reclaim
# ---------------------------------------------------------------------------
def test_cost_aware_reclaim_prefers_cheap_to_redownload_victims():
    # 2x2 all-SMALL fabric, two 2-tile residents saturate it.  A is older
    # but expensive to re-download; B is fresher but nearly free.  Pure LRU
    # would evict A; the cost model must spare it and evict B.
    ov = Overlay(2, 2, large_fraction=0.0, cost_aware_reclaim=True)
    g_a, g_b, g_c = (saxpy_graph(32, alpha=float(i)) for i in (1, 2, 3))
    rid_a = ov.assemble(g_a).resident_id
    rid_b = ov.assemble(g_b).resident_id
    ov.fabric.record_download_cost(rid_a, 30.0)     # pricey bitstream
    ov.fabric.record_download_cost(rid_b, 0.0001)   # trivial bitstream
    rid_c = ov.assemble(g_c).resident_id            # pressure: must reclaim
    live = set(ov.fabric.residents)
    assert live == {rid_a, rid_c}
    assert rid_b not in live
    assert ov.stats.reclaims == 1


def test_unmeasured_resident_is_not_the_preferred_victim():
    # a resident whose first download hasn't committed yet has no measured
    # cost; it must be priced at the measured mean (neutral), not ~0 —
    # otherwise every mid-download admission would be evicted first
    ov = Overlay(2, 2, large_fraction=0.0, cost_aware_reclaim=True)
    g_a, g_b, g_c = (saxpy_graph(32, alpha=float(i)) for i in (7, 8, 9))
    rid_a = ov.assemble(g_a).resident_id
    ov.fabric.record_download_cost(rid_a, 0.5)
    rid_b = ov.assemble(g_b).resident_id
    ov.fabric._download_costs.pop(rid_b, None)       # simulate: not measured
    ov.fabric.get(rid_b).download_cost = 0.0
    ov.assemble(g_c)                                 # pressure
    live = set(ov.fabric.residents)
    assert rid_b in live                             # fresh one survived
    assert rid_a not in live                         # LRU-equivalent choice


def test_uniform_costs_degrade_to_pure_lru():
    ov = Overlay(2, 2, large_fraction=0.0, cost_aware_reclaim=True)
    g1, g2, g3 = (saxpy_graph(32, alpha=float(i)) for i in (4, 5, 6))
    r1 = ov.assemble(g1).resident_id
    r2 = ov.assemble(g2).resident_id
    ov.assemble(g1)                                 # touch: g2 becomes LRU
    r3 = ov.assemble(g3).resident_id
    assert set(ov.fabric.residents) == {r1, r3}     # LRU victim (g2) evicted


def test_download_cost_ledger_survives_eviction():
    ov = Overlay(2, 2, large_fraction=0.0)
    g = saxpy_graph(32, alpha=9.0)
    rid = ov.assemble(g).resident_id
    # lazy sync downloads don't feed the model (their ~0s build time is
    # scheduling noise); the first real measurement is taken verbatim
    assert ov.fabric.download_cost(rid) == 0.0
    ov.fabric.record_download_cost(rid, 2.0)
    assert ov.fabric.download_cost(rid) == 2.0
    ov.evict(g)
    assert ov.fabric.get(rid) is None
    assert ov.fabric.download_cost(rid) == 2.0      # model persists
    # re-admission seeds from the persisted model, and the lazy re-download
    # leaves it untouched
    res = ov.fabric.get(ov.assemble(saxpy_graph(32, alpha=9.0)).resident_id)
    assert res.download_cost == 2.0


# ---------------------------------------------------------------------------
# shutdown / eviction regressions: late bitstreams must not resurrect
# ---------------------------------------------------------------------------
def test_evicted_resident_not_resurrected_by_late_download():
    ov = Overlay(3, 3, async_downloads=True)
    gate = _gate_downloads(ov)
    jitted = ov.jit(lambda x: x - 1.0, name="dec")
    x = jnp.ones((32,))
    jitted(x)                                  # fallback; download blocked
    assert len(ov.fabric) == 1
    ov.evict("dec")                            # free the PR regions now
    assert len(ov.fabric) == 0
    gate.set()                                 # late bitstream arrives
    assert ov.drain(30)
    assert len(ov.fabric) == 0                 # still evicted
    assert len(ov.cache) == 0                  # no orphan bitstream published
    sched = ov.scheduler.describe()
    assert sched["cancelled"] + sched["dropped_stale"] >= 1
    assert sched["completed"] == 0


def test_reconfigure_mid_download_drops_stale_bitstream():
    ov = Overlay(3, 3, async_downloads=True)
    gate = _gate_downloads(ov)
    jitted = ov.jit(lambda x: x * 2.0, name="dbl")
    x = jnp.ones((32,))
    jitted(x)
    time.sleep(0.05)                           # worker holds the gated job
    ov.reconfigure(prefetch=False)             # flush; nothing re-requested
    assert len(ov.fabric) == 0
    gate.set()
    assert ov.drain(30)
    assert len(ov.fabric) == 0 and len(ov.cache) == 0
    # the next call still works: fresh fallback + fresh download
    np.testing.assert_allclose(jitted(x), x * 2.0)
    gate.set()
    assert ov.drain(30)
    assert len(ov.fabric) == 1


def test_commit_guard_checks_fabric_is_current():
    # the backstop for the cancel/commit race: a commit whose (rid,
    # generation) is no longer current must be refused outright
    ov = Overlay(3, 3, async_downloads=True)
    acc = ov.assemble(saxpy_graph(32, alpha=1.5))
    res = ov.fabric.get(acc.resident_id)
    from repro.core.overlay import _PendingDownload
    stale = _PendingDownload(rid=res.rid, generation=res.generation - 1,
                             key="k", base=acc, avals=())
    assert ov._commit_download(stale, object(), 0.1) is None
    assert ov.stats.stale_downloads == 1


def test_failed_download_retries_are_bounded_and_fallback_survives():
    ov = Overlay(3, 3, async_downloads=True)
    ov._compile_bitstream = lambda pending: (_ for _ in ()).throw(
        RuntimeError("synthetic compile failure"))
    jitted = ov.jit(lambda x: x * 4.0, name="quad")
    x = jnp.ones((32,))
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore", RuntimeWarning)
        for _ in range(6):                      # every call keeps working
            np.testing.assert_allclose(jitted(x), x * 4.0)
            assert ov.drain(30)
    # retries are capped: not one background compile per call forever
    assert ov.scheduler.stats.failed == 3
    assert ov.stats.fallback_calls == 6


def test_jit_kwargs_survive_reconfigure_prefetch():
    # donate_argnums shape the bitstream (the cache keys on them); the
    # post-reconfigure auto-prefetch must rebuild with the same kwargs
    ov = Overlay(3, 3, async_downloads=True)
    jitted = ov.jit(lambda x: x + 1.0, name="inc", donate_argnums=(0,))
    x = jnp.ones((32,))
    jitted(x)
    assert ov.drain(60)
    entry = next(iter(jitted._entries.values()))
    assert entry.jit_kwargs == {"donate_argnums": (0,)}
    ov.reconfigure()
    assert ov.drain(60)
    assert entry.jit_kwargs == {"donate_argnums": (0,)}
    assert len(ov.fabric) == 1                  # re-downloaded via prefetch
    np.testing.assert_allclose(jitted(jnp.ones((32,))), jnp.ones((32,)) + 1.0)


# ---------------------------------------------------------------------------
# deterministic synchronous mode
# ---------------------------------------------------------------------------
def test_sync_mode_keeps_pre_scheduler_behavior():
    # async off (the default): a jit miss assembles on the critical path,
    # no worker threads spawn, no fallbacks serve, stats read as before
    for ov in (Overlay(3, 3), Overlay(3, 3, async_downloads=False)):
        jitted = ov.jit(lambda a, b: jnp.sum(a * b), name="dot")
        x = jnp.linspace(0.0, 1.0, 64)
        np.testing.assert_allclose(jitted(x, x), jnp.sum(x * x), rtol=1e-6)
        assert not ov.async_downloads and not ov.cost_aware_reclaim
        assert ov.stats.fallback_calls == 0
        assert ov.stats.downloads == 1
        sched = ov.scheduler.describe()
        assert sched["submitted"] == 0 and sched["workers"] == 0
        acc = jitted.accelerator(x, x)
        assert acc is not None and ov.resident_current(acc)


def test_mesh_overlay_forces_synchronous_mode():
    import jax
    if len(jax.devices()) < 1:                 # pragma: no cover
        pytest.skip("no devices")
    mesh = make_mesh((1,), ("tiles",))
    ov = Overlay(3, 3, mesh=mesh, async_downloads=True)
    assert not ov.async_downloads              # sharded assembly stays sync


def test_submit_after_shutdown_returns_cancelled_handle():
    # Regression: submit() used to pre-check _shutdown outside the critical
    # section, so a shutdown landing between the check and the enqueue left
    # the job queued on a dead scheduler — waiters hung, observers never
    # fired.  Now the race is decided under _cond: a post-shutdown submit
    # returns an already-done CANCELLED handle and still calls on_done.
    s = DownloadScheduler()
    s.shutdown(wait=True)
    seen = []
    h = s.submit("late", lambda: 1, lambda r, dt: r,
                 on_done=lambda r, hh: seen.append((r, hh.status)))
    assert h.status == "cancelled"
    assert h.wait(1)                       # event pre-set: no hang
    assert seen == [(None, "cancelled")]
    assert s.stats.cancelled == 1

"""Tiered route specialization (DESIGN.md §7): the route-constant
specialized artifact is bit-identical to the generic relocatable kernel,
swaps in atomically off the scheduler's low lane, and any relocation
instantly despecializes back to the generic tier."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import (Opcode, Overlay, PlacementPolicy, TileGrid,
                        build_kernel, compile_compute, compile_specialized,
                        place, place_static, route_hops, route_vector,
                        saxpy_graph, specialize_kernel, trace_to_graph,
                        vmul_reduce_graph, zero_hop)


def _gate_spec(ov):
    """Block the overlay's specialize compiles until the gate is set."""
    gate = threading.Event()
    orig = ov._compile_specialized_tier

    def gated(pending):
        gate.wait(30)
        return orig(pending)

    ov._compile_specialized_tier = gated
    return gate


def _disjoint_placement(ov, graph, res):
    return place(graph, ov.grid, ov.policy, occupied=set(res.tiles))


# ---------------------------------------------------------------------------
# ISA: the specialized controller program carries NO per-dispatch routes
# ---------------------------------------------------------------------------
def test_compile_specialized_has_no_route_programming():
    g = vmul_reduce_graph(128)
    ops = g.op_nodes()
    # a deliberately spread-out static placement: plenty of hops
    pl = place_static(g, TileGrid(3, 3),
                      {ops[0].node_id: (2, 2), ops[1].node_id: (0, 0)})
    assert pl.total_hops > 0
    spec = compile_specialized(g, pl)
    assert not any(i.opcode.name.startswith(("ROUTE", "BYPASS"))
                   for i in spec.instructions)
    head = spec.instructions[0]
    assert head.opcode is Opcode.LD_INSTR          # baked instruction image
    assert head.meta[0] == "route-const"
    assert dict(head.meta[1]) == pl.edge_hops      # hops folded into the meta
    # exactly the compute body plus the one instruction-BRAM load
    assert len(spec) == len(compile_compute(g)) + 1
    assert spec.mix()["interconnect"] == 1         # only the closing BARRIER


# ---------------------------------------------------------------------------
# kernel level: bit-identical outputs, loop structure gone
# ---------------------------------------------------------------------------
def test_specialized_kernel_bit_identical_contraction_prone():
    # mul feeding add is the FMA-contraction hazard; the exactness guard
    # must keep the fused specialized body bit-identical to the generic
    # kernel's loop-bounded one
    def fn(x, w):
        acc = x
        for i in range(6):
            acc = (acc * w) + float(i + 1)
        return jnp.sqrt(acc * acc + 1.0) - (acc * w)

    x = jnp.linspace(0.1, 1.0, 256)
    w = jnp.linspace(0.9, 1.1, 256)
    g = trace_to_graph(fn, x, w, name="fma_chain").graph
    pl = place(g, TileGrid(3, 3), PlacementPolicy.DYNAMIC)
    hops = route_hops(g, pl)
    y_gen = np.asarray(jax.jit(build_kernel(g))(route_vector(g, pl), x, w))
    y_spec = np.asarray(jax.jit(specialize_kernel(g, hops))(
        route_vector(g, pl), x, w))
    assert np.array_equal(y_gen, y_spec)


def test_specialized_kernel_bit_identical_multi_hop():
    # a spread static placement: baked hops >= 2 unroll the pass-through
    # multiplies statically and must still match the generic fori_loop
    g = vmul_reduce_graph(512)
    ops = g.op_nodes()
    pl = place_static(g, TileGrid(3, 3),
                      {ops[0].node_id: (2, 2), ops[1].node_id: (0, 0)})
    hops = route_hops(g, pl)
    assert max(hops) >= 2 and not zero_hop(hops)
    a = jnp.linspace(0.0, 1.0, 512)
    b = jnp.linspace(1.0, 2.0, 512)
    rv = route_vector(g, pl)
    y_gen = np.asarray(jax.jit(build_kernel(g))(rv, a, b))
    y_spec = np.asarray(jax.jit(specialize_kernel(g, hops))(rv, a, b))
    assert np.array_equal(y_gen, y_spec)


def test_specialize_kernel_rejects_wrong_arity():
    g = saxpy_graph(32)
    with pytest.raises(ValueError):
        specialize_kernel(g, (0,))


def test_zero_hop_predicate():
    assert zero_hop(())
    assert zero_hop((0, 1, 1, 0))
    assert not zero_hop((0, 2))


# ---------------------------------------------------------------------------
# overlay: explicit specialization (sync), swap, dispatch records
# ---------------------------------------------------------------------------
def test_sync_specialize_swaps_tier_and_stays_bit_identical():
    ov = Overlay(3, 3)
    jitted = ov.jit(lambda x, w: jnp.sqrt((x * w) ** 2 + 1.0) * 2.0,
                    name="spec_me")
    x = jnp.linspace(0.1, 1.0, 128)
    w = jnp.linspace(0.9, 1.1, 128)
    y0 = np.asarray(jitted(x, w))
    entry = next(iter(jitted._entries.values()))
    assert entry.record is not None and entry.record.tier == "generic"
    ins = ov.cache.stats.insertions
    jitted.specialize(x, w)
    assert entry.record.tier == "specialized"
    res = ov.fabric.get(entry.acc.resident_id)
    assert res.tier == "specialized"
    assert ov.cache.specialized_count() == 1
    assert ov.cache.stats.insertions == ins     # generic store untouched
    assert ov.cache.spec_stats.specializations == 1
    y1 = np.asarray(jitted(x, w))
    assert np.array_equal(y0, y1)               # bit-identical across tiers
    assert ov.cache.spec_stats.specialized_hits == 1
    # idempotent: already specialized -> no-op
    assert jitted.specialize(x, w) is None
    assert ov.cache.spec_stats.specializations == 1


def test_sync_overlay_never_auto_specializes():
    ov = Overlay(3, 3)                          # deterministic mode
    jitted = ov.jit(lambda x: x * 2.0, name="no_auto")
    x = jnp.ones((64,))
    for _ in range(8):
        jitted(x)
    assert ov.scheduler.describe()["submitted"] == 0
    (res,) = ov.fabric.residents.values()
    assert res.tier == "generic"


def test_relocation_despecializes_instantly():
    ov = Overlay(3, 3)
    jitted = ov.jit(lambda x, w: jnp.maximum(x * w, 0.5) + w, name="mover")
    x = jnp.linspace(0.1, 1.0, 64)
    y0 = np.asarray(jitted(x, x))
    entry = next(iter(jitted._entries.values()))
    jitted.specialize(x, x)
    assert np.array_equal(np.asarray(jitted(x, x)), y0)
    res = ov.fabric.get(entry.acc.resident_id)
    g = entry.lowered.graph
    ov.relocate(g, _disjoint_placement(ov, g, res))
    res2 = ov.fabric.get(res.rid)
    assert res2.tier == "generic"               # instant despecialization
    assert res2.spec_fn is None
    assert ov.cache.specialized_count() == 0    # artifacts dropped
    assert ov.cache.spec_stats.despecializations == 1
    y1 = np.asarray(jitted(x, x))               # generic keeps serving
    assert np.array_equal(y0, y1)               # zero drift through the cycle
    assert entry.record.tier == "generic"
    # re-specialize at the new placement: fresh artifact, fresh routes
    jitted.specialize(x, x)
    assert ov.fabric.get(res.rid).tier == "specialized"
    assert np.array_equal(np.asarray(jitted(x, x)), y0)


def test_eviction_drops_specialized_artifacts():
    ov = Overlay(3, 3)
    jitted = ov.jit(lambda x: x - 1.5, name="doomed")
    x = jnp.ones((32,))
    jitted(x)
    jitted.specialize(x)
    assert ov.cache.specialized_count() == 1
    ov.evict("doomed")
    assert ov.cache.specialized_count() == 0
    assert len(ov.cache) == 0
    # destroying a specialized resident is a despecialization on the ledger
    assert ov.cache.spec_stats.despecializations == 1


# ---------------------------------------------------------------------------
# async: auto-specialization triggers, low lane, despecialize races
# ---------------------------------------------------------------------------
def test_async_auto_specializes_contiguous_resident():
    ov = Overlay(3, 3, async_downloads=True)
    jitted = ov.jit(lambda x: x * 3.0 + 1.0, name="hot")
    x = jnp.ones((64,))
    y0 = np.asarray(jitted(x))                  # fallback; download submitted
    assert ov.drain(60)
    y1 = np.asarray(jitted(x))                  # generic hit -> zero-hop trigger
    assert ov.drain(60)                         # low-lane spec compile lands
    assert ov.cache.spec_stats.specializations == 1
    assert ov.scheduler.stats.low_jobs == 1
    (res,) = ov.fabric.residents.values()
    assert res.tier == "specialized" and res.zero_hop
    y2 = np.asarray(jitted(x))                  # specialized dispatch
    assert ov.cache.spec_stats.specialized_hits >= 1
    assert np.array_equal(y0, y1) and np.array_equal(y1, y2)


def test_async_stability_trigger_after_n_dispatches():
    ov = Overlay(3, 3, async_downloads=True, specialize_after=3)
    jitted = ov.jit(lambda x: x + 0.5, name="stable")
    x = jnp.ones((32,))
    jitted(x)
    assert ov.drain(60)
    (res,) = ov.fabric.residents.values()
    res.zero_hop = False                        # force the stability path
    jitted(x)
    jitted(x)
    assert ov.scheduler.stats.low_jobs == 0     # 2 < specialize_after
    jitted(x)                                   # 3rd stable dispatch
    assert ov.scheduler.stats.low_jobs == 1
    assert ov.drain(60)
    assert ov.fabric.get(res.rid).tier == "specialized"


def test_relocation_cancels_inflight_specialize_job():
    ov = Overlay(3, 3, async_downloads=True, auto_specialize=False)
    jitted = ov.jit(lambda x: x * 4.0, name="racer")
    x = jnp.ones((32,))
    jitted(x)
    assert ov.drain(60)
    gate = _gate_spec(ov)
    handle = jitted.specialize(x)
    assert handle is not None
    time.sleep(0.05)                            # worker inside the gated job
    entry = next(iter(jitted._entries.values()))
    res = ov.fabric.get(entry.acc.resident_id)
    g = entry.lowered.graph
    y0 = np.asarray(jitted(x))
    ov.relocate(g, _disjoint_placement(ov, g, res))   # cancels + despecializes
    gate.set()
    assert ov.drain(60)
    assert ov.cache.spec_stats.specializations == 0   # never committed
    assert ov.cache.specialized_count() == 0
    assert ov.fabric.get(res.rid).tier == "generic"
    sched = ov.scheduler.stats
    assert sched.cancelled + sched.dropped_stale >= 1
    assert np.array_equal(np.asarray(jitted(x)), y0)


def test_spec_commit_landing_after_relocation_is_dropped():
    # the commit-side guard: a specialized compile whose (rid, generation)
    # relocated while it was building must be refused — the baked routes no
    # longer describe the resident's tiles
    ov = Overlay(3, 3, async_downloads=True, auto_specialize=False)
    jitted = ov.jit(lambda x: x - 2.0, name="late")
    x = jnp.ones((32,))
    jitted(x)
    assert ov.drain(60)
    gate = _gate_spec(ov)
    assert jitted.specialize(x) is not None
    time.sleep(0.05)
    entry = next(iter(jitted._entries.values()))
    res = ov.fabric.get(entry.acc.resident_id)
    res.spec_job = None      # hide the job from the relocation's cancel so
    g = entry.lowered.graph  # the commit itself must hit the guard
    y0 = np.asarray(jitted(x))
    ov.relocate(g, _disjoint_placement(ov, g, res))
    gate.set()
    assert ov.drain(60)
    assert ov.cache.spec_stats.dropped_stale == 1
    assert ov.cache.spec_stats.specializations == 0
    assert ov.cache.specialized_count() == 0
    res2 = ov.fabric.get(res.rid)
    assert res2.tier == "generic" and res2.spec_fn is None
    assert np.array_equal(np.asarray(jitted(x)), y0)


def test_failed_specialize_compile_unwedges_and_bounds_retries():
    # a failing background specialize must clear spec_pending (else the
    # resident is wedged generic-forever with "specializing" stuck True)
    # and stop being retried after the cap — the generic tier keeps serving
    import warnings as _warnings

    ov = Overlay(3, 3, async_downloads=True)
    jitted = ov.jit(lambda x: x * 2.0, name="failer")
    x = jnp.ones((16,))
    jitted(x)
    assert ov.drain(60)
    ov._compile_specialized_tier = lambda pending: (_ for _ in ()).throw(
        RuntimeError("synthetic specialize failure"))
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore", RuntimeWarning)
        for _ in range(6):                      # zero-hop trigger each call
            np.testing.assert_allclose(jitted(x), x * 2.0)
            assert ov.drain(60)
    (res,) = ov.fabric.residents.values()
    assert res.tier == "generic"
    assert not res.spec_pending                 # never wedged
    assert res.spec_failures == 3
    assert ov.scheduler.stats.failed == 3       # retries are capped
    assert ov.cache.spec_stats.specializations == 0


def test_defragment_enqueues_specialization_for_contiguous_residents():
    ov = Overlay(2, 2, large_fraction=0.0, async_downloads=True)
    filler = ov.jit(lambda x: x * 2.0, name="filler")
    mover = ov.jit(lambda x: x - 4.0, name="mover")
    x = jnp.ones((32,))
    filler(x)
    y0 = np.asarray(mover(x))
    assert ov.drain(60)
    ov.evict("filler")
    assert ov.defragment() == 1                 # move + spec enqueued
    assert ov.drain(60)
    (res,) = ov.fabric.residents.values()
    assert res.tier == "specialized"
    entry = next(iter(mover._entries.values()))
    assert entry.record is not None and entry.record.tier == "specialized"
    assert np.array_equal(np.asarray(mover(x)), y0)


def test_sharded_overlay_specializes_bit_identical():
    # mesh mode: static hops unroll into ppermutes (no fori_loop/switch);
    # outputs must still match the generic collective kernel bit for bit
    mesh = make_mesh((len(jax.devices()),), ("tiles",))
    ov = Overlay(3, 3, mesh=mesh)
    jitted = ov.jit(lambda x, w: jnp.sqrt((x * w) ** 2 + 1.0), name="sh")
    x = jnp.linspace(0.1, 1.0, 64)
    w = jnp.linspace(0.9, 1.1, 64)
    y0 = np.asarray(jitted(x, w))
    jitted.specialize(x, w)
    entry = next(iter(jitted._entries.values()))
    assert entry.record.tier == "specialized"
    assert np.array_equal(np.asarray(jitted(x, w)), y0)


def test_serve_engine_requests_decode_specialization_eagerly():
    # decode is the per-token hot path: the engine must queue its
    # route-constant tier the moment traffic arrives, without ever blocking
    # a tick (low lane)
    from repro.configs.archs import smoke_config
    from repro.models import params as pm
    from repro.models.model import model_spec
    from repro.serving import EventLoopEngine, Request

    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    ov = Overlay(4, 4, async_downloads=True)
    engine = EventLoopEngine(params, cfg, batch=2, max_len=64, overlay=ov)
    engine.submit(Request(rid=0, prompt=[1, 2, 3, 4], max_new_tokens=4))
    done = engine.run_until_drained()
    assert len(done) == 1 and done[0].decode_steps == 4
    assert ov.scheduler.stats.low_jobs == 1     # exactly the decode spec job
    assert ov.drain(120)
    tiers = {r.name: r.tier for r in ov.fabric.residents.values()}
    assert tiers[f"{cfg.name}.decode"] == "specialized"
    assert tiers[f"{cfg.name}.prefill_chunk"] == "generic"


# ---------------------------------------------------------------------------
# stats accounting + introspection
# ---------------------------------------------------------------------------
def test_specialization_stats_accounting_full_cycle():
    ov = Overlay(3, 3)
    jitted = ov.jit(lambda x: jnp.abs(x) + 1.0, name="counted")
    x = jnp.linspace(-1.0, 1.0, 64)
    jitted(x)
    jitted.specialize(x)
    for _ in range(3):
        jitted(x)
    entry = next(iter(jitted._entries.values()))
    res = ov.fabric.get(entry.acc.resident_id)
    g = entry.lowered.graph
    ov.relocate(g, _disjoint_placement(ov, g, res))
    jitted(x)                                   # generic again
    spec = ov.describe()["specialization"]
    assert spec["specializations"] == 1
    assert spec["despecializations"] == 1
    assert spec["specialized_hits"] == 3
    assert spec["dropped_stale"] == 0
    assert spec["specialized_artifacts"] == 0
    assert spec["compile_seconds"] > 0.0
    # per-resident tier reporting for operators
    rep = ov.describe()["fabric"]["residents"][res.rid]
    assert rep["tier"] == "generic"
    assert "zero_hop" in rep and "specializing" in rep


def test_describe_reports_specialized_tier_per_resident():
    ov = Overlay(3, 3)
    jitted = ov.jit(lambda x: x * 9.0, name="seen")
    x = jnp.ones((16,))
    jitted(x)
    jitted.specialize(x)
    entry = next(iter(jitted._entries.values()))
    rep = ov.describe()["fabric"]["residents"][entry.acc.resident_id]
    assert rep["tier"] == "specialized"
    assert rep["specializing"] is False


# ---------------------------------------------------------------------------
# device-resident routes (built once at admit/relocate, never per call)
# ---------------------------------------------------------------------------
def test_routes_built_once_at_admit_and_refreshed_on_relocate():
    ov = Overlay(3, 3)
    g = saxpy_graph(64)
    acc = ov.assemble(g)
    res = ov.fabric.get(acc.resident_id)
    assert isinstance(res.routes, jax.Array)    # device-resident, eager
    assert ov.cache.route_stats.emitted == 1
    x = jnp.ones((64,))
    acc(x, x)
    ov.assemble(saxpy_graph(64))                # resident hit
    assert ov.cache.route_stats.emitted == 1    # never rebuilt on dispatch
    new_pl = place(g, ov.grid, ov.policy, occupied=set(res.tiles))
    ov.relocate(g, new_pl)
    res2 = ov.fabric.get(res.rid)
    assert isinstance(res2.routes, jax.Array)   # rebuilt eagerly at the move
    assert ov.cache.route_stats.emitted == 2
    np.testing.assert_array_equal(
        np.asarray(res2.routes), np.asarray(route_vector(g, new_pl)))


def test_reconfigure_flush_clears_specialized_tier():
    ov = Overlay(3, 3, async_downloads=True)
    jitted = ov.jit(lambda x: x + 7.0, name="flushed")
    x = jnp.ones((16,))
    jitted(x)
    assert ov.drain(60)
    jitted(x)
    assert ov.drain(60)                         # auto-spec landed
    assert ov.cache.specialized_count() == 1
    ov.reconfigure(prefetch=False)
    assert ov.cache.specialized_count() == 0
    np.testing.assert_allclose(jitted(x), x + 7.0)
    assert ov.drain(60)                         # leave no job behind

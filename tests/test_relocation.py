"""Relocatable bitstreams: compiled kernel artifacts are placement-free and
residents move between placements (defrag, budget repacks, policy changes)
without re-downloading — only the cheap route program is re-emitted."""

import dataclasses
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FabricError, Overlay, PlacementError,
                        PlacementPolicy, TileGrid, compile_compute,
                        compile_graph, compile_routes, place, place_dynamic,
                        place_static, saxpy_graph, vmul_reduce_graph)
from repro.core.interpreter import edge_order, route_vector


# ---------------------------------------------------------------------------
# ISA split: compute body is placement-invariant, routes carry the placement
# ---------------------------------------------------------------------------
def test_compile_graph_is_compute_woven_with_routes():
    g = vmul_reduce_graph(128)
    grid = TileGrid(3, 3)
    ops = g.op_nodes()
    fixed = {ops[0].node_id: (2, 2), ops[1].node_id: (0, 0)}
    pl = place_static(g, grid, fixed)
    full = compile_graph(g, pl)
    compute = compile_compute(g)
    routes = compile_routes(g, pl)
    assert len(full) == len(compute) + len(routes)
    full_mix, comp_mix, route_mix = full.mix(), compute.mix(), routes.mix()
    for cat in full_mix:
        assert full_mix[cat] == comp_mix[cat] + route_mix[cat]
    # the compute body's only interconnect is the closing BARRIER (a sync
    # point, not a route); the route program is pure interconnect
    assert comp_mix["interconnect"] == 1
    assert route_mix["interconnect"] == len(routes)
    assert all(i.opcode.name.startswith(("ROUTE", "BYPASS"))
               for i in routes.instructions)


def test_compute_body_identical_across_placements():
    g = vmul_reduce_graph(128)
    comp_a = compile_compute(g)
    comp_b = compile_compute(g)
    assert [i.opcode for i in comp_a.instructions] == \
           [i.opcode for i in comp_b.instructions]
    # routes differ between placements, compute does not
    pl_a = place_dynamic(g, TileGrid(3, 3))
    ops = g.op_nodes()
    pl_b = place_static(g, TileGrid(3, 3),
                        {ops[0].node_id: (2, 2), ops[1].node_id: (0, 0)})
    assert len(compile_routes(g, pl_a)) != len(compile_routes(g, pl_b))


def test_route_vector_matches_edge_hops():
    g = saxpy_graph(64)
    pl = place_dynamic(g, TileGrid(3, 3))
    rv = np.asarray(route_vector(g, pl))
    edges = edge_order(g)
    assert rv.shape == (len(edges),)
    for e, h in zip(edges, rv):
        assert int(h) == pl.edge_hops.get(e, 0)


# ---------------------------------------------------------------------------
# kernel artifacts are placement-free (shared across placements / pinnings)
# ---------------------------------------------------------------------------
def test_two_pinnings_share_one_kernel_artifact():
    ov = Overlay(3, 3, policy=PlacementPolicy.STATIC)
    g1, g2 = vmul_reduce_graph(64), vmul_reduce_graph(64)
    ops1, ops2 = g1.op_nodes(), g2.op_nodes()
    f1 = {ops1[0].node_id: (0, 1), ops1[1].node_id: (0, 0)}
    f2 = {ops2[0].node_id: (2, 1), ops2[1].node_id: (2, 2)}
    acc1 = ov.assemble(g1, fixed=f1)
    ov.assemble(g2, fixed=f2)                  # same graph, different tiles
    assert len(ov.fabric) == 2                 # two residents...
    assert len(ov.cache) == 1                  # ...ONE compiled kernel
    assert ov.cache.stats.misses == 1 and ov.cache.stats.hits >= 1
    # evicting one pinning must NOT drop the kernel the survivor still owns
    ov._evict_resident(acc1.resident_id)
    assert len(ov.fabric) == 1
    assert len(ov.cache) == 1                  # shared artifact survives
    misses = ov.cache.stats.misses
    ov.assemble(vmul_reduce_graph(64), fixed=f2)   # survivor: pure hit
    assert ov.cache.stats.misses == misses


def test_public_relocate_rejects_invalid_placements():
    ov = Overlay(3, 3)                         # LARGE at (0,0),(1,1),(2,2)
    g = vmul_reduce_graph(64)                  # Reduce is LARGE-class
    ov.assemble(g)
    res = ov.fabric.get(next(iter(ov.fabric.residents)))
    ops = g.op_nodes()
    bad_class = dataclasses.replace(
        res.placement,
        assignment={ops[0].node_id: (0, 1), ops[1].node_id: (0, 2)})
    with pytest.raises(PlacementError):        # LARGE op on SMALL tile
        ov.relocate(g, bad_class)
    off_grid = dataclasses.replace(
        res.placement,
        assignment={ops[0].node_id: (9, 9), ops[1].node_id: (0, 0)})
    with pytest.raises(PlacementError):        # coordinate off the grid
        ov.relocate(g, off_grid)
    assert ov.stats.relocations == 0           # fabric untouched


def test_relocation_preserves_numerics_bit_identical():
    ov = Overlay(3, 3)
    g = vmul_reduce_graph(512)
    a = jnp.linspace(0.0, 1.0, 512)
    b = jnp.linspace(1.0, 2.0, 512)
    acc = ov.assemble(g)
    y0 = np.asarray(jax.block_until_ready(acc(a, b)))
    res = ov.fabric.get(acc.resident_id)
    old_tiles = set(res.tiles)
    # a disjoint placement forces a real move
    new_pl = place(g, ov.grid, ov.policy, occupied=old_tiles)
    ins, ev = ov.cache.stats.insertions, ov.cache.stats.evictions
    moved = ov.relocate(g, new_pl)
    assert moved.tiles and not (moved.tiles & old_tiles)
    assert moved.relocations == 1
    acc2 = ov.assemble(g)
    y1 = np.asarray(jax.block_until_ready(acc2(a, b)))
    assert np.array_equal(y0, y1)              # bit-identical across the move
    assert ov.cache.stats.insertions == ins    # zero kernel churn
    assert ov.cache.stats.evictions == ev
    assert ov.stats.relocations == 1


def test_fabric_relocate_keeps_artifacts_and_ledger():
    ov = Overlay(3, 3)
    g = saxpy_graph(64)
    acc = ov.assemble(g)
    rid = acc.resident_id
    ov.fabric.record_download_cost(rid, 1.5)
    res = ov.fabric.get(rid)
    keys_before = res.cache_keys
    assert keys_before
    gen_before = res.generation
    new_pl = place(g, ov.grid, ov.policy, occupied=set(res.tiles))
    moved = ov.fabric.relocate(rid, new_pl, compile_graph(g, new_pl))
    assert moved.cache_keys == keys_before       # kernel artifacts survive
    assert ov.fabric.download_cost(rid) == 1.5   # ledger intact
    assert moved.generation > gen_before         # dispatch handles refresh
    assert moved.admit_generation == res.admit_generation
    # the old generation is still the same residency epoch (commit guard)...
    assert ov.fabric.same_residency(rid, gen_before)
    # ...but no longer current for dispatch
    assert not ov.fabric.is_current(rid, gen_before)


def test_fabric_relocate_onto_occupied_tiles_raises():
    ov = Overlay(2, 2, large_fraction=0.0)
    g1, g2 = saxpy_graph(32, alpha=1.0), saxpy_graph(32, alpha=2.0)
    g1.name, g2.name = "one", "two"
    acc1 = ov.assemble(g1)
    acc2 = ov.assemble(g2)
    res2 = ov.fabric.get(acc2.resident_id)
    clashing = res2.placement                  # two's tiles are occupied
    with pytest.raises(FabricError):
        ov.fabric.relocate(acc1.resident_id, clashing,
                           compile_graph(g1, clashing))


def test_kernel_jit_kwargs_shifts_all_donate_forms():
    from repro.core import kernel_jit_kwargs
    # index 0 (falsy) and bare-int forms jax.jit accepts must shift too —
    # the routes vector at kernel arg 0 is never donated
    assert kernel_jit_kwargs({"donate_argnums": (0,)}) == {"donate_argnums": (1,)}
    assert kernel_jit_kwargs({"donate_argnums": 0}) == {"donate_argnums": (1,)}
    assert kernel_jit_kwargs({"donate_argnums": 2}) == {"donate_argnums": (3,)}
    assert kernel_jit_kwargs({"donate_argnums": (0, 1)}) == \
        {"donate_argnums": (1, 2)}
    assert kernel_jit_kwargs(None) == {}


def test_relocate_by_accelerator_name():
    # the public API resolves names the way evict() does
    ov = Overlay(3, 3)
    g = saxpy_graph(64)
    acc = ov.assemble(g)
    res = ov.fabric.get(acc.resident_id)
    new_pl = place(g, ov.grid, ov.policy, occupied=set(res.tiles))
    moved = ov.relocate("saxpy", new_pl)
    assert moved.relocations == 1
    with pytest.raises(FabricError):
        ov.relocate("no-such-accelerator", new_pl)


def test_route_program_table_stays_bounded_under_repeated_moves():
    ov = Overlay(3, 3)
    g = saxpy_graph(64)
    acc = ov.assemble(g)
    for _ in range(5):                          # bounce between placements
        res = ov.fabric.get(acc.resident_id)
        new_pl = place(g, ov.grid, ov.policy, occupied=set(res.tiles))
        ov.relocate(g, new_pl)
        acc = ov.assemble(g)                    # rebuilds the route program
    # old-placement programs die with each move: one live entry, not five
    assert ov.cache.route_programs() == 1
    assert ov.cache.route_stats.emitted == 6    # initial + 5 moves


# ---------------------------------------------------------------------------
# defragment(): moves are relocations — zero kernel-artifact churn
# ---------------------------------------------------------------------------
def test_defragment_moves_without_kernel_evictions_or_insertions():
    ov = Overlay(2, 2, large_fraction=0.0)
    g1, g2 = saxpy_graph(32, alpha=1.0), saxpy_graph(32, alpha=2.0)
    g1.name, g2.name = "front", "back"
    ov.assemble(g1)
    acc2 = ov.assemble(g2)
    x = jnp.linspace(0.0, 1.0, 32)
    y0 = np.asarray(acc2(x, x))
    ov.evict(g1)
    ins, ev = ov.cache.stats.insertions, ov.cache.stats.evictions
    assert ov.defragment() == 1
    assert ov.cache.stats.insertions == ins    # acceptance: zero insertions
    assert ov.cache.stats.evictions == ev      # acceptance: zero evictions
    acc2b = ov.assemble(g2)
    assert np.array_equal(np.asarray(acc2b(x, x)), y0)
    assert ov.cache.stats.insertions == ins    # rebind was a pure hit
    (res,) = ov.fabric.residents.values()
    assert res.relocations == 1
    assert ov.describe()["fabric"]["residents"][res.rid]["relocations"] == 1


def test_jitted_fn_survives_defrag_without_redownload_sync():
    ov = Overlay(2, 2, large_fraction=0.0)
    filler = ov.jit(lambda x: x * 2.0 + 1.0, name="filler")
    moved = ov.jit(lambda x: x * 3.0 - 1.0, name="mover")
    x = jnp.linspace(0.0, 1.0, 64)
    y_fill = filler(x)
    y0 = moved(x)
    ov.evict("filler")
    ins = ov.cache.stats.insertions
    assert ov.defragment() == 1
    y1 = moved(x)                              # stale handle -> cheap rebind
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    assert ov.cache.stats.insertions == ins    # no XLA re-download
    np.testing.assert_allclose(y_fill, x * 2.0 + 1.0)


def test_defrag_failure_counts_and_warns(caplog):
    # a LARGE-op resident placed while LARGE tiles existed; the grid then
    # loses them, so the survivor cannot re-place — the pass must abort,
    # count the failure and name the blocking resident
    ov = Overlay(2, 2, large_fraction=0.5)
    g = vmul_reduce_graph(64)                  # Reduce is LARGE
    ov.assemble(g)
    ov.assemble(saxpy_graph(64))
    ov.evict("saxpy")                          # open a hole so defrag tries
    ov.grid = TileGrid(2, 2, large_fraction=0.0)
    ov.fabric.grid = ov.grid
    with caplog.at_level(logging.WARNING, logger="repro.core.overlay"):
        assert ov.defragment() == 0
    assert ov.stats.defrag_failures == 1
    assert ov.stats.defrags == 0
    assert any("vmul_reduce" in rec.getMessage() for rec in caplog.records)
    assert ov.describe()["defrag_failures"] == 1


# ---------------------------------------------------------------------------
# tile-budget repacks and policy reconfigure ride on relocation
# ---------------------------------------------------------------------------
def test_tile_budget_repack_relocates_without_redownload():
    ov = Overlay(3, 3, large_fraction=0.0)
    g = saxpy_graph(64)
    acc = ov.assemble(g)                       # spreads over 2 tiles
    assert len(set(acc.placement.assignment.values())) == 2
    x = jnp.linspace(0.0, 1.0, 64)
    y0 = np.asarray(acc(x, x))
    ins = ov.cache.stats.insertions
    acc2 = ov.assemble(saxpy_graph(64), tile_budget=1)
    assert len(set(acc2.placement.assignment.values())) == 1
    assert ov.stats.relocations == 1
    assert ov.cache.stats.insertions == ins    # repack is not a download
    assert np.array_equal(np.asarray(acc2(x, x)), y0)
    res = ov.fabric.get(acc2.resident_id)
    assert res.tile_budget == 1
    # same budget again: no further move
    ov.assemble(saxpy_graph(64), tile_budget=1)
    assert ov.stats.relocations == 1


def test_jit_tile_budget_resize_relocates_in_place():
    # EventLoopEngine.resize() path: mutating a wrapper's tile_budget repacks
    # the live resident on the next dispatch — relocation, not re-download
    ov = Overlay(3, 3, large_fraction=0.0)
    jitted = ov.jit(lambda x, y: x * 2.0 + y, name="resizable", tile_budget=2)
    x = jnp.linspace(0.0, 1.0, 32)
    y0 = jitted(x, x)
    acc = jitted.accelerator(x, x)
    assert len(set(acc.placement.assignment.values())) == 2
    ins = ov.cache.stats.insertions
    jitted.tile_budget = 1                     # what EventLoopEngine.resize sets
    y1 = jitted(x, x)
    acc2 = jitted.accelerator(x, x)
    assert len(set(acc2.placement.assignment.values())) == 1
    assert ov.stats.relocations == 1
    assert ov.cache.stats.insertions == ins    # no re-download
    assert np.array_equal(np.asarray(y0), np.asarray(y1))


def test_reconfigure_relocate_keeps_residents_and_cache():
    ov = Overlay(3, 3)
    g1, g2 = vmul_reduce_graph(128), saxpy_graph(128)
    ov.assemble(g1)
    ov.assemble(g2)
    cached = len(ov.cache)
    ins = ov.cache.stats.insertions
    ov.reconfigure(policy=PlacementPolicy.STATIC, relocate=True)
    assert ov.policy is PlacementPolicy.STATIC
    assert len(ov.fabric) == 2                 # nothing flushed
    assert len(ov.cache) == cached             # bitstreams survive
    acc = ov.assemble(vmul_reduce_graph(128))  # resident hit, STATIC layout
    assert acc.placement.policy is PlacementPolicy.STATIC
    assert ov.cache.stats.insertions == ins    # zero re-downloads
    a = jnp.linspace(0.0, 1.0, 128)
    np.testing.assert_allclose(acc(a, a), jnp.sum(a * a), rtol=1e-6)


def test_reconfigure_relocate_evicts_only_unplaceable_residents():
    ov = Overlay(2, 2, large_fraction=0.5)
    big = vmul_reduce_graph(64)                # needs a LARGE tile
    small = saxpy_graph(64)
    ov.assemble(big)
    ov.assemble(small)
    ov.reconfigure(large_fraction=0.0, relocate=True)
    names = {r.name for r in ov.fabric.residents.values()}
    assert "saxpy" in names                    # placeable resident survived
    assert "vmul_reduce" not in names          # unplaceable one was evicted


# ---------------------------------------------------------------------------
# async pipeline: relocation commits are cheap, generation-guarded, and
# never queue behind (or cancel) full compiles
# ---------------------------------------------------------------------------
def _gate_downloads(ov):
    gate = threading.Event()
    orig = ov._compile_bitstream

    def gated(pending):
        gate.wait(30)
        return orig(pending)

    ov._compile_bitstream = gated
    return gate


def test_inflight_download_survives_relocation():
    ov = Overlay(2, 2, large_fraction=0.0, async_downloads=True)
    gate = _gate_downloads(ov)
    filler = saxpy_graph(32, alpha=1.0)
    filler.name = "filler"
    ov.assemble(filler)                        # sync path: no scheduler
    jitted = ov.jit(lambda x: x * 5.0 + 2.0, name="mover")
    x = jnp.ones((32,))
    y0 = jitted(x)                             # fallback; download gated
    assert ov.stats.fallback_calls == 1
    ov.evict("filler")
    assert ov.defragment() == 1                # relocates mid-download
    gate.set()                                 # compile lands POST-move
    assert ov.drain(30)
    # the placement-free kernel committed instead of being dropped
    assert ov.scheduler.stats.completed >= 1
    assert ov.scheduler.stats.dropped_stale == 0
    y1 = jitted(x)
    assert ov.stats.fallback_calls == 1        # dispatched to the bitstream
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    acc = jitted.accelerator(x)
    assert ov.resident_current(acc)


def test_async_defrag_rebinds_entries_without_fallback():
    ov = Overlay(2, 2, large_fraction=0.0, async_downloads=True)
    filler = saxpy_graph(32, alpha=3.0)
    filler.name = "filler"
    ov.assemble(filler)
    jitted = ov.jit(lambda x: x - 4.0, name="mover")
    x = jnp.ones((32,))
    y0 = jitted(x)
    assert ov.drain(60)                        # bitstream downloaded
    ov.evict("filler")
    assert ov.defragment() == 1                # priority rebind job submitted
    assert ov.drain(60)
    assert ov.scheduler.stats.priority_jobs >= 1
    fallbacks = ov.stats.fallback_calls
    ins = ov.cache.stats.insertions
    y1 = jitted(x)                             # already rebound: no fallback
    assert ov.stats.fallback_calls == fallbacks
    assert ov.cache.stats.insertions == ins
    assert np.array_equal(np.asarray(y0), np.asarray(y1))


# (the hypothesis property sweep lives in tests/test_relocation_property.py —
# importorskip("hypothesis") skips a whole module, and these deterministic
# tests must run even without the optional dependency)

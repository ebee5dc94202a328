"""Projections are local: a ``proj[i]`` node sits on its producer's tile.

A projection takes one element of a tuple-valued residue (a layer scan,
say).  Both placement policies put it on the residue's own tile, so the
edge into it has 0 hops at every placement and ``build_kernel`` lowers it
with no loop: even a zero-trip loop pins its operand's layout.  The
projection's out-edges are routed like any other, and the generic and
specialized tiers stay bit-identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Overlay, PlacementError, PlacementPolicy, TileGrid,
                        build_kernel, check_assignment, place, place_static,
                        route_hops, route_vector, specialize_kernel,
                        trace_to_graph)
from repro.core.graph import is_projection
from repro.core.placement import Placement


def _scan_fn(x, y):
    def body(c, _):
        a, b = c
        return (a * 1.5 + b, jnp.exp(b) - a), None

    (a, b), _ = jax.lax.scan(body, (x, y), None, length=3)
    return jnp.sqrt(a * a + 1.0) * b + x


def _graph():
    x = jnp.linspace(0.1, 1.0, 32, dtype=jnp.float32)
    y = jnp.linspace(-0.5, 0.5, 32, dtype=jnp.float32)
    return trace_to_graph(_scan_fn, x, y, name="scan_pair").graph, (x, y)


def _projections(g):
    projs = [n for n in g.op_nodes() if is_projection(n)]
    assert len(projs) == 2          # one per scan result
    return projs


def _placements(g, grid):
    ops = [n for n in g.op_nodes() if not is_projection(n)]
    coords = grid.coords()
    # every non-projection op pinned to a LARGE tile, spread apart
    pins = {n.node_id: coords[(4 * i) % len(coords)]
            for i, n in enumerate(ops)}
    return {
        "dynamic": place(g, grid, PlacementPolicy.DYNAMIC),
        "static-round-robin": place(g, grid, PlacementPolicy.STATIC),
        "static-pinned": place_static(g, grid, pins),
        "dynamic-one-tile": place(g, grid, PlacementPolicy.DYNAMIC,
                                  max_tiles=1),
    }


@pytest.mark.parametrize("which", ["dynamic", "static-round-robin",
                                   "static-pinned", "dynamic-one-tile"])
def test_projection_sits_on_its_producers_tile(which):
    g, _ = _graph()
    pl = _placements(g, TileGrid(4, 4))[which]
    routes = dict(zip(g.edges(), np.asarray(route_vector(g, pl)).tolist()))
    for n in _projections(g):
        (src,) = n.inputs
        assert pl.assignment[n.node_id] == pl.assignment[src]
        assert pl.edge_hops[(src, n.node_id)] == 0
        assert routes[(src, n.node_id)] == 0
    check_assignment(g, pl.grid, pl)


def test_projection_claims_no_tile_of_its_own():
    g, _ = _graph()
    pl = place(g, TileGrid(4, 4), PlacementPolicy.DYNAMIC)
    n_placed = len([n for n in g.op_nodes() if not is_projection(n)])
    assert len(set(pl.assignment.values())) == n_placed


def test_projection_pinned_off_its_producer_is_refused():
    g, _ = _graph()
    grid = TileGrid(4, 4)
    pl = _placements(g, grid)["static-pinned"]
    proj = _projections(g)[0]
    away = next(c for c in grid.coords()
                if c != pl.assignment[proj.node_id])
    pins = dict(pl.assignment)
    pins[proj.node_id] = away
    with pytest.raises(PlacementError, match="projection"):
        place_static(g, grid, pins)
    moved = Placement(grid, pl.policy, pins, pl.edge_hops)
    with pytest.raises(PlacementError, match="projection"):
        check_assignment(g, grid, moved)


def test_build_kernel_routes_no_projection_in_edge():
    g, xs = _graph()
    kernel = build_kernel(g)
    routes = jnp.zeros((len(g.edges()),), jnp.int32)
    jaxpr = jax.make_jaxpr(kernel)(routes, *xs).jaxpr
    by_id = {n.node_id: n for n in g.toposorted()}
    placed = {n.node_id for n in g.op_nodes()}
    routed = [(s, d) for s, d in g.edges()
              if s in placed and d in placed]
    # one hop loop per routed edge, none on an edge into a projection
    expect = sum(1 for s, d in routed if not is_projection(by_id[d]))
    assert expect > 0
    assert sum(e.primitive.name == "while" for e in jaxpr.eqns) == expect


@pytest.mark.parametrize("which", ["dynamic", "static-round-robin",
                                   "static-pinned"])
def test_tiers_bit_identical_with_projections(which):
    g, xs = _graph()
    pl = _placements(g, TileGrid(4, 4))[which]
    routes = route_vector(g, pl)
    generic = jax.jit(build_kernel(g))(routes, *xs)
    spec = jax.jit(specialize_kernel(g, route_hops(g, pl)))(routes, *xs)
    eager = _scan_fn(*xs)
    bits = lambda y: np.asarray(y).view(np.uint32)
    assert np.array_equal(bits(generic), bits(spec))
    np.testing.assert_allclose(np.asarray(generic), np.asarray(eager),
                               rtol=1e-6)


def test_relocating_a_scan_graph_keeps_one_executable():
    g, xs = _graph()
    ov = Overlay(4, 4)
    y0 = np.asarray(ov.assemble(g)(*xs))
    res = ov.fabric.get(ov.assemble(g).resident_id)
    ins = ov.cache.stats.insertions
    ov.relocate(g, place(g, ov.grid, ov.policy, occupied=set(res.tiles)))
    y1 = np.asarray(ov.assemble(g)(*xs))
    assert np.array_equal(y0.view(np.uint32), y1.view(np.uint32))
    assert ov.cache.stats.insertions == ins

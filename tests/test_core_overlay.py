"""Core overlay tests: patterns, graph, ISA, placement, interpreter, cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BitstreamCache, Opcode, Overlay, PlacementError,
                        PlacementPolicy, TileGrid, assemble, branchy_graph,
                        compile_graph, place, place_dynamic, place_static,
                        run_program, saxpy_graph, vmul_reduce_graph)
from repro.core import patterns
from repro.core.isa import (BRANCH_OPS, INTERCONNECT_OPS, MEMREG_OPS,
                            VECTOR_OPS)
from repro.core.placement import manhattan, route


# ---------------------------------------------------------------------------
# ISA invariants (paper §II: 42 instructions in 4 categories)
# ---------------------------------------------------------------------------
def test_isa_has_exactly_42_instructions_in_paper_categories():
    assert len(Opcode) == 42
    assert len(INTERCONNECT_OPS) == 22
    assert len(BRANCH_OPS) == 6
    assert len(VECTOR_OPS) == 2
    assert len(MEMREG_OPS) == 12


def test_isa_categories_partition_opcodes():
    seen = set()
    for group in (INTERCONNECT_OPS, BRANCH_OPS, VECTOR_OPS, MEMREG_OPS):
        assert not (seen & group)
        seen |= group
    assert seen == set(Opcode)


# ---------------------------------------------------------------------------
# Routing geometry
# ---------------------------------------------------------------------------
def test_route_excludes_endpoints_and_has_manhattan_length():
    a, b = (0, 0), (2, 2)
    path = route(a, b)
    assert a not in path and b not in path
    assert len(path) == manhattan(a, b) - 1


def test_route_adjacent_is_empty():
    assert route((1, 1), (1, 2)) == []
    assert route((1, 1), (0, 1)) == []


# ---------------------------------------------------------------------------
# Placement
# ---------------------------------------------------------------------------
def test_grid_large_fraction_quarter():
    grid = TileGrid(4, 4, large_fraction=0.25)
    assert len(grid.large_coords()) == 4      # 1/4 of 16 tiles


def test_dynamic_placement_is_contiguous_for_chain():
    g = vmul_reduce_graph(1024)
    pl = place_dynamic(g, TileGrid(3, 3))
    # the paper's claim: dynamic placement -> operators contiguous
    assert pl.total_passthrough == 0


def test_static_placement_pays_passthrough():
    g = vmul_reduce_graph(1024)
    ops = g.op_nodes()
    fixed = {ops[0].node_id: (0, 0), ops[1].node_id: (2, 2)}
    pl = place_static(g, TileGrid(3, 3), fixed)
    assert pl.total_passthrough == 3          # manhattan 4 -> 3 pass-throughs


def test_large_op_requires_large_tile():
    g = vmul_reduce_graph(64)
    ops = g.op_nodes()
    grid = TileGrid(3, 3)
    small = grid.small_coords()[0]
    fixed = {ops[0].node_id: (0, 1), ops[1].node_id: small}  # reduce is LARGE
    with pytest.raises(PlacementError):
        place_static(g, grid, fixed)


def test_placement_saturation_colocates():
    # more ops than tiles: 1x1 grid with everything LARGE-ok
    g = saxpy_graph(16)
    pl = place_dynamic(g, TileGrid(1, 1, large_fraction=1.0))
    assert pl.total_hops == 0                  # all co-located


# ---------------------------------------------------------------------------
# Assembly correctness vs direct evaluation (+ eager ISA)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("maker,num_inputs", [
    (vmul_reduce_graph, 2), (saxpy_graph, 2), (branchy_graph, 1)])
@pytest.mark.parametrize("policy", [PlacementPolicy.DYNAMIC,
                                    PlacementPolicy.STATIC])
def test_assembled_matches_direct(maker, num_inputs, policy):
    g = maker(512)
    key = jax.random.PRNGKey(42)
    inputs = tuple(jax.random.normal(k, (512,))
                   for k in jax.random.split(key, num_inputs))
    ref = g.evaluate(*inputs)
    pl = place(g, TileGrid(3, 3), policy)
    acc = assemble(g, pl)
    np.testing.assert_allclose(acc(*inputs), ref, rtol=2e-5, atol=2e-5)


def test_eager_isa_interpreter_matches_direct():
    g = vmul_reduce_graph(256)
    a = jnp.linspace(0, 1, 256)
    b = jnp.linspace(1, 2, 256)
    pl = place_dynamic(g, TileGrid(3, 3))
    prog = compile_graph(g, pl)
    out, st = run_program(prog, g, (a, b), return_state=True)
    np.testing.assert_allclose(out, g.evaluate(a, b), rtol=1e-6)
    assert st.executed == 2                    # VMUL + Reduce


def test_branchy_selects_correct_arm():
    g = branchy_graph(64)
    x_pos = jnp.ones((64,)) * 2.0              # sum > 0 -> sqrt(|x|)
    x_neg = -x_pos                             # sum < 0 -> sin(x)
    acc = Overlay(3, 3).assemble(g)
    np.testing.assert_allclose(acc(x_pos), jnp.sqrt(x_pos), rtol=1e-6)
    np.testing.assert_allclose(acc(x_neg), jnp.sin(x_neg), rtol=1e-6)


def test_program_mix_counts_categories():
    g = vmul_reduce_graph(128)
    pl = place_dynamic(g, TileGrid(3, 3))
    prog = compile_graph(g, pl)
    mix = prog.mix()
    assert sum(mix.values()) == len(prog)
    assert mix["vector"] == 2
    assert mix["memreg"] >= 4                  # 2 LD_STREAM, LD_TILEs, ST_STREAM


# ---------------------------------------------------------------------------
# BitstreamCache (PR overhead, C3)
# ---------------------------------------------------------------------------
def test_cache_hit_on_reassembly():
    ov = Overlay(3, 3)
    g = vmul_reduce_graph(128)
    ov.assemble(g)
    ov.assemble(g)
    assert ov.cache.stats.misses == 1
    assert ov.cache.stats.hits == 1


def test_reconfigurations_increment_on_placement_change():
    ov = Overlay(3, 3)
    ov.assemble(vmul_reduce_graph(128))
    assert ov.stats.reconfigurations == 0      # first placement: nothing prior
    ov.assemble(saxpy_graph(128))              # different graph -> new layout
    assert ov.stats.reconfigurations == 1
    ov.assemble(saxpy_graph(128))              # same layout -> no reconfig
    assert ov.stats.reconfigurations == 1


def test_describe_reports_cache_and_reconfigurations():
    ov = Overlay(3, 3)
    g = vmul_reduce_graph(128)
    ov.assemble(g)
    ov.assemble(g)
    d = ov.describe()
    assert d["assemblies"] == 2
    assert d["cache"]["hits"] == 1 and d["cache"]["misses"] == 1
    assert d["cached_bitstreams"] == 1
    assert d["reconfigurations"] == 0


def test_evict_frees_one_accelerators_bitstreams():
    ov = Overlay(3, 3)
    ov.assemble(vmul_reduce_graph(128))
    ov.assemble(saxpy_graph(128))
    assert len(ov.cache) == 2
    assert ov.evict("vmul_reduce") == 1
    assert len(ov.cache) == 1
    ov.assemble(vmul_reduce_graph(128))        # must re-download
    assert ov.cache.stats.misses == 3


def test_reconfigure_flushes_fabric_and_counts():
    ov = Overlay(3, 3)
    g = vmul_reduce_graph(128)
    ov.assemble(g)
    ov.reconfigure(policy=PlacementPolicy.STATIC)
    assert len(ov.cache) == 0
    assert ov.stats.reconfigurations == 1
    assert ov.policy is PlacementPolicy.STATIC
    acc = ov.assemble(g)
    assert acc.placement.policy is PlacementPolicy.STATIC


def test_cache_distinguishes_shapes():
    ov = Overlay(3, 3)
    ov.assemble(vmul_reduce_graph(128))
    ov.assemble(vmul_reduce_graph(256))
    assert ov.cache.stats.misses == 2


def test_cache_lru_eviction():
    c = BitstreamCache(capacity=2)
    c.get_or_compile("a", lambda: 1)
    c.get_or_compile("b", lambda: 2)
    c.get_or_compile("c", lambda: 3)
    assert "a" not in c and "b" in c and "c" in c
    assert c.stats.evictions == 1


def test_fragmentation_metric():
    g = saxpy_graph(64)                        # all SMALL ops
    grid = TileGrid(2, 2, large_fraction=0.5)
    ops = g.op_nodes()
    large = grid.large_coords()
    fixed = {n.node_id: large[i % len(large)] for i, n in enumerate(ops)}
    pl = place_static(g, grid, fixed)
    assert pl.fragmentation(g) == 1.0          # SMALL ops squat all LARGE tiles


def test_cache_clear_preserves_stats_like_evict_prefix():
    c = BitstreamCache(capacity=4)
    c.get_or_compile("a:1", lambda: 1)
    c.get_or_compile("a:1", lambda: 1)         # hit
    c.put("b:2", 2)
    assert c.stats.insertions == 2             # one miss-compile + one put
    c.clear()
    assert len(c) == 0
    assert c.stats.hits == 1 and c.stats.misses == 1
    assert c.stats.insertions == 2             # history survives the flush
    assert c.stats.evictions == 2              # a flush IS evictions


def test_cache_keys_and_evict_keys():
    c = BitstreamCache(capacity=4)
    c.put("x:1", 1)
    c.put("y:2", 2)
    c.put("x:3", 3)
    assert c.keys() == ["x:1", "y:2", "x:3"]
    assert c.evict_keys(["x:1", "not-there"]) == 1
    assert "x:1" not in c and len(c) == 2
    assert c.stats.evictions == 1


# ---------------------------------------------------------------------------
# Generic kernel: what the routes vector can and cannot move
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", list(PlacementPolicy))
def test_generic_kernel_streams_inputs_without_copies(policy):
    # graph inputs and constants are never placed, so no placement routes
    # them; the generic kernel must not push a weight through a hop loop,
    # whose carry XLA fills with a copy of the read-only argument
    from repro.core import build_kernel, route_vector, trace_to_graph
    x = jnp.ones((8, 256), jnp.float32)
    w = jnp.ones((256, 1024), jnp.float32)
    g = trace_to_graph(lambda x, w: jnp.tanh(x @ w) * 2.0 + 1.0, x, w).graph
    pl = place(g, TileGrid(3, 3), policy)
    routes = route_vector(g, pl)
    unplaced = {n.node_id for n in g.nodes if n.kind in ("input", "const")}
    for (src, _), h in zip(g.edges(), np.asarray(routes)):
        if src in unplaced:
            assert h == 0
    compiled = jax.jit(build_kernel(g)).lower(routes, x, w).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < w.nbytes
    np.testing.assert_allclose(np.asarray(compiled(routes, x, w)),
                               np.tanh(np.asarray(x) @ np.asarray(w)) * 2.0
                               + 1.0, rtol=1e-6)

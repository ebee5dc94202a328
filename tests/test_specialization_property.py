"""Hypothesis property sweep: route specialization preserves numerics.

For random DAGs, the route-constant specialized tier must be *bit-identical*
to the generic relocatable kernel — including the FMA-contraction-prone
mul→add adjacencies the exactness guard exists for — and a
specialize → relocate → despecialize cycle must end with zero drift and
zero new kernel-artifact insertions."""

import jax
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dep: property tests need hypothesis")
from hypothesis import given, settings, strategies as st

# hypothesis sweeps take minutes; the tier-1 CI lane skips them
pytestmark = pytest.mark.slow

from repro.core import Graph, Overlay, PlacementError, place
from repro.core import patterns

UNARY = [patterns.NEG, patterns.ABS, patterns.RELU, patterns.SQRT,
         patterns.EXP]
BINARY = [patterns.ADD, patterns.SUB, patterns.MUL, patterns.MAX, patterns.MIN]


@st.composite
def small_graph(draw):
    """A random DAG of unary/binary ops over positive inputs — biased
    toward mul/add adjacency (the contraction hazard)."""
    n_inputs = draw(st.integers(1, 3))
    n_ops = draw(st.integers(1, 6))
    size = draw(st.sampled_from([8, 32]))
    g = Graph("spec_prop")
    refs = [g.input(f"x{i}", (size,)) for i in range(n_inputs)]
    for _ in range(n_ops):
        if draw(st.booleans()) or len(refs) < 2:
            op = draw(st.sampled_from(UNARY))
            refs.append(g.apply(op, draw(st.sampled_from(refs))))
        else:
            op = draw(st.sampled_from(BINARY))
            refs.append(g.apply(op, draw(st.sampled_from(refs)),
                                draw(st.sampled_from(refs))))
    g.output(refs[-1])
    return g, size, n_inputs


@settings(max_examples=25, deadline=None)
@given(data=small_graph(), seed=st.integers(0, 2**31 - 1))
def test_specialization_bit_identical_property(data, seed):
    g, size, n_inputs = data
    ov = Overlay(4, 4, large_fraction=0.25)
    key = jax.random.PRNGKey(seed)
    xs = tuple(0.25 + jax.random.uniform(k, (size,))
               for k in jax.random.split(key, n_inputs))
    try:
        acc = ov.assemble(g)
    except PlacementError:
        return                                  # graph too large for 4x4
    y0 = np.asarray(jax.block_until_ready(acc(*xs)))

    res = ov.fabric.get(acc.resident_id)
    from repro.core import route_hops, route_vector, specialize_kernel
    hops = route_hops(g, res.placement)
    spec = jax.jit(specialize_kernel(g, hops))
    y1 = np.asarray(jax.block_until_ready(
        spec(route_vector(g, res.placement), *xs)))
    # compare bits, not values: a bit-identical NaN is still identical
    bits = lambda y: y.view(np.uint32)
    assert np.array_equal(bits(y0), bits(y1))   # bit-identical across tiers

    ins = ov.cache.stats.insertions
    try:
        new_pl = place(g, ov.grid, ov.policy, occupied=set(res.tiles))
    except PlacementError:
        return                                  # no disjoint placement exists
    ov.relocate(g, new_pl)
    y2 = np.asarray(jax.block_until_ready(ov.assemble(g)(*xs)))
    assert np.array_equal(bits(y0), bits(y2))   # zero drift through the cycle
    assert ov.cache.stats.insertions == ins     # zero new kernel insertions
    # re-specialize at the NEW placement: still bit-identical
    res2 = ov.fabric.get(acc.resident_id)
    spec2 = jax.jit(specialize_kernel(g, route_hops(g, res2.placement)))
    y3 = np.asarray(jax.block_until_ready(
        spec2(route_vector(g, res2.placement), *xs)))
    assert np.array_equal(bits(y0), bits(y3))

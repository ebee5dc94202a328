"""Runtime interpreter — executes controller programs to assemble accelerators.

Two execution modes, mirroring the paper's runtime:

1. **Eager ISA interpretation** (:func:`run_program`) — instruction-by-
   instruction execution with a register file, stack, and hop accounting.
   This is the debugging/verification mode (and the oracle the assembled
   accelerator is tested against).

2. **JIT assembly** (:func:`assemble` / :func:`assemble_sharded`) — the
   paper's contribution: the interpreter walks the program once and *builds*
   a fused accelerator.  Interconnect instructions become physical data
   movement:

   * local mode — each pass-through hop becomes a
     ``jax.lax.optimization_barrier`` so the hop is structurally present in
     the lowered HLO (XLA cannot fold the route away; hop cost is visible to
     the roofline layer);
   * sharded mode — each hop becomes a ``jax.lax.ppermute`` step along the
     device ring of a mesh axis, i.e. a *real* ICI nearest-neighbour
     transfer.  This reproduces Fig. 3: static placements with more
     pass-through tiles pay more ppermute hops; dynamic placement pays ~none.

Relocatable bitstreams: the compute body (:func:`build_kernel`) is
*placement-invariant* — it takes the per-edge hop counts as a runtime
``routes`` vector (:func:`route_vector`), so ONE compiled executable serves
every placement of a graph.  Moving a resident to new tiles re-emits only
the routes vector (and the controller route program); the expensive XLA
compile — the paper's PR bitstream download — is never repaid.

Tiered route specialization (DESIGN.md §7): the generic relocatable kernel
pays ``fori_loop``/``optimization_barrier`` *structure* on every edge even
when the placement is contiguous and all hop trip counts are zero at
runtime.  :func:`specialize_kernel` builds the second artifact tier — a
**route-constant** kernel in which the hop counts are baked in as Python
ints at trace time, so pass-through-free edges vanish entirely and XLA
fully fuses the body (the paper's application-specialized bitstream,
recovering "dynamic ≈ fully custom" on the steady-state serving path).
The specialized executable is valid for exactly one routes vector; any
relocation despecializes back to the always-correct generic kernel.

The assembled callable is pure and traceable: it can be jitted, differentiated,
lowered and AOT-compiled (then held in the BitstreamCache).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.graph import Graph, is_projection
from repro.core.isa import Opcode, Program, compile_graph
from repro.core.placement import Placement


# --------------------------------------------------------------------------
# Mode 1: eager ISA interpretation
# --------------------------------------------------------------------------
@dataclasses.dataclass
class MachineState:
    regs: dict[int, Any]
    stack: list[Any]
    hops: int = 0
    bypasses: int = 0
    executed: int = 0


_ROUTE_OPS = {
    Opcode.ROUTE_N_OUT, Opcode.ROUTE_E_OUT, Opcode.ROUTE_S_OUT, Opcode.ROUTE_W_OUT,
    Opcode.ROUTE_N_IN, Opcode.ROUTE_E_IN, Opcode.ROUTE_S_IN, Opcode.ROUTE_W_IN,
}
_BYPASS_OPS = {
    Opcode.BYPASS_NS, Opcode.BYPASS_SN, Opcode.BYPASS_EW, Opcode.BYPASS_WE,
    Opcode.BYPASS_NE, Opcode.BYPASS_NW, Opcode.BYPASS_SE, Opcode.BYPASS_SW,
}


def run_program(program: Program, graph: Graph, inputs: tuple, *,
                return_state: bool = False):
    """Execute a compiled program eagerly, one instruction at a time."""
    if len(inputs) != len(graph.input_ids):
        raise TypeError(f"expected {len(graph.input_ids)} inputs, got {len(inputs)}")
    st = MachineState(regs={}, stack=[])
    in_iter = iter(zip(graph.input_ids, inputs))
    nodes = {n.node_id: n for n in graph.toposorted()}
    outputs: list[Any] = []

    for ins in program.instructions:
        op = ins.opcode
        if op is Opcode.LD_STREAM:
            nid, val = next(in_iter)
            if nid != ins.dst:
                raise RuntimeError("input order mismatch")
            st.regs[nid] = val
        elif op is Opcode.LD_CONST:
            st.regs[ins.dst] = nodes[ins.dst].payload
        elif op in _ROUTE_OPS:
            st.hops += 1
        elif op in _BYPASS_OPS:
            st.bypasses += 1
        elif op is Opcode.LD_TILE:
            pass  # operands already in regs (BRAM modelled by the register file)
        elif op in (Opcode.VEXEC, Opcode.VEXEC_ACC):
            node = nodes[ins.dst]
            st.regs[ins.dst] = node.op.fn(*(st.regs[s] for s in ins.srcs))
            st.executed += 1
        elif op is Opcode.SELECT:
            p, t, e = (st.regs[s] for s in ins.srcs)
            st.regs[ins.dst] = jnp.where(p, t, e)
            st.executed += 1
        elif op is Opcode.SET_REG:
            pass  # value already latched by VEXEC
        elif op is Opcode.ST_STREAM:
            outputs.append(st.regs[ins.srcs[0]])
        elif op in (Opcode.SPEC_BEGIN, Opcode.SPEC_COMMIT, Opcode.BARRIER,
                    Opcode.FENCE, Opcode.LD_INSTR):
            pass
        elif op is Opcode.PUSH:
            st.stack.append(st.regs[ins.srcs[0]])
        elif op is Opcode.POP:
            st.regs[ins.dst] = st.stack.pop()
        elif op is Opcode.MOV:
            st.regs[ins.dst] = st.regs[ins.srcs[0]]
        else:  # pragma: no cover — remaining opcodes are placement-time only
            pass

    result = tuple(outputs)
    result = result[0] if len(result) == 1 else result
    return (result, st) if return_state else result


# --------------------------------------------------------------------------
# Mode 2: JIT assembly
# --------------------------------------------------------------------------
@dataclasses.dataclass
class AssembledAccelerator:
    """The product of JIT assembly: a fused callable plus its provenance."""

    name: str
    fn: Callable[..., Any]          # pure, traceable
    program: Program
    placement: Placement
    total_hops: int
    instruction_mix: dict[str, int]
    # residency handle (set by Overlay.assemble): which Fabric resident this
    # executable belongs to, and at which admission generation.  A stale
    # generation means the accelerator's PR regions were reclaimed — callers
    # (JitAssembled) re-assemble instead of running off released tiles.
    resident_id: str | None = None
    generation: int = -1
    # relocatable-bitstream split: ``kernel(routes, *inputs)`` is the
    # placement-invariant compute body; ``routes`` is this placement's
    # per-edge hop vector.  ``fn`` == kernel with routes bound.
    kernel: Callable[..., Any] | None = None
    routes: Any = None
    # artifact tier this accelerator dispatches to: "generic" (relocatable,
    # routes as a runtime argument) or "specialized" (route-constant)
    tier: str = "generic"

    def __call__(self, *args):
        return self.fn(*args)


def edge_order(graph: Graph) -> list[tuple[int, int]]:
    """Canonical (src, dst) order of every dataflow edge — the index space
    of the ``routes`` vector.  Depends only on the graph, never on a
    placement; delegates to :meth:`Graph.edges` so there is exactly one
    definition of the ordering."""
    return graph.edges()


def route_vector(graph: Graph, placement: Placement) -> Any:
    """The per-placement route program's data half: an int32 vector of
    Manhattan hop counts, one per edge in :func:`edge_order` order.  This —
    not the compiled executable — is all that changes when a resident moves."""
    hops = placement.edge_hops
    return jnp.asarray([hops.get(e, 0) for e in edge_order(graph)],
                       dtype=jnp.int32)


def bind_routes(kernel: Callable[..., Any], routes: Any) -> Callable[..., Any]:
    """Close a placement-invariant kernel over one placement's routes."""
    return partial(kernel, routes)


def route_hops(graph: Graph, placement: Placement) -> tuple[int, ...]:
    """The routes vector as host Python ints (same :func:`edge_order` order)
    — the constant half a route-specialized kernel bakes in at trace time."""
    hops = placement.edge_hops
    return tuple(int(hops.get(e, 0)) for e in edge_order(graph))


def zero_hop(hops: "tuple[int, ...] | Any") -> bool:
    """Whether a hop vector implies NO pass-through work: every edge is
    co-located (0) or nearest-neighbour (1), so each generic ``fori_loop``
    runs zero trips.  This is the contiguous steady state ``defragment()``
    produces — the placements where route specialization deletes every last
    bit of routing structure from the compiled body."""
    return all(int(h) <= 1 for h in hops)


def _dyn_barrier_hops(v, h):
    """Local mode: one *physical copy pass* per pass-through tile (h-1 for a
    h-hop route).  An FPGA pass-through tile registers and forwards the
    stream — one full pass over the data with no compute — modelled as a
    multiply by an opaque 1.0 (``optimization_barrier`` makes the scalar
    opaque so XLA can neither fold the multiply nor fuse across it).
    ``h`` is a *traced* scalar from the routes vector, so the loop lowers to
    a ``fori_loop`` whose trip count the placement supplies at dispatch time
    — the compiled body is placement-invariant.  ``v`` may be a pytree
    (tuple-valued residue nodes): the whole bundle crosses the tile."""
    def one_leaf(leaf):
        def body(_, x):
            one = jax.lax.optimization_barrier(jnp.ones((), x.dtype))
            return jax.lax.optimization_barrier(x * one)
        return jax.lax.fori_loop(0, jnp.maximum(h - 1, 0), body, leaf)
    return jax.tree.map(one_leaf, v)


def _dyn_ici_hops(axis: str, n_dev: int) -> Callable[[Any, Any], Any]:
    """Sharded mode: ``h`` forward ``ppermute`` ring steps (the pass-through
    latency actually paid) and one shift-by--h return permute picked by a
    ``switch`` over the ring's static permutations, all driven by the traced
    hop count — one compiled collective program serves every placement."""
    ring = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def back_branch(k: int):
        if k == 0:
            return lambda x: x
        perm = [(i, (i - k) % n_dev) for i in range(n_dev)]
        return lambda x: jax.lax.ppermute(x, axis, perm=perm)

    branches = [back_branch(k) for k in range(n_dev)]

    def hop_fn(v, h):
        def one_leaf(leaf):
            leaf = jax.lax.fori_loop(
                0, h, lambda _, x: jax.lax.ppermute(x, axis, perm=ring), leaf)
            # return to origin so downstream ops see position-independent
            # data; the forward hops already paid the pass-through latency
            return jax.lax.switch(jnp.mod(h, n_dev), branches, leaf)
        return jax.tree.map(one_leaf, v)

    return hop_fn


def _name_after(kernel: Callable[..., Any], name: str) -> None:
    """Name a kernel after the function it assembles: ``jax.jit`` names the
    executable ``jit_<name>``, so a profile's device ops say which jitted
    function, and which tier, they ran for."""
    kernel.__name__ = kernel.__qualname__ = name


def build_kernel(graph: Graph, *,
                 hop_fn: Callable[[Any, Any], Any] | None = None
                 ) -> Callable[..., Any]:
    """The placement-invariant compute body: ``kernel(routes, *inputs)``.

    Walks the DFG once and returns a traceable fn in which every dataflow
    edge's hop cost is looked up in the runtime ``routes`` vector
    (:func:`route_vector`).  Compiling this kernel produces ONE executable
    valid for *every* placement of ``graph`` — the TPU analogue of the
    paper's pre-synthesized bitstream being downloadable into any compatible
    PR region.  Relocation swaps the routes vector; the executable stays.

    Only edges between two placed nodes carry a hop: graph inputs and
    constants are never placed, so their edges stream straight in for every
    placement (their routes entries are always 0).  Routing them anyway
    would push every weight through a loop carry, and XLA copies a
    read-only argument into each carry: at published widths that is one
    extra copy of the model's weights per call.  A projection sits on its
    producer's tile (both placement policies put it there), so the edge
    into it is local too and takes its element with no loop: even a
    zero-trip loop pins its operand's layout, and on a tuple-valued
    residue such as the layer scan that forced a relayout copy of the
    whole KV cache.  The projection's out-edges are routed as usual.
    """
    nodes = graph.toposorted()
    eidx = {e: i for i, e in enumerate(edge_order(graph))}
    hop = hop_fn or _dyn_barrier_hops
    unplaced = {n.node_id for n in nodes if n.kind in ("input", "const")}

    def kernel(routes, *inputs):
        vals: dict[int, Any] = dict(zip(graph.input_ids, inputs))
        for n in nodes:
            if n.kind == "input":
                continue
            if n.kind == "const":
                vals[n.node_id] = n.payload
                continue
            if is_projection(n):
                (src,) = n.inputs                # a multi-result residue
                vals[n.node_id] = n.op.fn(vals[src])
                continue
            route = lambda src: routes[eidx[(src, n.node_id)]]
            args = [vals[src] if src in unplaced else hop(vals[src], route(src))
                    for src in n.inputs]
            if n.kind == "op":
                vals[n.node_id] = n.op.fn(*args)
            elif n.kind == "select":
                p, t, e = args
                vals[n.node_id] = jnp.where(p, t, e)
        outs = tuple(vals[i] for i in graph.output_ids)
        return outs[0] if len(outs) == 1 else outs

    _name_after(kernel, graph.name)
    return kernel


def _opaque_one(routes) -> Any:
    """An f32 scalar that is exactly 1.0 at runtime but OPAQUE to every
    compiler layer: derived from the runtime ``routes`` argument through
    float arithmetic (``convert(r0) * 0.0 + 1.0``) that neither XLA's
    simplifier nor LLVM may fold (``x * 0.0`` is not an identity under
    IEEE; routes are ints, so the result can never be NaN/Inf-poisoned).
    See :func:`_static_barrier_hops` for why specialization needs it."""
    return routes[0].astype(jnp.float32) * 0.0 + 1.0


def _static_barrier_hops(one) -> Callable[[Any, int, bool], Any]:
    """Route-constant local mode: ``h`` is a Python int at trace time, so
    the generic tier's per-edge ``fori_loop``/dynamic-trip-count carcass is
    gone and XLA fuses the whole body into one kernel.  Pass-through-free
    edges (``h <= 1``) shrink to the exactness guard; ``h >= 2`` edges keep
    their h-1 physical copy passes (the pass-through cost model), now
    statically unrolled.

    The guard preserves bit-identity across tiers: the generic kernel's
    zero-trip loops are *fusion boundaries*.  Fused straight across an
    edge, LLVM contracts a cross-node ``mul``+``add`` into an FMA and
    XLA's simplifier rewrites cross-node patterns (``exp(a)*exp(b)`` into
    ``exp(a+b)``, seeing through ``max(x, x)``), each drifting by ULPs.
    So every edge out of a computed node multiplies by ``one`` — the
    runtime-opaque exact 1.0: no pattern matches through it, and a
    contraction computes ``fma(x, 1.0, c) == round(x + c)``, exact.  The
    fused specialized body reproduces the generic tier bit for bit.
    Non-float edges need no guard."""
    def hop_fn(v, h: int, guard: bool):
        def one_leaf(leaf):
            if not jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
                return leaf
            passes = h - 1 if h >= 2 else (1 if guard else 0)
            if passes:
                edge_one = one.astype(leaf.dtype)
                for _ in range(passes):
                    leaf = leaf * edge_one
            return leaf

        return jax.tree.map(one_leaf, v)

    return hop_fn


def _static_ici_hops(one, axis: str, n_dev: int
                     ) -> Callable[[Any, int, bool], Any]:
    """Route-constant sharded mode: ``h`` is static, so the forward ring
    walk unrolls and the return permute is ONE static ``ppermute`` (no
    ``fori_loop``, no ``switch`` over every possible shift).  A zero-hop
    guarded edge keeps the opaque-one multiply (the generic tier's
    ``switch`` is a fusion boundary there; see
    :func:`_static_barrier_hops`); hopped edges end in a ``ppermute``,
    a boundary in both tiers."""
    ring = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def hop_fn(v, h: int, guard: bool):
        def one_leaf(leaf):
            if h == 0:
                if guard and jnp.issubdtype(jnp.result_type(leaf),
                                            jnp.floating):
                    leaf = leaf * one.astype(leaf.dtype)
                return leaf
            for _ in range(h):
                leaf = jax.lax.ppermute(leaf, axis, perm=ring)
            k = h % n_dev
            if k:
                back = [(i, (i - k) % n_dev) for i in range(n_dev)]
                leaf = jax.lax.ppermute(leaf, axis, perm=back)
            return leaf

        return jax.tree.map(one_leaf, v)

    return hop_fn


def specialize_kernel(graph: Graph, hops: "tuple[int, ...]", *,
                      hop_factory: "Callable[[Any], Callable[[Any, int], Any]] | None" = None
                      ) -> Callable[..., Any]:
    """The route-CONSTANT compute body — the specialized artifact tier.

    Same DFG walk and calling convention as :func:`build_kernel`
    (``kernel(routes, *inputs)``), but every edge's hop count is the Python
    int ``hops[edge_index]`` (:func:`route_hops`) baked in at trace time:
    no hop count is ever READ from the runtime routes vector, so the
    ``fori_loop`` routing structure vanishes and XLA fuses the whole body.
    The routes argument survives only as the seed of the opaque exact-1.0
    guarding every edge out of a computed node (see
    :func:`_static_barrier_hops`).  Keeping one calling convention across
    tiers also means
    donation kwargs, route binding and dispatch records need no per-tier
    cases.

    The compiled executable is the paper's *application-specialized*
    bitstream: valid for exactly one hop vector, bit-identical to the
    generic relocatable kernel, and despecialized (dropped) the moment the
    resident's routes change.
    """
    nodes = graph.toposorted()
    by_id = {n.node_id: n for n in nodes}
    order = edge_order(graph)
    if len(hops) != len(order):
        raise ValueError(
            f"hop vector has {len(hops)} entries for {len(order)} edges")
    static_hops = {e: int(h) for e, h in zip(order, hops)}
    # graph inputs and constants are never fused with a producer, and a
    # projection's in-edge is local in the generic tier too
    guards = {e: by_id[e[0]].kind not in ("input", "const")
              and not is_projection(by_id[e[1]]) for e in order}
    needs_one = any(g or static_hops[e] >= 2 for e, g in guards.items())
    factory = hop_factory or _static_barrier_hops

    def kernel(routes, *inputs):
        hop = factory(_opaque_one(routes) if needs_one else None)
        vals: dict[int, Any] = dict(zip(graph.input_ids, inputs))
        for n in nodes:
            if n.kind == "input":
                continue
            if n.kind == "const":
                vals[n.node_id] = n.payload
                continue
            if is_projection(n):
                (src,) = n.inputs
                vals[n.node_id] = n.op.fn(vals[src])
                continue
            args = []
            for src in n.inputs:
                e = (src, n.node_id)
                args.append(hop(vals[src], static_hops[e], guards[e]))
            if n.kind == "op":
                vals[n.node_id] = n.op.fn(*args)
            elif n.kind == "select":
                p, t, e = args
                vals[n.node_id] = jnp.where(p, t, e)
        outs = tuple(vals[i] for i in graph.output_ids)
        return outs[0] if len(outs) == 1 else outs

    _name_after(kernel, f"{graph.name}.specialized")
    return kernel


def assemble(graph: Graph, placement: Placement, *,
             program: Program | None = None,
             routes: Any = None) -> AssembledAccelerator:
    """JIT-assemble the accelerator for single-device execution.

    The returned accelerator carries the placement-invariant ``kernel`` and
    this placement's ``routes`` separately; ``fn`` is the bound pair."""
    graph.validate()
    program = program or compile_graph(graph, placement)
    kernel = build_kernel(graph)
    if routes is None:
        routes = route_vector(graph, placement)
    return AssembledAccelerator(
        name=graph.name, fn=bind_routes(kernel, routes), program=program,
        placement=placement, total_hops=placement.total_hops,
        instruction_mix=program.mix(), kernel=kernel, routes=routes)


def assemble_sharded(graph: Graph, placement: Placement, mesh: jax.sharding.Mesh,
                     axis: str = "tiles",
                     program: Program | None = None,
                     routes: Any = None) -> AssembledAccelerator:
    """JIT-assemble with *real* ICI transfers: each hop = one ``ppermute``
    along the device ring of ``axis``.

    All devices execute the operator SPMD-style (TPUs cannot gate per-chip
    programs the way PR tiles differ), but every dataflow edge whose endpoints
    are k tiles apart physically moves its operand k nearest-neighbour steps —
    the exact cost structure of the paper's pass-through tiles.  The returned
    fn must be called under ``shard_map``/``jax.jit`` with ``mesh`` active;
    use :func:`wrap_sharded` for a ready-to-call jitted version.
    """
    graph.validate()
    program = program or compile_graph(graph, placement)
    kernel = build_kernel(graph, hop_fn=_dyn_ici_hops(axis, mesh.shape[axis]))
    if routes is None:
        routes = route_vector(graph, placement)
    return AssembledAccelerator(
        name=f"{graph.name}@{axis}", fn=bind_routes(kernel, routes),
        program=program, placement=placement,
        total_hops=placement.total_hops, instruction_mix=program.mix(),
        kernel=kernel, routes=routes)


def wrap_sharded_kernel(acc: AssembledAccelerator, graph: Graph,
                        mesh: jax.sharding.Mesh) -> Callable[..., Any]:
    """shard_map + jit the *placement-invariant* kernel: the result takes
    ``(routes, *inputs)`` — the relocatable artifact the overlay caches.

    In/out are replicated: the overlay streams whole vectors *through* tiles;
    it does not shard the data (data sharding belongs to the model layer).
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    n_in = len(graph.input_ids)
    smapped = shard_map(
        acc.kernel, mesh=mesh, in_specs=(P(),) * (n_in + 1), out_specs=P(),
        check_vma=False)
    return jax.jit(smapped)


def wrap_sharded(acc: AssembledAccelerator, graph: Graph,
                 mesh: jax.sharding.Mesh) -> Callable[..., Any]:
    """Ready-to-call jitted sharded accelerator for ``acc``'s own placement
    (the routes-bound convenience over :func:`wrap_sharded_kernel`)."""
    return bind_routes(wrap_sharded_kernel(acc, graph, mesh), acc.routes)


def wrap_sharded_specialized(graph: Graph, hops: "tuple[int, ...]",
                             mesh: jax.sharding.Mesh,
                             axis: str = "tiles") -> Callable[..., Any]:
    """shard_map + jit the route-CONSTANT kernel — the specialized artifact
    tier for a sharded overlay: takes ``(routes, *inputs)`` like the
    generic tier, but each static hop is an unrolled ``ppermute`` (no
    ``fori_loop``, no return ``switch``)."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    n_dev = mesh.shape[axis]
    kernel = specialize_kernel(
        graph, hops,
        hop_factory=lambda one: _static_ici_hops(one, axis, n_dev))
    n_in = len(graph.input_ids)
    smapped = shard_map(kernel, mesh=mesh, in_specs=(P(),) * (n_in + 1),
                        out_specs=P(), check_vma=False)
    return jax.jit(smapped)

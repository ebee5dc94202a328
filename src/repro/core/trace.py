"""Tracing frontend — plain JAX functions become overlay accelerators (C1).

The paper's programmers write *ordinary source code with symbolic links to
library patterns*; the runtime resolves those links and JIT-assembles the
accelerator.  This module is the resolution step: :func:`trace_to_graph`
captures a plain Python/``jnp`` function with ``jax.make_jaxpr`` and lowers
each jaxpr equation onto :mod:`repro.core.patterns` library operators through
the pluggable primitive registry (``patterns.register_op``), producing the
existing :class:`~repro.core.graph.Graph` as IR.  From there the usual
pipeline applies: placement -> controller ISA -> JIT assembly -> bitstream
cache.

Lowering policy, per equation:

1. ``select_n`` with two cases becomes a :meth:`Graph.select` node — the
   overlay's *speculative branch* (both arms execute, predicate picks; C4).
2. Call primitives (``pjit``, ``custom_vjp_call_jaxpr``, ``remat``, ...):
   if the callee name is a registered kernel call (``patterns.register_call``
   — how ``kernels/`` exposes its Pallas bitstreams) the whole call becomes
   ONE LARGE node; otherwise the sub-jaxpr is inlined and lowered recursively.
3. The primitive registry is consulted (``mul``/``add``/``reduce_sum``/
   ``sqrt``/``dot_general``/...).
4. Anything unmapped is either an error (``strict=True``) or *fused-XLA
   residue*: the equation is wrapped as one SMALL operator that re-binds the
   original primitive, so the accelerator stays correct and XLA fuses the
   residue into neighbouring tiles.  Residue primitives are recorded on the
   returned :class:`Lowered` for inspection.

Multi-result residue equations (``scan``, ``while``, ...) lower to one tuple-
valued node plus per-result ``proj[i]`` nodes, keeping the Graph single-value
per edge.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable

import jax

from repro.compat import ClosedJaxpr, DropVar, Jaxpr, Literal
from repro.core import patterns
from repro.core.graph import PROJ_PREFIX, Graph, NodeRef
from repro.core.patterns import Operator, TileClass

RESIDUE_PREFIX = "xla["

# call-style primitives whose sub-jaxpr we inline (NOT loop/branch primitives
# like scan/while/cond, whose sub-jaxprs have different calling conventions —
# those stay residue), and the params keys that may hold the sub-jaxpr
_CALL_PRIMITIVES = frozenset({
    "pjit", "jit", "closed_call", "core_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
})
_CALL_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


class TraceError(RuntimeError):
    """A primitive could not be lowered onto the operator library."""


@dataclasses.dataclass
class Lowered:
    """The product of tracing: a Graph plus calling-convention metadata."""

    graph: Graph
    in_tree: Any                  # PyTreeDef of the (dynamic) argument tuple
    out_tree: Any                 # PyTreeDef of the function result
    in_avals: tuple               # flat abstract inputs, jaxpr order
    unmapped: tuple[str, ...]     # primitive names left as XLA residue

    @property
    def num_residue(self) -> int:
        return len(self.unmapped)


def _as_closed(obj) -> ClosedJaxpr | None:
    if isinstance(obj, ClosedJaxpr):
        return obj
    if isinstance(obj, Jaxpr):
        return ClosedJaxpr(obj, ())
    return None


def _callee(eqn) -> tuple[ClosedJaxpr | None, str | None]:
    """Extract (sub_jaxpr, callee_name) from a call-style equation."""
    if eqn.primitive.name not in _CALL_PRIMITIVES:
        return None, None
    for key in _CALL_JAXPR_PARAMS:
        closed = _as_closed(eqn.params.get(key))
        if closed is not None:
            return closed, eqn.params.get("name")
    return None, None


def _residue_operator(eqn) -> Operator:
    """Wrap an unmapped equation as a fused-XLA residue operator."""
    prim, params = eqn.primitive, dict(eqn.params)

    def fn(*xs, _p=prim, _params=params):
        out = _p.bind(*xs, **_params)
        return tuple(out) if _p.multiple_results else out

    # two residues of the same primitive with different params (e.g. two
    # different scan bodies) must not alias in the bitstream cache
    sig = hashlib.sha256(repr(sorted(
        (k, str(v)) for k, v in params.items())).encode()).hexdigest()[:12]
    return Operator(name=f"{RESIDUE_PREFIX}{prim.name}]", arity=len(eqn.invars),
                    fn=fn, tile_class=TileClass.SMALL, signature=sig)


def _projection(i: int) -> Operator:
    return Operator(name=f"{PROJ_PREFIX}{i}]", arity=1,
                    fn=lambda t, _i=i: t[_i],
                    tile_class=TileClass.SMALL, flops_per_elem=0.0)


class _Lowering:
    def __init__(self, graph: Graph, strict: bool):
        self.g = graph
        self.strict = strict
        self.unmapped: list[str] = []

    def _ref(self, env: dict, atom) -> NodeRef:
        if isinstance(atom, Literal):
            return self.g.const(atom.val, name="lit")
        return NodeRef(self.g, env[atom])

    def _set_aval(self, node_id: int, aval) -> None:
        # record the jaxpr-known output aval so the finished graph can
        # seal_shapes() instead of re-deriving every node via eval_shape
        self.g.nodes[node_id].aval = jax.ShapeDtypeStruct(aval.shape,
                                                          aval.dtype)

    def lower_eqns(self, env: dict, eqns) -> None:
        for eqn in eqns:
            prim = eqn.primitive.name
            refs = [self._ref(env, v) for v in eqn.invars]
            in_avals = tuple(v.aval for v in eqn.invars)

            # 1. speculative branch (C4): select_n(pred, on_false, on_true)
            if prim == "select_n" and len(refs) == 3 and len(eqn.outvars) == 1:
                nid = self.g.select(refs[0], refs[2], refs[1]).node_id
                self._set_aval(nid, eqn.outvars[0].aval)
                env[eqn.outvars[0]] = nid
                continue

            # 2. call primitives: registered Pallas bitstream, or inline
            sub, callee = _callee(eqn)
            if sub is not None:
                op = patterns.lookup_call(callee) if callee else None
                if op is not None and len(eqn.outvars) == 1:
                    # one opaque LARGE node; identity/tile-class come from the
                    # registration, the computation stays the equation's own
                    # sub-jaxpr (so non-default kernel kwargs remain correct)
                    res = _residue_operator(eqn)
                    fn = res.fn
                    if eqn.primitive.multiple_results:  # pjit: 1-elem tuple
                        fn = lambda *xs, _b=res.fn: _b(*xs)[0]
                    node_op = dataclasses.replace(
                        res, name=op.name, fn=fn, tile_class=op.tile_class,
                        flops_per_elem=op.flops_per_elem)
                    nid = self.g.apply(node_op, *refs).node_id
                    self._set_aval(nid, eqn.outvars[0].aval)
                    env[eqn.outvars[0]] = nid
                    continue
                if len(sub.jaxpr.invars) == len(refs):
                    inner: dict = {}
                    for var, ref in zip(sub.jaxpr.invars, refs):
                        inner[var] = ref.node_id
                    for var, val in zip(sub.jaxpr.constvars, sub.consts):
                        inner[var] = self.g.const(val, name="const").node_id
                    self.lower_eqns(inner, sub.jaxpr.eqns)
                    for outvar, res in zip(eqn.outvars, sub.jaxpr.outvars):
                        if isinstance(outvar, DropVar):
                            continue
                        env[outvar] = self._ref(inner, res).node_id
                    continue
                # arity mismatch (e.g. hoisted consts) — fall through to residue

            # 3. primitive registry dispatch
            rule = patterns.lookup_primitive(prim)
            op = rule(in_avals, eqn.params) if rule is not None else None
            if (op is not None and op.arity == len(refs)
                    and len(eqn.outvars) == 1):
                nid = self.g.apply(op, *refs).node_id
                self._set_aval(nid, eqn.outvars[0].aval)
                env[eqn.outvars[0]] = nid
                continue

            # 4. unmapped: strict error or fused-XLA residue
            if self.strict:
                raise TraceError(
                    f"primitive {prim!r} has no operator-library lowering "
                    f"(strict mode). Register one with patterns.register_op"
                    f"({prim!r}, ...) or trace with strict=False to leave it "
                    f"as fused XLA residue. Registered primitives: "
                    f"{patterns.registered_primitives()}")
            self.unmapped.append(prim)
            node = self.g.apply(_residue_operator(eqn), *refs)
            if eqn.primitive.multiple_results:
                # tuple-valued residue node: its aval is the tuple of all
                # result avals (what the re-bound primitive returns)
                self.g.nodes[node.node_id].aval = tuple(
                    jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                    for v in eqn.outvars)
                for i, outvar in enumerate(eqn.outvars):
                    if isinstance(outvar, DropVar):
                        continue
                    pid = self.g.apply(_projection(i), node).node_id
                    self._set_aval(pid, outvar.aval)
                    env[outvar] = pid
            else:
                self._set_aval(node.node_id, eqn.outvars[0].aval)
                env[eqn.outvars[0]] = node.node_id


def trace_to_graph(fn: Callable[..., Any], *args, name: str | None = None,
                   strict: bool = False) -> Lowered:
    """Capture ``fn`` at the abstract shapes of ``args`` and lower it to a
    :class:`Graph`.

    Args:
      fn: any JAX-traceable callable; arguments may be arbitrary pytrees.
      *args: concrete arrays or ``jax.ShapeDtypeStruct`` pytrees fixing the
        trace signature (exactly like ``jax.jit`` lowering).
      name: graph name (defaults to ``fn.__name__``).
      strict: error on primitives without a library lowering instead of
        leaving them as fused XLA residue.

    Returns:
      A :class:`Lowered` carrying the graph plus pytree/calling metadata.
    """
    _, in_tree = jax.tree_util.tree_flatten(args)
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    _, out_tree = jax.tree_util.tree_flatten(out_shape)

    g = Graph(name or getattr(fn, "__name__", None) or "traced")
    lowering = _Lowering(g, strict)
    env: dict = {}
    for i, var in enumerate(closed.jaxpr.invars):
        ref = g.input(f"arg{i}", var.aval.shape, var.aval.dtype)
        env[var] = ref.node_id
    for var, val in zip(closed.jaxpr.constvars, closed.consts):
        env[var] = g.const(val, name="closure_const").node_id

    lowering.lower_eqns(env, closed.jaxpr.eqns)
    g.output(*[lowering._ref(env, v) for v in closed.jaxpr.outvars])
    # every node carries its jaxpr-known aval: skip the eval_shape sweep
    # (validate() on multi-hundred-node traced model graphs was costing
    # ~1 ms/node on the assembly critical path)
    g.seal_shapes()

    return Lowered(graph=g, in_tree=in_tree, out_tree=out_tree,
                   in_avals=tuple(v.aval for v in closed.jaxpr.invars),
                   unmapped=tuple(lowering.unmapped))

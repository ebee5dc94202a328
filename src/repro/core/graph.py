"""Dataflow graph of pattern instances — the "symbolic link" composition API.

The paper's programmers write source code containing *symbolic links* to library
patterns; compilation turns those links into interpreter instructions.  Here the
same role is played by a :class:`Graph`: a static DAG whose nodes are
:class:`~repro.core.patterns.Operator` instances and whose edges are tensor
dataflow.  ``Graph`` is pure metadata — no tensors are touched until the
interpreter assembles it (``interpreter.py``) under a placement
(``placement.py``).

Conditional branching (paper §II, C4) is expressed with ``select`` nodes: both
branches are *speculatively* evaluated and the predicate picks the result —
the TPU-idiomatic equivalent of the overlay's speculative contiguous-tile
branching (documented in DESIGN.md §2).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import patterns
from repro.core.patterns import Operator


@dataclasses.dataclass(frozen=True)
class NodeRef:
    """Handle to a graph node's output (what user code passes around)."""

    graph: "Graph"
    node_id: int

    def __add__(self, other: "NodeRef") -> "NodeRef":
        return self.graph.apply(patterns.ADD, self, other)

    def __mul__(self, other: "NodeRef") -> "NodeRef":
        return self.graph.apply(patterns.MUL, self, other)

    def __sub__(self, other: "NodeRef") -> "NodeRef":
        return self.graph.apply(patterns.SUB, self, other)


# name prefix of the operator that takes one element of a tuple-valued node
# (``core/trace.py`` makes one per result of a multi-result residue)
PROJ_PREFIX = "proj["


@dataclasses.dataclass
class Node:
    node_id: int
    kind: str                      # "input" | "const" | "op" | "select" | "output"
    op: Operator | None            # for kind == "op"
    inputs: tuple[int, ...]        # node ids feeding this node
    name: str                      # display / placement name
    aval: Any = None               # jax.ShapeDtypeStruct, filled by infer_shapes
    payload: Any = None            # const value for kind == "const"


def is_projection(node: Node) -> bool:
    """Whether ``node`` takes one element of its producer's tuple result."""
    return node.kind == "op" and node.op.name.startswith(PROJ_PREFIX)


class Graph:
    """A DAG of operator applications, built through a symbolic API.

    >>> g = Graph("dot")
    >>> a = g.input("a", (1024,), jnp.float32)
    >>> b = g.input("b", (1024,), jnp.float32)
    >>> s = g.apply(patterns.make_reduce(patterns.ADD), a * b)
    >>> g.output(s)
    """

    def __init__(self, name: str = "graph") -> None:
        self.name = name
        self.nodes: list[Node] = []
        self.input_ids: list[int] = []
        self.output_ids: list[int] = []
        self._shape_cache: dict[int, Any] | None = None

    # --- construction -------------------------------------------------------
    def _add(self, kind: str, op: Operator | None, inputs: Sequence[NodeRef | int],
             name: str, payload: Any = None) -> NodeRef:
        ids = tuple(i.node_id if isinstance(i, NodeRef) else int(i) for i in inputs)
        for i in ids:
            if not (0 <= i < len(self.nodes)):
                raise ValueError(f"dangling input node id {i}")
        node = Node(node_id=len(self.nodes), kind=kind, op=op, inputs=ids,
                    name=name, payload=payload)
        self.nodes.append(node)
        self._shape_cache = None
        return NodeRef(self, node.node_id)

    def input(self, name: str, shape: Sequence[int], dtype=jnp.float32) -> NodeRef:
        ref = self._add("input", None, (), name)
        self.nodes[ref.node_id].aval = jax.ShapeDtypeStruct(tuple(shape), dtype)
        self.input_ids.append(ref.node_id)
        return ref

    def input_tree(self, name: str, aval_tree: Any) -> NodeRef:
        """Pytree-valued input (e.g. a parameter dict feeding stage operators)."""
        ref = self._add("input", None, (), name)
        self.nodes[ref.node_id].aval = aval_tree
        self.input_ids.append(ref.node_id)
        return ref

    def const(self, value, name: str = "const") -> NodeRef:
        arr = jnp.asarray(value)
        ref = self._add("const", None, (), name, payload=arr)
        self.nodes[ref.node_id].aval = jax.ShapeDtypeStruct(arr.shape, arr.dtype)
        return ref

    def apply(self, op: Operator, *args: NodeRef, name: str | None = None) -> NodeRef:
        if len(args) != op.arity:
            raise TypeError(f"{op.name} expects {op.arity} args, got {len(args)}")
        return self._add("op", op, args, name or op.name)

    def select(self, pred: NodeRef, then_val: NodeRef, else_val: NodeRef,
               name: str = "select") -> NodeRef:
        """Speculative branch: both sides computed, predicate selects (C4)."""
        return self._add("select", None, (pred, then_val, else_val), name)

    def output(self, *refs: NodeRef) -> None:
        for r in refs:
            self.output_ids.append(r.node_id)

    # --- analysis -----------------------------------------------------------
    def op_nodes(self) -> list[Node]:
        return [n for n in self.nodes if n.kind in ("op", "select")]

    def toposorted(self) -> list[Node]:
        """Nodes are appended in topological order by construction."""
        return list(self.nodes)

    def edges(self) -> list[tuple[int, int]]:
        return [(src, n.node_id) for n in self.nodes for src in n.inputs]

    def infer_shapes(self) -> dict[int, jax.ShapeDtypeStruct]:
        """Abstract-evaluate every node (no FLOPs — jax.eval_shape).

        Memoized until the graph is next mutated: traced model graphs run to
        thousands of nodes and are validated several times per assembly.
        """
        if self._shape_cache is not None:
            return self._shape_cache
        avals: dict[int, Any] = {}
        for n in self.nodes:
            if n.kind in ("input", "const"):
                avals[n.node_id] = n.aval
            elif n.kind == "op":
                args = [avals[i] for i in n.inputs]
                avals[n.node_id] = jax.eval_shape(n.op.fn, *args)
            elif n.kind == "select":
                _, t, e = n.inputs
                if (avals[t].shape, avals[t].dtype) != (avals[e].shape, avals[e].dtype):
                    raise TypeError(
                        f"select branches disagree: {avals[t]} vs {avals[e]}")
                avals[n.node_id] = avals[t]
            n.aval = avals[n.node_id]
        self._shape_cache = avals
        return avals

    def seal_shapes(self) -> None:
        """Adopt externally-recorded node avals as the shape cache.

        The tracer already knows every equation's output aval, so traced
        graphs don't need :meth:`infer_shapes`'s per-node ``jax.eval_shape``
        sweep (which re-traces each operator fn — ~1 ms/node, the dominant
        cost of validating large traced graphs).  Any later mutation clears
        the cache and falls back to full inference.
        """
        missing = [n.node_id for n in self.nodes if n.aval is None]
        if missing:
            raise ValueError(
                f"seal_shapes: nodes without avals: {missing[:5]}")
        self._shape_cache = {n.node_id: n.aval for n in self.nodes}

    def validate(self) -> None:
        if not self.output_ids:
            raise ValueError(f"graph {self.name!r} has no outputs")
        self.infer_shapes()

    def fingerprint(self) -> str:
        """Content hash of the graph: structure, operator identities, and
        const payloads.  Two graphs with the same name and input signature
        but different baked-in constants (e.g. traced closures over different
        static arguments) are *different bitstreams* — the cache keys on this.
        """
        h = hashlib.sha256()
        for n in self.nodes:
            op_id = (n.op.name, n.op.signature) if n.op is not None else None
            h.update(repr((n.kind, n.inputs, op_id)).encode())
            if n.kind == "const" and n.payload is not None:
                pay = n.payload
                shape = tuple(getattr(pay, "shape", ()))
                dtype = str(getattr(pay, "dtype", type(pay).__name__))
                size = int(getattr(pay, "size", 0) or np.asarray(pay).size)
                h.update(repr((shape, dtype, size)).encode())
                # cap hashing cost on huge constants: sample BEFORE any host
                # transfer so a closure over a multi-GB array costs a strided
                # copy plus a device-side checksum, not a full D2H round trip
                if size <= (1 << 18):
                    h.update(np.asarray(pay).tobytes())
                else:
                    flat = pay.ravel() if hasattr(pay, "ravel") else np.asarray(pay).ravel()
                    stride = max(1, size // (1 << 16))
                    h.update(np.asarray(flat[::stride]).tobytes())
                    h.update(np.asarray(flat[-1024:]).tobytes())
                    h.update(np.asarray(flat.sum()).tobytes())  # catches
                    # differences the strided sample steps over
        h.update(repr(tuple(self.output_ids)).encode())
        return h.hexdigest()[:16]

    # --- direct (un-assembled) evaluation: the correctness oracle ------------
    def evaluate(self, *inputs) -> Any:
        """Reference evaluation in graph order, bypassing placement/ISA.

        Used by tests as the oracle the assembled accelerator must match.
        """
        if len(inputs) != len(self.input_ids):
            raise TypeError(
                f"graph {self.name!r} takes {len(self.input_ids)} inputs, "
                f"got {len(inputs)}")
        vals: dict[int, Any] = {}
        for nid, arr in zip(self.input_ids, inputs):
            vals[nid] = arr
        for n in self.nodes:
            if n.kind == "input":
                continue
            if n.kind == "const":
                vals[n.node_id] = n.payload
            elif n.kind == "op":
                vals[n.node_id] = n.op.fn(*(vals[i] for i in n.inputs))
            elif n.kind == "select":
                p, t, e = (vals[i] for i in n.inputs)
                vals[n.node_id] = jnp.where(p, t, e)
        outs = tuple(vals[i] for i in self.output_ids)
        return outs[0] if len(outs) == 1 else outs


# --- canned graphs ------------------------------------------------------------
def vmul_reduce_graph(n: int, dtype=jnp.float32) -> Graph:
    """The paper's evaluation workload: ``sum = Σ A⃗·B⃗`` (VMUL + Reduce, §III)."""
    g = Graph("vmul_reduce")
    a = g.input("A", (n,), dtype)
    b = g.input("B", (n,), dtype)
    prod = g.apply(patterns.make_zip_with(patterns.MUL), a, b, name="VMUL")
    total = g.apply(patterns.make_reduce(patterns.ADD), prod, name="Reduce")
    g.output(total)
    return g


def saxpy_graph(n: int, alpha: float = 2.0, dtype=jnp.float32) -> Graph:
    g = Graph("saxpy")
    x = g.input("x", (n,), dtype)
    y = g.input("y", (n,), dtype)
    a = g.const(jnp.asarray(alpha, dtype), "alpha")
    ax = g.apply(patterns.MUL, a, x, name="scale")
    g.output(g.apply(patterns.ADD, ax, y, name="axpy"))
    return g


def branchy_graph(n: int, dtype=jnp.float32) -> Graph:
    """if mean(x) > 0 then sqrt(|x|) else sin(x) — exercises speculation (C4)."""
    g = Graph("branchy")
    x = g.input("x", (n,), dtype)
    mean = g.apply(patterns.make_reduce(patterns.ADD), x, name="sum")
    zero = g.const(jnp.zeros((), dtype))
    pred = g.apply(patterns.GT, mean, zero, name="pred")
    then_v = g.apply(patterns.SQRT, g.apply(patterns.ABS, x), name="then")
    else_v = g.apply(patterns.SIN, x, name="else")
    g.output(g.select(pred, then_v, else_v))
    return g

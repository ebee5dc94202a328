"""Parameter specification / initialization / abstraction.

A model is described by a *spec tree*: nested dicts whose leaves are
:class:`ParamSpec` (shape + logical axes + init scale).  From one spec tree we
derive:

  * ``init(spec, key)``            — materialized parameters (CPU tests),
  * ``abstract(spec)``             — ShapeDtypeStructs (dry-run, no memory),
  * ``axes(spec)``                 — logical-axes pytree (sharding rules),
  * ``shapes(spec)``               — shape pytree.

Keeping axes next to shapes is what lets the launcher build in_shardings for
a 512-device mesh without ever allocating a parameter.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]          # logical axis per dim
    init: str = "normal"                  # normal | zeros | ones | ssm_a
    scale: float | None = None            # None -> 1/sqrt(fan_in)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def dense(d_in: int, d_out: int, in_axis: str | None, out_axis: str | None,
          dtype=jnp.bfloat16) -> ParamSpec:
    return ParamSpec((d_in, d_out), (in_axis, out_axis), "normal", None, dtype)


def embedding(vocab: int, d: int, dtype=jnp.bfloat16) -> ParamSpec:
    return ParamSpec((vocab, d), ("vocab", "embed"), "normal", 0.02, dtype)


def norm_scale(d: int, dtype=jnp.float32) -> ParamSpec:
    return ParamSpec((d,), (None,), "ones", None, dtype)


def stacked(spec: ParamSpec, n: int) -> ParamSpec:
    """Add a leading scan-over-layers dim (never sharded)."""
    return dataclasses.replace(spec, shape=(n, *spec.shape),
                               axes=(None, *spec.axes))


def stack_tree(tree: Any, n: int) -> Any:
    return jax.tree.map(lambda s: stacked(s, n), tree, is_leaf=is_spec)


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def _materialize(spec: ParamSpec, key) -> jax.Array:
    # one compiled program per leaf: the f32 draw is fused into the cast, so
    # the device never holds an f32 copy of a bf16 leaf (3.2 GB for the
    # stacked MLP weights of a 3.8 B model) next to the leaves already made
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "ssm_a":  # mamba A_log: log of Uniform[1, 16]
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(spec.dtype)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(spec.dtype)


def init(spec_tree: Any, key) -> Any:
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef,
                              [_materialize(s, k) for s, k in zip(leaves, keys)])


def abstract(spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        spec_tree, is_leaf=is_spec)


def axes(spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: s.axes, spec_tree, is_leaf=is_spec)


def shapes(spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: s.shape, spec_tree, is_leaf=is_spec)


def count(spec_tree: Any) -> int:
    return sum(math.prod(s.shape)
               for s in jax.tree.leaves(spec_tree, is_leaf=is_spec))

"""The one home of JAX names whose spelling moves between releases.

Written against the installed JAX (0.9).  Every module of the repo that
needs one of these names imports it from here, so a JAX upgrade edits this
file and nothing else:

* the jaxpr classes the trace frontend walks: ``ClosedJaxpr``, ``Jaxpr``
  and ``Literal`` live in ``jax.extend.core``; ``DropVar`` is not exported
  there and is still read from ``jax.core``;
* :func:`make_mesh`, whose axes are ``Auto`` (``jax.make_mesh`` now
  defaults to ``Explicit`` axes, under which ``with_sharding_constraint``
  on an un-annotated value is refused);
* :func:`shard_map`, ``jax.shard_map`` with its ``check_vma`` flag.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.core import DropVar
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal
from jax.sharding import AxisType

shard_map = jax.shard_map

__all__ = ["ClosedJaxpr", "DropVar", "Jaxpr", "Literal", "make_mesh",
           "shard_map"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Sequence | None = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: shardings come from the
    ``in_shardings``/``with_sharding_constraint`` the program states, as
    the sharding rules in :mod:`repro.sharding` expect."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)

"""Fused RMSNorm Pallas kernel.

One pass over each row block: mean-of-squares, rsqrt, scale — the three ops
never leave VMEM (unfused XLA does two HBM round-trips for large rows).
Rows are processed in (BLOCK_ROWS, d) tiles; d stays whole per tile (RMSNorm
reduces over the full feature axis, and d_model ≤ 12288 ⇒ ≤ 12 MB bf16 per
256-row tile — fits v5e's 128 MB VMEM comfortably at our block sizes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 128, interpret: bool | None = None) -> jax.Array:
    """RMSNorm over the last axis. x: (..., d), w: (d,)."""
    interpret = interpret_mode(interpret)
    if w.ndim != 1 or x.shape[-1] != w.shape[0]:
        raise ValueError(f"shape mismatch: x {x.shape}, w {w.shape}")
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    x2 = x.reshape(rows, d)

    br = min(block_rows, rows)
    pad_rows = ((rows + br - 1) // br) * br
    if pad_rows != rows:
        x2 = jnp.pad(x2, ((0, pad_rows - rows), (0, 0)))
    import functools
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(pad_rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pad_rows, d), x.dtype),
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(*lead, d)

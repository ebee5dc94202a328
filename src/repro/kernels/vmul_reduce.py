"""Fused VMUL+Reduce Pallas kernel — the paper's evaluation workload (§III).

``sum = Σ A⃗ · B⃗`` as ONE kernel: the multiply never round-trips to HBM.  On
the paper's overlay this is the dynamic configuration — multiplier and adder
in *contiguous* tiles, pipelined; the fused kernel is the TPU equivalent
(VMUL feeding the reduction accumulator through VMEM, zero HBM traffic for
the intermediate).

Tiling: inputs are viewed as (rows, LANE)-blocks; each grid step streams one
(BLOCK_ROWS, 128) tile of A and B into VMEM, multiplies on the VPU and
accumulates a per-lane partial into the resident (1, 128) output block; the
128 lane partials are summed outside the kernel (the TPU stores vectors,
not scalars, to VMEM).  Accumulation is f32 regardless of input dtype
(bf16-safe).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import LANE, interpret_mode


def _kernel(a_ref, b_ref, o_ref):
    # the output block maps to (0, 0) at every step, so it stays resident
    # in VMEM across the grid and doubles as the accumulator
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    # VPU multiply + row-fold; keep a (1, LANE) partial per lane to stay 2D
    o_ref[...] += jnp.sum(a * b, axis=0, keepdims=True)


def vmul_reduce(a: jax.Array, b: jax.Array, *, block_rows: int = 256,
                interpret: bool | None = None) -> jax.Array:
    """Fused dot product of two 1-D vectors. Pads to a (rows, 128) view."""
    if a.shape != b.shape or a.ndim != 1:
        raise ValueError(f"expect equal 1-D shapes, got {a.shape} vs {b.shape}")
    interpret = interpret_mode(interpret)
    n = a.shape[0]

    rows = max((n + LANE - 1) // LANE, 1)
    # round rows up so the grid divides evenly
    rows = ((rows + block_rows - 1) // block_rows) * block_rows
    padded = rows * LANE
    if padded != n:
        a = jnp.pad(a, (0, padded - n))
        b = jnp.pad(b, (0, padded - n))
    a2 = a.reshape(rows, LANE)
    b2 = b.reshape(rows, LANE)
    grid = (rows // block_rows,)

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        interpret=interpret,
    )(a2, b2)
    return jnp.sum(out).astype(a.dtype)

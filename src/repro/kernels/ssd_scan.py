"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD recurrence  h_t = e^{a_t} h_{t-1} + B_t ⊗ x_t ,  y_t = C_t · h_t
is evaluated with the chunked algorithm (Mamba-2 paper §6): the sequence is
split into chunks of length L; *within* a chunk the recurrence is expanded
into a quadratic "attention-like" form (two MXU matmuls per chunk — the
compute hot-spot, implemented here in Pallas); *across* chunks only the
(p × n) chunk states participate in a cheap sequential scan (left in jnp —
it is O(S/L) tiny steps and memory-bound).

Kernel per (batch·head, chunk) grid cell, all tiles in VMEM:
    a_cum   = cumsum(a)                                  (L,)
    M[i,j]  = (C_i · B_j) · e^{a_cum_i − a_cum_j} · [i≥j]   (L, L)   MXU
    y_diag  = M @ x                                       (L, p)    MXU
    state   = (B · e^{a_cum_L − a_cum})ᵀ @ x              (n, p)    MXU
Outputs y_diag, per-chunk states, and a_cum (needed for the inter-chunk
correction outside).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref, acum_ref, *, chunk: int):
    x = x_ref[0, 0].astype(jnp.float32)      # (L, p)
    a = a_ref[0, 0].astype(jnp.float32)      # (1, L): a row, lane-major
    bmat = b_ref[0, 0].astype(jnp.float32)   # (L, n)
    cmat = c_ref[0, 0].astype(jnp.float32)   # (L, n)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # a_cum[i] = Σ_{j≤i} a[j] as masked reductions of a broadcast square:
    # over lanes it lands in a column, over sublanes (of the transpose) in
    # a row — the two layouts the (L, L) segment sums need
    a_rows = jnp.broadcast_to(a, (chunk, chunk))             # [i, j] = a[j]
    cum_col = jnp.sum(jnp.where(lj <= li, a_rows, 0.0), axis=1,
                      keepdims=True)                         # (L, 1)
    a_cum = jnp.sum(jnp.where(li <= lj, a_rows.T, 0.0), axis=0,
                    keepdims=True)                           # (1, L)
    seg = cum_col - a_cum                                    # (L, L)
    # mask before exp (j>i entries have seg>0 -> overflow)
    decay = jnp.exp(jnp.where(li >= lj, seg, -jnp.inf))      # (L, L)

    scores = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32) * decay
    y_ref[0, 0] = jnp.dot(scores, x,
                          preferred_element_type=jnp.float32).astype(y_ref.dtype)

    a_tot = jnp.sum(a, axis=1, keepdims=True)                # (1, 1)
    w = jnp.exp(a_tot - cum_col)                             # (L, 1)
    st_ref[0, 0] = jnp.dot((bmat * w).T, x,
                           preferred_element_type=jnp.float32).astype(st_ref.dtype)
    acum_ref[0, 0] = a_cum.astype(acum_ref.dtype)


def ssd_chunk(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *,
              chunk: int, interpret: bool | None = None):
    """Chunk-local SSD terms.

    Args:
      x: (bh, nchunks, L, p) pre-discretized inputs (x·Δ).
      a: (bh, nchunks, 1, L) log-decay per step (Δ·A, ≤ 0); the unit axis
        keeps the block's last two dims equal to the array's (TPU tiling).
      b, c: (bh, nchunks, L, n) input/output projections.
    Returns:
      y_diag: (bh, nchunks, L, p), states: (bh, nchunks, n, p),
      a_cum: (bh, nchunks, 1, L).
    """
    interpret = interpret_mode(interpret)
    bh, nc, L, p = x.shape
    n = b.shape[-1]
    if L != chunk:
        raise ValueError(f"chunk mismatch {L} != {chunk}")

    grid = (bh, nc)
    y, st, acum = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, L, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, L, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, L, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, n, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, nc, 1, L), jnp.float32),
        ],
        interpret=interpret,
    )(x, a, b, c)
    return y, st, acum


def ssd(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *,
        chunk: int = 64, interpret: bool | None = None,
        initial_state: jax.Array | None = None):
    """Full SSD: chunk-local kernel + inter-chunk state scan.

    Args:
      x: (batch, seqlen, heads, p); a: (batch, seqlen, heads);
      b, c: (batch, seqlen, heads, n).
    Returns:
      y: (batch, seqlen, heads, p), final_state: (batch, heads, n, p).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    if s % chunk:
        raise ValueError(f"seqlen {s} must divide chunk {chunk}")
    nc = s // chunk

    def to_bh(t, feat):
        # (batch, s, h, f?) -> (batch*h, nc, L, f?)
        if feat:
            t = t.transpose(0, 2, 1, 3).reshape(bsz * h, nc, chunk, t.shape[-1])
        else:
            t = t.transpose(0, 2, 1).reshape(bsz * h, nc, chunk)
        return t

    xb, ab, bb, cb = to_bh(x, True), to_bh(a, False), to_bh(b, True), to_bh(c, True)
    y_diag, states, a_cum = ssd_chunk(xb, ab[:, :, None], bb, cb, chunk=chunk,
                                      interpret=interpret)
    a_cum = a_cum[:, :, 0]                               # (bh, nc, L)

    # inter-chunk recurrence on (n, p) states — O(nc) sequential, tiny
    a_tot = a_cum[..., -1]                               # (bh, nc)
    init = (jnp.zeros((bsz * h, n, p), jnp.float32) if initial_state is None
            else initial_state.reshape(bsz * h, n, p).astype(jnp.float32))

    def step(carry, inp):
        st_c, a_c = inp                                  # (bh, n, p), (bh,)
        prev = carry
        new = prev * jnp.exp(a_c)[:, None, None] + st_c
        return new, prev                                 # emit state *entering* chunk

    final, prev_states = jax.lax.scan(
        step, init, (states.swapaxes(0, 1), a_tot.swapaxes(0, 1)))
    prev_states = prev_states.swapaxes(0, 1)             # (bh, nc, n, p)

    # inter-chunk contribution: y_off[l] = C_l · prev_state · e^{a_cum_l}
    y_off = jnp.einsum("zcln,zcnp,zcl->zclp", cb.astype(jnp.float32),
                       prev_states, jnp.exp(a_cum))
    y = (y_diag + y_off).reshape(bsz, h, nc * chunk, p).transpose(0, 2, 1, 3)
    return y.astype(x.dtype), final.reshape(bsz, h, n, p)

"""Blocked online-softmax attention (FlashAttention) Pallas kernel.

Grid = (batch·q_heads, q_blocks, k_blocks); the innermost k dimension streams
K/V tiles through VMEM while running max ``m``, denominator ``l`` and the
output accumulator live in VMEM scratch (carried across k steps — Pallas TPU
grids iterate the last axis innermost, so scratch is coherent per (bh, iq)).

Features needed by the assigned archs:
  * causal masking                  (all decoder LMs)
  * GQA — kv head = q head // group (mistral/phi3/gemma2/pixtral/…)
  * sliding-window masking          (gemma2 local layers)
  * logit soft-capping              (gemma2: tanh(logits/cap)·cap)

The kv-head mapping happens in the BlockSpec index_map (no materialized
repeat_kv — the paper's "reuse one pre-synthesized bitstream from several
consumers" case, i.e. one K/V tile feeds `group` q-heads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int | None,
            softcap: float | None, bq: int, bk: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)                  # (bk, d)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk) MXU
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    iq = pl.program_id(1)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (max = NEG_INF) against exp overflow to nan
    p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(
        jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev)
        - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _fin():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Attention over (B, Hq, S, D) q and (B, Hkv, S, D) k/v with Hq % Hkv == 0."""
    interpret = interpret_mode(interpret)
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq lens ({sq},{sk}) must divide blocks ({bq},{bk})")

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik, _group=group, _hq=hq, _hkv=hkv):
        bidx = bh // _hq
        qh = bh % _hq
        return (bidx * _hkv + qh // _group, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          softcap=softcap, bq=bq, bk=bk),
        grid=(b * hq, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)

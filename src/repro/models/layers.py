"""Transformer layers: RoPE, norms, attention variants, MLPs.

Every layer is a pair of functions:
  ``<layer>_spec(cfg)``              -> ParamSpec tree (shapes + logical axes)
  ``<layer>_fwd(p, x, ...)``         -> activations

Attention covers the assigned archs' variants behind one interface:
  * GQA (kv_heads < heads)                          — mistral/phi3/minicpm/…
  * sliding window + logit softcap + query scaling  — gemma2 local layers
  * MLA latent attention (+ absorbed decode)        — deepseek-v3
  * cross attention                                 — seamless-m4t decoder
Prefill uses the Pallas flash kernel (or a chunked-XLA path for dry-run
lowering); decode does masked dense attention against the KV cache.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models.params import ParamSpec, dense, norm_scale

# attention implementation selector:
#   "pallas"      — flash kernel; CPU tests / TPU production path
#   "xla"         — dense einsum; dry-run baseline lowering (S² scores in HBM)
#   "xla_chunked" — online-softmax scan over K blocks in plain XLA; the
#                   flash *schedule* without Pallas — peak memory is
#                   O(S·block) instead of O(S²) (hillclimb iteration)
# Set by launch/dryrun.py.
ATTN_IMPL = "pallas"


def set_attn_impl(impl: str) -> None:
    global ATTN_IMPL
    if impl not in ("pallas", "xla", "xla_chunked"):
        raise ValueError(impl)
    ATTN_IMPL = impl


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_cos_sin(positions: jax.Array, dim: int, theta: float):
    """positions: (...,) int -> cos/sin (..., dim/2) f32."""
    freqs = jnp.exp(-math.log(theta) *
                    jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, S, D/2)."""
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]   # (B, S, 1, D/2)
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norm
# ---------------------------------------------------------------------------
def rmsnorm_fwd(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    # kernel for big rows; jnp for tiny (smoke) rows
    if x.shape[-1] >= 128 and ATTN_IMPL == "pallas":
        return kops.rmsnorm(x, scale, eps=eps)
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------
def mlp_spec(cfg: ArchConfig, d_ff: int | None = None) -> dict:
    f = d_ff or cfg.d_ff
    return {
        "w_gate": dense(cfg.d_model, f, "embed", "ffn"),
        "w_up": dense(cfg.d_model, f, "embed", "ffn"),
        "w_down": dense(f, cfg.d_model, "ffn", "embed"),
    }


def _act(cfg: ArchConfig, x):
    return jax.nn.gelu(x) if cfg.act == "gelu" else jax.nn.silu(x)


def mlp_fwd(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = _act(cfg, x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


def cache_update(cache: jax.Array, new: jax.Array, idx, *, axis: int):
    """Write ``new`` into ``cache`` at position ``idx`` along ``axis``.

    Plain dynamic-update-slice. NOTE (§Perf minicpm iters 2a-2c): when the
    seq dim was model-sharded, DUS with a traced index forced per-layer
    cache all-gathers (2×144 MiB/layer); a one-hot masked blend was tried
    and REFUTED (gathers grew to 6.3 GB).  The production serving layout
    therefore shards the cache head_dim instead (SERVE_RULES) — seq stays
    unsharded and this update is fully shard-local.
    """
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), idx, axis=axis)


# rows are written through a window of this many positions, aligned to it:
# one (8, 128) tile row of the cache's sequence axis, so the write leaves
# the cache in its default device layout
KV_WRITE_WINDOW = 128


def write_rows(cache: jax.Array, layer, new: jax.Array,
               pos: jax.Array) -> jax.Array:
    """Write each batch row's new K or V entry into one layer of a stacked
    cache, in place: ``cache[layer, b, :, pos[b], :] = new[b, :, 0, :]``.

    cache: (L, B, Hkv, Smax, hd); new: (B, Hkv, 1, hd); pos: (B,) int32.
    Each row reads an aligned window of ``KV_WRITE_WINDOW`` positions,
    selects its one entry into it and writes the window back, so the write
    moves B windows rather than the layer's whole slice.  A position
    outside [0, Smax) writes nothing, as a one-hot select would.
    """
    _, b, hkv, smax, hd = cache.shape
    w = min(KV_WRITE_WINDOW, smax)
    new = new.astype(cache.dtype)
    layer = jnp.asarray(layer, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    for r in range(b):
        start = jnp.clip(pos[r] // w * w, 0, smax - w)
        at = (layer, jnp.int32(r), zero, start, zero)
        win = jax.lax.dynamic_slice(cache, at, (1, 1, hkv, w, hd))
        sel = (jnp.arange(w) == pos[r] - start)[:, None]
        win = jnp.where(sel, new[r][None, None], win)
        cache = jax.lax.dynamic_update_slice(cache, win, at)
    return cache


# ---------------------------------------------------------------------------
# Attention (GQA family)
# ---------------------------------------------------------------------------
def attn_spec(cfg: ArchConfig) -> dict:
    hd = cfg.resolved_head_dim
    return {
        "wq": dense(cfg.d_model, cfg.num_heads * hd, "embed", "heads"),
        "wk": dense(cfg.d_model, cfg.num_kv_heads * hd, "embed", "kv_heads"),
        "wv": dense(cfg.d_model, cfg.num_kv_heads * hd, "embed", "kv_heads"),
        "wo": dense(cfg.num_heads * hd, cfg.d_model, "heads", "embed"),
    }


def _attention_xla(q, k, v, *, causal, window, softcap, scale,
                   q_offset: int = 0, kv_len: jax.Array | None = None):
    """Dense masked attention in plain XLA (B,H,Sq,D)x(B,Hkv,Sk,D).

    ``q_offset`` positions queries within the kv sequence (decode);
    ``kv_len`` masks out unwritten cache slots.  Either may also be a (B,)
    array — ragged decode, where every batch row sits at its own position
    (continuous batching with mixed prompt lengths).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    # keep K/V in their storage dtype (bf16 cache!) and accumulate in f32 —
    # upcasting the cache materializes+gathers a 2x-sized f32 copy per layer
    qg = q.reshape(b, hkv, group, sq, d)
    s = jnp.einsum("bkgqd,bkld->bkgql", qg, k,
                   preferred_element_type=jnp.float32) * scale
    # pin scores to the KV layout (seq-sharded under SERVE_RULES) — without
    # this the partitioner prefers all-gathering f32 copies of K/V per layer
    s = shd.constrain_logical(s, ("batch", "kv_heads", None, None, "seq"))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    if getattr(q_offset, "ndim", 0) >= 1 or getattr(kv_len, "ndim", 0) >= 1:
        # ragged: per-row offsets/lengths -> a (B, Sq, Sk) mask.  Mask
        # VALUES for any given row match the scalar path at that row's
        # position exactly, so uniform batches stay bit-identical.
        qo = jnp.asarray(q_offset, jnp.int32).reshape(-1)
        qpos = qo[:, None, None] + jnp.arange(sq)[None, :, None]
        kpos = jnp.arange(sk)[None, None, :]
        mask = jnp.ones((b, sq, sk), bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        if kv_len is not None:
            kl = jnp.asarray(kv_len, jnp.int32).reshape(-1)
            mask &= kpos < kl[:, None, None]
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgql,bkld->bkgqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, hq, sq, v.shape[-1]).astype(q.dtype)
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    if kv_len is not None:
        mask = mask & (kpos < kv_len)
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgql,bkld->bkgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, hq, sq, v.shape[-1]).astype(q.dtype)


def _attention_xla_chunked(q, k, v, *, causal, window, softcap, scale,
                           block: int = 1024, q_offset=0, kv_len=None):
    """Online-softmax attention: lax.scan over K/V blocks (flash schedule in
    plain XLA).  Peak score memory is (B,H,Sq,block) instead of (B,H,Sq,Sk);
    the whole function recomputes in backward (checkpoint) so no per-block
    residuals are saved.  ``q_offset``/``kv_len`` support the cached-prefill
    case (queries positioned inside a longer KV window)."""
    b, hq, sq, dqk = q.shape
    _, hkv, sk, dv = k.shape[0], k.shape[1], k.shape[2], v.shape[-1]
    group = hq // hkv
    nb = sk // block
    qg = (q.reshape(b, hkv, group, sq, dqk) * scale).astype(jnp.float32)
    kb = k.reshape(b, hkv, nb, block, dqk).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, hkv, nb, block, dv).transpose(2, 0, 1, 3, 4)
    qpos = q_offset + jnp.arange(sq)[:, None]

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        ib, k_blk, v_blk = inp
        s = jnp.einsum("bkgqd,bkld->bkgql", qg, k_blk.astype(jnp.float32))
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        kpos = ib * block + jnp.arange(block)[None, :]
        mask = jnp.ones((sq, block), bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        if kv_len is not None:
            mask = mask & (kpos < kv_len)
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha[..., 0][..., None] + jnp.einsum(
            "bkgql,bkld->bkgqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, hkv, group, sq, 1), -1e30, jnp.float32),
            jnp.zeros((b, hkv, group, sq, 1), jnp.float32),
            jnp.zeros((b, hkv, group, sq, dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        step, init, (jnp.arange(nb), kb, vb))
    o = acc / jnp.where(l == 0.0, 1.0, l)
    return o.reshape(b, hq, sq, dv).astype(q.dtype)


def multihead_attention(q, k, v, *, causal=True, window=None, softcap=None,
                        scale=None):
    """Full-sequence attention dispatcher (train/prefill)."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    use_pallas = (ATTN_IMPL == "pallas"
                  and q.shape[2] % 128 == 0 and k.shape[2] % 128 == 0
                  and q.shape[-1] == v.shape[-1])
    if use_pallas:
        return kops.attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale)
    if ATTN_IMPL == "xla_chunked" and k.shape[2] % 1024 == 0:
        fn = jax.checkpoint(
            functools.partial(_attention_xla_chunked, causal=causal,
                              window=window, softcap=softcap, scale=scale),
            prevent_cse=False)
        return fn(q, k, v)
    return _attention_xla(q, k, v, causal=causal, window=window,
                          softcap=softcap, scale=scale)


def attn_fwd(p: dict, x: jax.Array, cfg: ArchConfig, *, kind: str,
             positions: jax.Array, cache: dict | None = None,
             x_kv: jax.Array | None = None,
             layer: jax.Array | int | None = None
             ) -> tuple[jax.Array, dict | None]:
    """Unified attention forward.

    x: (B, S, D). kind: dense|local|global|shared_attn|enc|cross.
    cache: None (train/prefill without cache) or
      {"k": (B, Hkv, Smax, hd), "v": ..., "index": scalar} for decode.
    x_kv: encoder output for cross attention.
    layer: ragged decode only — the cache is the layer-stacked
      {"k": (L, B, Hkv, Smax, hd), "v": ..., "index": (L,)} and this call
      is layer ``layer`` of it; the returned cache is the whole stack.
    Returns (out, updated_cache).
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    src = x if x_kv is None else x_kv
    s_kv = src.shape[1]

    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (src @ p["wk"]).reshape(b, s_kv, hkv, hd)
    v = (src @ p["wv"]).reshape(b, s_kv, hkv, hd)

    is_cross = (x_kv is not None) or kind == "cross"
    causal = kind != "enc" and not is_cross
    window = cfg.sliding_window if kind == "local" else None
    if cfg.query_pre_attn_scalar is not None:
        scale = cfg.query_pre_attn_scalar ** -0.5
    else:
        scale = hd ** -0.5

    if not is_cross:  # RoPE on self-attention only
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    # shard attention activations by (batch, heads) so the S×S score tensors
    # partition over the model axis instead of replicating
    qt = shd.constrain_logical(q.transpose(0, 2, 1, 3),
                               ("batch", "heads", None, None))
    kt = shd.constrain_logical(k.transpose(0, 2, 1, 3),
                               ("batch", "kv_heads", None, None))
    vt = shd.constrain_logical(v.transpose(0, 2, 1, 3),
                               ("batch", "kv_heads", None, None))

    if cache is None:
        o = multihead_attention(qt, kt, vt, causal=causal, window=window,
                                softcap=cfg.attn_softcap, scale=scale)
        new_cache = None
    else:
        idx = cache["index"]
        if is_cross:
            # cross-attn cache is precomputed once at prefill; mask empty slots
            kt, vt = cache["k"], cache["v"]
            o = _attention_xla(qt, kt, vt, causal=False, window=None,
                               softcap=cfg.attn_softcap, scale=scale,
                               kv_len=idx)
            new_cache = cache
        elif getattr(positions, "ndim", 0) >= 2:
            # ragged decode (s == 1): every batch row writes its KV entry at
            # its OWN position and attends against its own filled extent.
            # With ``layer`` given the cache leaves are the layer-stacked
            # arrays the scan carries, written in place; the scalar cache
            # "index" keeps ticking but the mask below never reads it.
            pos_b = positions[:, 0].astype(jnp.int32)              # (B,)
            ks, vs, lyr = cache["k"], cache["v"], layer
            if layer is None:        # one layer's own cache: a stack of one
                ks, vs, lyr = ks[None], vs[None], 0
            ks = write_rows(ks, lyr, kt, pos_b)
            vs = write_rows(vs, lyr, vt, pos_b)
            ck = jax.lax.dynamic_index_in_dim(ks, lyr, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(vs, lyr, keepdims=False)
            ck = shd.constrain_logical(ck, ("batch", "kv_heads", "seq", None))
            cv = shd.constrain_logical(cv, ("batch", "kv_heads", "seq", None))
            o = _attention_xla(qt, ck, cv, causal=True, window=window,
                               softcap=cfg.attn_softcap, scale=scale,
                               q_offset=pos_b, kv_len=pos_b + s)
            if layer is None:
                new_cache = {"k": ck, "v": cv, "index": idx + s}
            else:
                new_cache = {"k": ks, "v": vs, "index": idx.at[layer].add(s)}
        else:
            ck = cache_update(cache["k"], kt, idx, axis=2)
            cv = cache_update(cache["v"], vt, idx, axis=2)
            ck = shd.constrain_logical(ck, ("batch", "kv_heads", "seq", None))
            cv = shd.constrain_logical(cv, ("batch", "kv_heads", "seq", None))
            if s > 1 and ATTN_IMPL == "xla_chunked" and \
                    ck.shape[2] % 1024 == 0:
                # cached prefill: flash schedule, not dense S² scores
                fn = jax.checkpoint(
                    functools.partial(
                        _attention_xla_chunked, causal=True, window=window,
                        softcap=cfg.attn_softcap, scale=scale,
                        q_offset=idx, kv_len=idx + s), prevent_cse=False)
                o = fn(qt, ck, cv)
            else:
                o = _attention_xla(qt, ck, cv, causal=True, window=window,
                                   softcap=cfg.attn_softcap, scale=scale,
                                   q_offset=idx, kv_len=idx + s)
            new_cache = {"k": ck, "v": cv, "index": idx + s}

    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return o @ p["wo"], new_cache


def attn_cache_spec(cfg: ArchConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16) -> dict:
    hd = cfg.resolved_head_dim
    shape = (batch, cfg.num_kv_heads, max_len, hd)
    axes = ("batch", "kv_heads", "seq", "head_dim")
    return {"k": ParamSpec(shape, axes, "zeros", dtype=dtype),
            "v": ParamSpec(shape, axes, "zeros", dtype=dtype),
            "index": ParamSpec((), (), "zeros", dtype=jnp.int32)}


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (deepseek-v3)
# ---------------------------------------------------------------------------
def mla_spec(cfg: ArchConfig) -> dict:
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vh = cfg.v_head_dim
    return {
        "wq_a": dense(cfg.d_model, cfg.q_lora_rank, "embed", None),
        "q_norm": norm_scale(cfg.q_lora_rank),
        "wq_b": dense(cfg.q_lora_rank, cfg.num_heads * (nope + rope_d),
                      None, "heads"),
        "wkv_a": dense(cfg.d_model, cfg.kv_lora_rank + rope_d, "embed", None),
        "kv_norm": norm_scale(cfg.kv_lora_rank),
        "wkv_b": dense(cfg.kv_lora_rank, cfg.num_heads * (nope + vh),
                       None, "heads"),
        "wo": dense(cfg.num_heads * vh, cfg.d_model, "heads", "embed"),
    }


def mla_fwd(p: dict, x: jax.Array, cfg: ArchConfig, *,
            positions: jax.Array, cache: dict | None = None
            ) -> tuple[jax.Array, dict | None]:
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope_d, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    scale = (nope + rope_d) ** -0.5

    q_lat = rmsnorm_fwd(p["q_norm"], x @ p["wq_a"], cfg.norm_eps)
    q = (q_lat @ p["wq_b"]).reshape(b, s, h, nope + rope_d)
    q = shd.constrain_logical(q, ("batch", None, "heads", None))
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv_a = x @ p["wkv_a"]                       # (B, S, r + rope_d)
    c_kv = rmsnorm_fwd(p["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    k_rope = kv_a[..., r:].reshape(b, s, 1, rope_d)

    cos, sin = rope_cos_sin(positions, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)

    if cache is None:
        # prefill/train: materialize per-head K/V from the latent
        kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, nope + vh)
        kv = shd.constrain_logical(kv, ("batch", None, "heads", None))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope_d))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = multihead_attention(q_full.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3),
                                causal=True, scale=scale)
        # pad V head dim? v_head==vh; attention needs q/k same dim, v free —
        # the pallas kernel assumes same d for q/k/v, so use xla when vh != d_qk
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * vh)
        return o @ p["wo"], None

    # absorbed decode: score via latent cache, never materialize K/V
    idx = cache["index"]
    if getattr(positions, "ndim", 0) >= 2:
        # ragged decode: per-row one-hot latent writes + per-row causal
        # extent (mirrors the ragged branch in attn_fwd)
        pos_b = positions[:, 0].astype(jnp.int32)                   # (B,)
        sel = jnp.arange(cache["c_kv"].shape[1])[None, :] == pos_b[:, None]
        ckv = jnp.where(sel[:, :, None],
                        c_kv.astype(cache["c_kv"].dtype), cache["c_kv"])
        krc = jnp.where(sel[:, :, None],
                        k_rope[:, :, 0].astype(cache["k_rope"].dtype),
                        cache["k_rope"])
        qpos_b = (pos_b[:, None] + jnp.arange(s)[None, :])[:, None, :, None]
    else:
        ckv = cache_update(cache["c_kv"], c_kv, idx, axis=1)        # (B, Smax, r)
        krc = cache_update(cache["k_rope"], k_rope[:, :, 0], idx, axis=1)
        qpos_b = None

    wkv_b = p["wkv_b"].reshape(r, h, nope + vh)
    w_k = wkv_b[..., :nope]                              # (r, h, nope)
    w_v = wkv_b[..., nope:]                              # (r, h, vh)

    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                       w_k.astype(jnp.float32))          # (B, S, h, r)
    scores = (jnp.einsum("bshr,blr->bhsl", q_abs, ckv.astype(jnp.float32)) +
              jnp.einsum("bshd,bld->bhsl", q_rope.astype(jnp.float32),
                         krc.astype(jnp.float32))) * scale
    # causal within the incoming window: query at idx+i sees keys <= idx+i
    kpos = jnp.arange(ckv.shape[1])[None, None, None, :]
    qpos = qpos_b if qpos_b is not None else \
        (idx + jnp.arange(s))[None, None, :, None]
    scores = jnp.where(kpos <= qpos, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhsl,blr->bshr", probs, ckv.astype(jnp.float32))
    o = jnp.einsum("bshr,rhv->bshv", ctx, w_v.astype(jnp.float32))
    o = o.reshape(b, s, h * vh).astype(x.dtype)
    return o @ p["wo"], {"c_kv": ckv, "k_rope": krc, "index": idx + s}


def mla_cache_spec(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    return {"c_kv": ParamSpec((batch, max_len, cfg.kv_lora_rank),
                              ("batch", "seq", "head_dim"), "zeros",
                              dtype=dtype),
            "k_rope": ParamSpec((batch, max_len, cfg.qk_rope_head_dim),
                                ("batch", "seq", None), "zeros", dtype=dtype),
            "index": ParamSpec((), (), "zeros", dtype=jnp.int32)}

"""Unified model: decoder LMs, MoE, SSM/hybrid, enc-dec — one code path.

A model is a sequence of *groups*; each group is ``(unit, repeat)`` from
``ArchConfig.blocks``.  The unit (a tuple of layer kinds) becomes the body of
one ``lax.scan`` over ``repeat`` — so an 88-layer dense model compiles ONE
layer body, and gemma-2's (local, global) alternation compiles exactly two.
The shared transformer blocks of ``hybrid<k>`` layers (zamba2) hold their
parameters OUTSIDE every scanned stack, at the model's top level — one
"bitstream" per block, referenced by each occurrence (paper's operator
reuse); each occurrence's own Mamba-2 block, adapter and linear are stacked
with the other layers.  A hybrid model also hands every layer the token
embedding, which the shared blocks read beside the residual stream.

Remat is applied to the scan body (``cfg.remat``: full | dots | none) — the
main activation-memory knob for the 4k-train shapes.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.configs.base import ArchConfig, hybrid_block
from repro.models import params as pm
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (attn_cache_spec, attn_fwd, attn_spec,
                                 mla_cache_spec, mla_fwd, mla_spec, mlp_fwd,
                                 mlp_spec, rmsnorm_fwd)
from repro.models.params import ParamSpec, dense, embedding, norm_scale

# the leaves of a self-attention cache that the ragged decode carries
KV_LEAVES = ("k", "v", "index")


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------
def layer_spec(cfg: ArchConfig, kind: str) -> dict:
    d = cfg.d_model
    if kind == "mamba":
        return {"ln1": norm_scale(d), "mixer": ssm_lib.ssm_spec(cfg)}
    if hybrid_block(kind) is not None:
        # the occurrence's own leaves; the shared block is shared_block_spec
        return {"ln1": norm_scale(d), "mixer": ssm_lib.ssm_spec(cfg),
                "adapter_down": dense(d, cfg.adapter_rank, "embed", None),
                "adapter_up": dense(cfg.adapter_rank, 2 * cfg.d_ff,
                                    None, "ffn"),
                "linear": dense(d, d, "embed", None)}
    s: dict[str, Any] = {"ln1": norm_scale(d)}
    s["attn"] = mla_spec(cfg) if kind.startswith("mla") else attn_spec(cfg)
    if kind == "dec":
        s["ln_cross"] = norm_scale(d)
        s["cross"] = attn_spec(cfg)
    s["ln2"] = norm_scale(d)
    s["ffn"] = (moe_lib.moe_spec(cfg) if kind in ("moe", "mla_moe")
                else mlp_spec(cfg))
    if cfg.post_norms:
        s["post_ln1"] = norm_scale(d)
        s["post_ln2"] = norm_scale(d)
    return s


def shared_block_spec(cfg: ArchConfig) -> dict:
    """One shared transformer block of a hybrid model: attention reads
    ``concat(x, embedding)`` (``2 d_model``) and writes ``d_model``; no
    residual of its own."""
    d = cfg.d_model
    return {"ln_in": norm_scale(2 * d), "attn": attn_spec(cfg, 2 * d),
            "ln_ff": norm_scale(d), "ffn": mlp_spec(cfg)}


def group_spec(cfg: ArchConfig, unit: tuple[str, ...], rep: int) -> dict:
    stacked = {f"{i}:{kind}": layer_spec(cfg, kind)
               for i, kind in enumerate(unit)}
    return {"layers": pm.stack_tree(stacked, rep)}


def model_spec(cfg: ArchConfig) -> dict:
    spec: dict[str, Any] = {"embed": embedding(cfg.vocab_size, cfg.d_model)}
    if cfg.frontend is not None:
        spec["frontend_proj"] = dense(cfg.frontend_dim, cfg.d_model,
                                      None, "embed")
    for gi, (unit, rep) in enumerate(cfg.encoder_blocks):
        spec[f"enc{gi}"] = group_spec(cfg, unit, rep)
    if cfg.encoder_blocks:
        spec["enc_norm"] = norm_scale(cfg.d_model)
    for gi, (unit, rep) in enumerate(cfg.blocks):
        spec[f"g{gi}"] = group_spec(cfg, unit, rep)
    if cfg.hybrid_layers:
        spec["shared"] = {f"block{k}": shared_block_spec(cfg)
                          for k in range(cfg.num_mem_blocks)}
    spec["final_norm"] = norm_scale(cfg.d_model)
    if not cfg.tie_embeddings:
        spec["lm_head"] = dense(cfg.d_model, cfg.vocab_size, "embed", "vocab")
    if cfg.mtp_depth:
        spec["mtp"] = {"proj": dense(2 * cfg.d_model, cfg.d_model,
                                     "embed", None),
                       "layer": layer_spec(cfg, "dense"),
                       "norm": norm_scale(cfg.d_model)}
    return spec


# ---------------------------------------------------------------------------
# Cache specs (decode)
# ---------------------------------------------------------------------------
def layer_cache_spec(cfg: ArchConfig, kind: str, batch: int, max_len: int):
    if kind == "mamba":
        return ssm_lib.ssm_cache_spec(cfg, batch)
    if hybrid_block(kind) is not None:   # its attention's K/V, its SSM state
        return {**attn_cache_spec(cfg, batch, max_len),
                **ssm_lib.ssm_cache_spec(cfg, batch)}
    if kind.startswith("mla"):
        return mla_cache_spec(cfg, batch, max_len)
    if kind == "dec":
        hd = cfg.resolved_head_dim
        cross = {"k": ParamSpec((batch, cfg.num_kv_heads, max_len, hd),
                                ("batch", "kv_heads", "seq", None), "zeros",
                                dtype=jnp.bfloat16),
                 "v": ParamSpec((batch, cfg.num_kv_heads, max_len, hd),
                                ("batch", "kv_heads", "seq", None), "zeros",
                                dtype=jnp.bfloat16),
                 "index": ParamSpec((), (), "zeros", dtype=jnp.int32)}
        return {"self": attn_cache_spec(cfg, batch, max_len), "cross": cross}
    return attn_cache_spec(cfg, batch, max_len)


def cache_spec(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    spec = {}
    for gi, (unit, rep) in enumerate(cfg.blocks):
        g = {f"{i}:{kind}": layer_cache_spec(cfg, kind, batch, max_len)
             for i, kind in enumerate(unit)}
        spec[f"g{gi}"] = pm.stack_tree(g, rep)
    return spec


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _maybe_post(cfg, p, key, x):
    return rmsnorm_fwd(p[key], x, cfg.norm_eps) if cfg.post_norms else x


def layer_fwd(p: dict, x: jax.Array, kind: str, cfg: ArchConfig, *,
              positions: jax.Array, cache=None, enc_out=None, layer=None,
              emb=None, shared=None, length=None):
    """One layer. Returns (x, new_cache, aux_loss).  ``layer`` marks a
    layer-stacked self-attention cache (see :func:`attn_fwd`); ``emb`` and
    ``shared`` are a hybrid model's token embedding and shared blocks;
    ``length`` is the real length of a right-padded prefill chunk, which
    the SSM layers hold their state to."""
    aux = jnp.zeros((), jnp.float32)
    rs = cfg.residual_scale
    if kind == "mamba":
        h = rmsnorm_fwd(p["ln1"], x, cfg.norm_eps)
        h, new_cache = ssm_lib.ssm_fwd(p["mixer"], h, cfg, cache=cache,
                                       length=length)
        return x + rs * h, new_cache, aux
    if hybrid_block(kind) is not None:
        blk = shared[f"block{hybrid_block(kind)}"]
        x, new_cache = hybrid_fwd(p, blk, x, emb, cfg, positions=positions,
                                  cache=cache, layer=layer, length=length)
        return x, new_cache, aux

    h = rmsnorm_fwd(p["ln1"], x, cfg.norm_eps)
    if kind.startswith("mla"):
        h, self_cache = mla_fwd(p["attn"], h, cfg, positions=positions,
                                cache=cache if kind != "dec" else None)
    else:
        self_c = cache["self"] if (kind == "dec" and cache is not None) else cache
        h, self_cache = attn_fwd(p["attn"], h, cfg, kind=kind,
                                 positions=positions, cache=self_c,
                                 layer=layer)
    h = _maybe_post(cfg, p, "post_ln1", h)
    x = x + rs * h

    new_cache = self_cache
    if kind == "dec":
        hc = rmsnorm_fwd(p["ln_cross"], x, cfg.norm_eps)
        cross_c = cache["cross"] if cache is not None else None
        if cross_c is not None:
            hc, _ = attn_fwd(p["cross"], hc, cfg, kind="cross",
                             positions=positions, cache=cross_c)
        else:
            hc, _ = attn_fwd(p["cross"], hc, cfg, kind="cross",
                             positions=positions, x_kv=enc_out)
        x = x + rs * hc
        if cache is not None:
            new_cache = {"self": self_cache, "cross": cross_c}

    h = rmsnorm_fwd(p["ln2"], x, cfg.norm_eps)
    if kind in ("moe", "mla_moe"):
        b, s, d = h.shape
        y, aux = moe_lib.moe_fwd(p["ffn"], h.reshape(b * s, d), cfg)
        h = y.reshape(b, s, d)
    else:
        h = mlp_fwd(p["ffn"], h, cfg)
    h = _maybe_post(cfg, p, "post_ln2", h)
    return x + rs * h, new_cache, aux


def hybrid_fwd(p: dict, blk: dict, x: jax.Array, emb: jax.Array,
               cfg: ArchConfig, *, positions, cache=None, layer=None,
               length=None):
    """zamba2's hybrid layer (``Zamba2HybridLayer``): the shared block
    ``blk`` on ``concat(x, emb)``, then this occurrence's linear ``p``
    adds its output to the input of this layer's own Mamba-2 block; the
    residual is ``x`` alone:

        t = ln_in(concat(x, emb));  a = ln_ff(attn(t))
        f = Wdown . act(a Wgate + a Adn Aup_g) * (a Wup + a Adn Aup_u)
        x = x + mamba(ln1(x + f L))

    ``cache`` holds the attention's K/V leaves (``KV_LEAVES``) and the
    Mamba-2 block's ``conv``/``ssm`` state.  Returns (x, new_cache)."""
    t = jnp.concatenate([x, emb.astype(x.dtype)], axis=-1)
    t = rmsnorm_fwd(blk["ln_in"], t, cfg.norm_eps)
    kv_c = None if cache is None else {n: cache[n] for n in KV_LEAVES}
    a, kv_c = attn_fwd(blk["attn"], t, cfg, kind="hybrid",
                       positions=positions, cache=kv_c, layer=layer)
    a = rmsnorm_fwd(blk["ln_ff"], a, cfg.norm_eps)
    f = mlp_fwd(blk["ffn"], a, cfg,
                adapter=(p["adapter_down"], p["adapter_up"]))
    h = rmsnorm_fwd(p["ln1"], x + f @ p["linear"], cfg.norm_eps)
    ssm_c = None if cache is None else {"conv": cache["conv"],
                                        "ssm": cache["ssm"]}
    h, ssm_c = ssm_lib.ssm_fwd(p["mixer"], h, cfg, cache=ssm_c,
                               length=length)
    new_cache = None if cache is None else {**kv_c, **ssm_c}
    return x + cfg.residual_scale * h, new_cache


def group_fwd(gp: dict, x: jax.Array, unit: tuple[str, ...], rep: int,
              cfg: ArchConfig, *, positions, caches=None, enc_out=None,
              emb=None, shared=None, length=None):
    """Scan ``rep`` repetitions of ``unit``. Returns (x, new_caches, aux).

    In the ragged decode (2-D ``positions``) every self-attention K/V cache
    (the ``KV_LEAVES`` of a layer's cache) rides in the scan carry as its
    layer-stacked arrays, and each layer writes its rows into it in place;
    every other cache leaf (a hybrid layer's SSM state too) is scanned as
    ``xs`` and comes back as ``ys``.  ``emb``, ``shared`` and ``length``
    reach every layer (:func:`layer_fwd`).
    """
    ragged = caches is not None and getattr(positions, "ndim", 0) >= 2
    kv = {k: {n: c[n] for n in KV_LEAVES}
          for k, c in (caches or {}).items() if ragged and "k" in c}
    scanned = None
    if caches is not None:
        scanned = {}
        for k, c in caches.items():
            rest = {n: v for n, v in c.items()
                    if k not in kv or n not in KV_LEAVES}
            if rest:
                scanned[k] = rest
    extra = dict(emb=emb, shared=shared, length=length)

    def body(carry, xs):
        x, kv = carry
        layer_p, cache_sl, layer = xs
        kv = dict(kv)
        aux_total = jnp.zeros((), jnp.float32)
        new_cache_sl = {} if cache_sl is not None else None
        for i, kind in enumerate(unit):
            key = f"{i}:{kind}"
            p = layer_p[key]
            if key in kv:
                c = {**kv[key], **cache_sl.get(key, {})}
                x, nc, aux = layer_fwd(p, x, kind, cfg, positions=positions,
                                       cache=c, layer=layer, **extra)
                kv[key] = {n: nc[n] for n in KV_LEAVES}
                rest = {n: v for n, v in nc.items() if n not in KV_LEAVES}
                if rest:
                    new_cache_sl[key] = rest
            else:
                c = cache_sl[key] if cache_sl is not None else None
                x, nc, aux = layer_fwd(p, x, kind, cfg, positions=positions,
                                       cache=c, enc_out=enc_out, **extra)
                if new_cache_sl is not None:
                    new_cache_sl[key] = nc
            aux_total += aux
        return (x, kv), (new_cache_sl, aux_total)

    if cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    if not cfg.scan_layers:
        new_caches, auxs = [], []
        for r in range(rep):
            lp = jax.tree.map(lambda a: a[r], gp["layers"])
            cs = (jax.tree.map(lambda a: a[r], scanned)
                  if scanned is not None else None)
            (x, kv), (nc, aux) = body((x, kv), (lp, cs, r if kv else None))
            new_caches.append(nc)
            auxs.append(aux)
        new_caches = (jax.tree.map(lambda *a: jnp.stack(a), *new_caches)
                      if caches is not None else None)
        auxs = jnp.stack(auxs)
    else:
        layers = jnp.arange(rep, dtype=jnp.int32) if kv else None
        (x, kv), (new_caches, auxs) = jax.lax.scan(
            body, (x, kv), (gp["layers"], scanned, layers))
    if caches is not None:
        new_caches = {k: {**new_caches.get(k, {}), **kv.get(k, {})}
                      for k in caches}
    return x, new_caches, jnp.sum(auxs)


def embed_tokens(params: dict, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = params["embed"][tokens] * cfg.embed_scale
    h = h.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    return shd.constrain_logical(h, ("batch", None, None))


def unembed(params: dict, h: jax.Array, cfg: ArchConfig) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                            params["embed"].astype(jnp.float32))
    else:
        logits = h.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return shd.constrain_logical(logits, ("batch", None, "vocab"))


def encode(params: dict, cfg: ArchConfig, enc_in: jax.Array) -> jax.Array:
    """Encoder stack. enc_in: (B, S, frontend_dim) embeds or (B, S) tokens."""
    if enc_in.ndim == 3:
        h = (enc_in.astype(jnp.bfloat16) @ params["frontend_proj"])
    else:
        h = embed_tokens(params, enc_in, cfg)
    positions = jnp.arange(h.shape[1])
    for gi, (unit, rep) in enumerate(cfg.encoder_blocks):
        h, _, _ = group_fwd(params[f"enc{gi}"], h, unit, rep, cfg,
                            positions=positions)
    return rmsnorm_fwd(params["enc_norm"], h, cfg.norm_eps)


def forward(params: dict, cfg: ArchConfig, tokens: jax.Array, *,
            pos0: jax.Array | int = 0, caches: dict | None = None,
            enc_out: jax.Array | None = None,
            patch_embeds: jax.Array | None = None, length=None):
    """Decoder stack. Returns (hidden, new_caches, aux_loss).  ``length``:
    the real length of a right-padded chunk, for the SSM layers."""
    h = embed_tokens(params, tokens, cfg)
    if patch_embeds is not None:     # vlm stub: patches replace leading slots
        pe = (patch_embeds.astype(h.dtype) @ params["frontend_proj"])
        npatch = pe.shape[1]
        h = jnp.concatenate([pe, h[:, npatch:]], axis=1)
    if getattr(pos0, "ndim", 0) >= 1:
        # per-row start positions (B,) -> ragged (B, S) position grid; the
        # attention layers switch to per-row cache writes/masks on seeing it
        positions = pos0[:, None] + jnp.arange(tokens.shape[1])[None, :]
    else:
        positions = pos0 + jnp.arange(tokens.shape[1])

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: dict | None = {} if caches is not None else None
    extra = {"length": length}
    if cfg.hybrid_layers:
        extra.update(emb=h, shared=params["shared"])
    for gi, (unit, rep) in enumerate(cfg.blocks):
        c = caches[f"g{gi}"] if caches is not None else None
        h, nc, aux = group_fwd(params[f"g{gi}"], h, unit, rep, cfg,
                               positions=positions, caches=c, enc_out=enc_out,
                               **extra)
        h = shd.constrain_logical(h, ("batch", None, None))
        if new_caches is not None:
            new_caches[f"g{gi}"] = nc
        aux_total += aux
    h = rmsnorm_fwd(params["final_norm"], h, cfg.norm_eps)
    return h, new_caches, aux_total

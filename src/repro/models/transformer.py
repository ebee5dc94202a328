"""Unified model: decoder LMs, MoE, SSM/hybrid, enc-dec — one code path.

A model is a sequence of *groups*; each group is ``(unit, repeat)`` from
``ArchConfig.blocks``.  The unit (a tuple of layer kinds) becomes the body of
one ``lax.scan`` over ``repeat`` — so an 88-layer dense model compiles ONE
layer body, and gemma-2's (local, global) alternation compiles exactly two.
``shared_attn`` layers (zamba2) hold their parameters OUTSIDE the scanned
stack — one "bitstream", referenced by all repetitions (paper's operator
reuse).

Remat is applied to the scan body (``cfg.remat``: full | dots | none) — the
main activation-memory knob for the 4k-train shapes.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.configs.base import ArchConfig
from repro.models import params as pm
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (attn_cache_spec, attn_fwd, attn_spec,
                                 mla_cache_spec, mla_fwd, mla_spec, mlp_fwd,
                                 mlp_spec, rmsnorm_fwd)
from repro.models.params import ParamSpec, dense, embedding, norm_scale

ATTN_KINDS = ("dense", "local", "global", "shared_attn", "enc", "dec",
              "mla_dense", "moe", "mla_moe")


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------
def layer_spec(cfg: ArchConfig, kind: str) -> dict:
    d = cfg.d_model
    if kind == "mamba":
        return {"ln1": norm_scale(d), "mixer": ssm_lib.ssm_spec(cfg)}
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


def group_spec(cfg: ArchConfig, unit: tuple[str, ...], rep: int) -> dict:
    stacked = {}
    shared = {}
    for i, kind in enumerate(unit):
        if kind == "shared_attn":
            if "shared_attn" not in shared:      # one bitstream for the group
                shared["shared_attn"] = layer_spec(cfg, kind)
        else:
            stacked[f"{i}:{kind}"] = layer_spec(cfg, kind)
    out = {"layers": pm.stack_tree(stacked, rep)}
    if shared:
        out["shared"] = shared
    return out


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
        g = {}
        for i, kind in enumerate(unit):
            key = f"{i}:{kind}" if kind != "shared_attn" else f"{i}:shared_attn"
            g[key] = layer_cache_spec(cfg, kind, batch, max_len)
        spec[f"g{gi}"] = pm.stack_tree(g, rep)
    return spec


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _maybe_post(cfg, p, key, x):
    return rmsnorm_fwd(p[key], x, cfg.norm_eps) if cfg.post_norms else x


def layer_fwd(p: dict, x: jax.Array, kind: str, cfg: ArchConfig, *,
              positions: jax.Array, cache=None, enc_out=None, layer=None):
    """One layer. Returns (x, new_cache, aux_loss).  ``layer`` marks a
    layer-stacked self-attention cache (see :func:`attn_fwd`)."""
    aux = jnp.zeros((), jnp.float32)
    rs = cfg.residual_scale
    if kind == "mamba":
        h = rmsnorm_fwd(p["ln1"], x, cfg.norm_eps)
        h, new_cache = ssm_lib.ssm_fwd(p["mixer"], h, cfg, cache=cache)
        return x + rs * h, new_cache, aux

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


def group_fwd(gp: dict, x: jax.Array, unit: tuple[str, ...], rep: int,
              cfg: ArchConfig, *, positions, caches=None, enc_out=None):
    """Scan ``rep`` repetitions of ``unit``. Returns (x, new_caches, aux).

    In the ragged decode (2-D ``positions``) every self-attention K/V cache
    rides in the scan carry as its layer-stacked arrays, and each layer
    writes its rows into it in place; every other cache is scanned as
    ``xs`` and comes back as ``ys``.
    """
    shared = gp.get("shared", {})
    ragged = caches is not None and getattr(positions, "ndim", 0) >= 2
    kv = {k: c for k, c in (caches or {}).items() if ragged and "k" in c}
    scanned = (None if caches is None else
               {k: c for k, c in caches.items() if k not in kv})

    def body(carry, xs):
        x, kv = carry
        layer_p, cache_sl, layer = xs
        kv = dict(kv)
        aux_total = jnp.zeros((), jnp.float32)
        new_cache_sl = {} if cache_sl is not None else None
        for i, kind in enumerate(unit):
            key = f"{i}:{kind}"
            p = shared["shared_attn"] if kind == "shared_attn" else layer_p[key]
            if key in kv:
                x, kv[key], aux = layer_fwd(p, x, kind, cfg,
                                            positions=positions,
                                            cache=kv[key], layer=layer)
            else:
                c = cache_sl[key] if cache_sl is not None else None
                x, nc, aux = layer_fwd(p, x, kind, cfg, positions=positions,
                                       cache=c, enc_out=enc_out)
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
        new_caches = {**new_caches, **kv}
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
            patch_embeds: jax.Array | None = None):
    """Decoder stack. Returns (hidden, new_caches, aux_loss)."""
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
    for gi, (unit, rep) in enumerate(cfg.blocks):
        c = caches[f"g{gi}"] if caches is not None else None
        h, nc, aux = group_fwd(params[f"g{gi}"], h, unit, rep, cfg,
                               positions=positions, caches=c, enc_out=enc_out)
        h = shd.constrain_logical(h, ("batch", None, None))
        if new_caches is not None:
            new_caches[f"g{gi}"] = nc
        aux_total += aux
    h = rmsnorm_fwd(params["final_norm"], h, cfg.norm_eps)
    return h, new_caches, aux_total

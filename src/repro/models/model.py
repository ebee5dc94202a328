"""Top-level model API: loss, train-step pieces, prefill/decode.

Also the **overlay integration**: ``build_step_graph`` registers the model's
stages (embed, each layer group, head) as operators in the overlay library
and returns a DFG — the runtime interpreter assembles the executable step
exactly the way the paper assembles accelerators from bitstreams
(``examples/overlay_assembly.py`` and the fig-3 benchmark drive this path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import params as pm
from repro.models import transformer as tfm
from repro.models.transformer import cache_spec, model_spec


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None):
    """Mean next-token CE in f32 + accuracy. logits: (B,S,V), labels: (B,S).

    The gold-logit extraction uses a one-hot reduction rather than
    ``take_along_axis``: a gather over a model-sharded vocab axis forces the
    SPMD partitioner to all-gather the full logits; the one-hot einsum
    reduces locally and psums a (B, S) scalar field instead.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, v, dtype=jnp.float32)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / denom
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / denom
    return loss, acc


def loss_fn(params: dict, batch: dict, cfg: ArchConfig, *,
            aux_weight: float = 0.01):
    """Returns (loss, metrics). batch keys per family:
       lm:   tokens, labels            (labels = tokens shifted by caller)
       vlm:  + patch_embeds            (patch positions masked from loss)
       audio enc-dec: frames (B,S,F), tokens, labels
    """
    enc_out = None
    if cfg.is_encdec:
        enc_out = tfm.encode(params, cfg, batch["frames"])
    h, _, aux = tfm.forward(
        params, cfg, batch["tokens"], enc_out=enc_out,
        patch_embeds=batch.get("patch_embeds"))
    logits = tfm.unembed(params, h, cfg)

    mask = batch.get("mask")
    if mask is None and "patch_embeds" in batch:
        npatch = batch["patch_embeds"].shape[1]
        pos = jnp.arange(batch["tokens"].shape[1])[None]
        mask = (pos >= npatch).astype(jnp.float32) * \
            jnp.ones_like(batch["labels"], jnp.float32)
    ce, acc = cross_entropy(logits, batch["labels"], mask)

    loss = ce + aux_weight * aux
    if cfg.mtp_depth:
        # deepseek-v3 multi-token prediction (depth 1): one extra layer sees
        # [h_t ; emb(label_t)] and predicts label_{t+1} (i.e. token t+2).
        mtp = params["mtp"]
        lbl_emb = tfm.embed_tokens(params, batch["labels"], cfg)
        h_in = jnp.concatenate([h[:, :-1], lbl_emb[:, :-1]], axis=-1).astype(
            lbl_emb.dtype) @ mtp["proj"]
        h2, _, _ = tfm.layer_fwd(mtp["layer"], h_in, "dense", cfg,
                                 positions=jnp.arange(h_in.shape[1]))
        h2 = tfm.rmsnorm_fwd(mtp["norm"], h2, cfg.norm_eps)
        logits2 = tfm.unembed(params, h2, cfg)
        ce2, _ = cross_entropy(logits2, batch["labels"][:, 1:], None)
        loss = loss + 0.3 * ce2
    return loss, {"ce": ce, "acc": acc, "aux": aux}


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    return pm.init(cache_spec(cfg, batch, max_len), jax.random.PRNGKey(0))


def prefill(params: dict, cfg: ArchConfig, tokens: jax.Array, caches: dict,
            *, enc_in: jax.Array | None = None,
            patch_embeds: jax.Array | None = None):
    """Run the prompt through the decoder, filling caches.

    Returns (logits_last (B, V), caches). For enc-dec models, also runs the
    encoder and fills cross-attn caches.
    """
    enc_out = None
    if cfg.is_encdec:
        enc_out = tfm.encode(params, cfg, enc_in)
        caches = _fill_cross_caches(params, cfg, enc_out, caches)
    h, caches, _ = tfm.forward(params, cfg, tokens, pos0=0, caches=caches,
                               enc_out=enc_out, patch_embeds=patch_embeds)
    logits = tfm.unembed(params, h[:, -1:], cfg)
    return logits[:, 0], caches


def _fill_cross_caches(params, cfg, enc_out, caches):
    """Precompute cross-attention K/V from encoder output (once)."""
    hd = cfg.resolved_head_dim
    b, s, _ = enc_out.shape
    new = dict(caches)
    for gi, (unit, rep) in enumerate(cfg.blocks):
        if "dec" not in unit:
            continue
        g = dict(caches[f"g{gi}"])
        for i, kind in enumerate(unit):
            if kind != "dec":
                continue
            key = f"{i}:{kind}"
            def per_layer(lp):
                k = (enc_out @ lp["cross"]["wk"]).reshape(
                    b, s, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
                v = (enc_out @ lp["cross"]["wv"]).reshape(
                    b, s, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
                return k, v
            ks, vs = jax.vmap(per_layer)(params[f"g{gi}"]["layers"][key])
            entry = dict(g[key])
            cross = dict(entry["cross"])
            # stacked cache dims: (rep, B, Hkv, Smax, hd); seq axis = 3
            cross["k"] = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(cross["k"]), ks.astype(cross["k"].dtype),
                0, axis=3)
            cross["v"] = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(cross["v"]), vs.astype(cross["v"].dtype),
                0, axis=3)
            cross["index"] = jnp.full((rep,), s, jnp.int32)
            entry["cross"] = cross
            g[key] = entry
        new[f"g{gi}"] = g
    return new


def decode_step(params: dict, cfg: ArchConfig, token: jax.Array, caches: dict,
                *, positions: jax.Array | None = None):
    """One token for every sequence in the batch. token: (B, 1).

    ``positions=None`` reads the shared scalar cache index (uniform batch —
    every row at the same depth).  Pass a (B,) int32 array to decode each
    row at its OWN KV position instead: ragged continuous batching, where
    co-resident slots hold prompts of different lengths (serving engine).
    """
    pos0 = _current_index(cfg, caches) if positions is None else positions
    h, caches, _ = tfm.forward(params, cfg, token, pos0=pos0, caches=caches)
    return tfm.unembed(params, h, cfg)[:, 0], caches


def prefill_chunk(params: dict, cfg: ArchConfig, tokens: jax.Array,
                  caches: dict, last_index: jax.Array):
    """Prefill ONE fixed-size chunk of a prompt into ``caches``.

    ``tokens``: (B, C) — the next C prompt tokens, starting at the cache's
    current index.  The final chunk of a prompt may be right-padded to a
    power-of-two bucket; padded positions write garbage K/V beyond the real
    prompt, which is causally masked here and overwritten position-by-
    position by decode before any query can attend to it.  ``last_index``
    is a *traced* int32 scalar selecting the in-chunk position whose
    logits are returned — the chunk length C is the only static shape, so
    one compiled signature serves every prompt sharing a bucket size.
    SSM layers cannot have padding overwritten later, so they are told the
    real length ``last_index + 1`` and hold their conv window and SSD state
    to it.  Returns (logits (B, V), caches).
    """
    pos0 = _current_index(cfg, caches)
    length = last_index + 1 if cfg.ssm_layers else None
    h, caches, _ = tfm.forward(params, cfg, tokens, pos0=pos0, caches=caches,
                               length=length)
    logits = tfm.unembed(params, h, cfg)
    sel = jax.lax.dynamic_slice_in_dim(logits, last_index, 1, axis=1)
    return sel[:, 0], caches


def _current_index(cfg: ArchConfig, caches: dict):
    """Fish the scalar decode position out of the (stacked) cache tree."""
    for gi, (unit, rep) in enumerate(cfg.blocks):
        g = caches[f"g{gi}"]
        for i, kind in enumerate(unit):
            entry = g[f"{i}:{kind}"]
            if kind == "mamba":
                continue
            if kind == "dec":
                entry = entry["self"]
            if "index" in entry:
                return entry["index"][0]   # stacked (rep,) — all equal
    return jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# The pooled cache of a serving engine: one batch row per slot
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _slot_spec(cfg: ArchConfig) -> dict:
    # built once per config: admission reads it inside the serving loop,
    # and an 81-layer spec takes ~1 ms to build
    return cache_spec(cfg, 1, 1)


def install_slot(cfg: ArchConfig, pool: dict, one: dict, slot: int) -> dict:
    """Scatter the batch-1 cache ``one`` into row ``slot`` of ``pool``.

    Each leaf's batch axis is the one ``cache_spec`` names ``"batch"``,
    behind the stacked layer axis.  A leaf with no batch axis is a layer's
    scalar ``index``, shared by every slot: it keeps the max (the ragged
    decode reads each slot's own position instead)."""
    def place(spec, p, o):
        if "batch" not in spec.axes:
            return jnp.maximum(p, o.astype(p.dtype))
        return jax.lax.dynamic_update_slice_in_dim(
            p, o.astype(p.dtype), slot, axis=spec.axes.index("batch"))

    return jax.tree.map(place, _slot_spec(cfg), pool, one,
                        is_leaf=pm.is_spec)


def donation_probes(caches: dict) -> tuple:
    """One self-attention K leaf and one SSD state leaf of ``caches`` (None
    where the model has none): after a call that donates ``caches``, each
    one's ``is_deleted()`` says whether that state was consumed."""
    def first(name):
        for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
            if any(getattr(k, "key", None) == name for k in path):
                return leaf
        return None

    return first("k"), first("ssm")


# ---------------------------------------------------------------------------
# Overlay integration: the model step as an assembled DFG
# ---------------------------------------------------------------------------
def build_step_graph(cfg: ArchConfig, batch_shape: tuple[int, int]):
    """Register model stages as overlay operators; return the step Graph.

    Stages: embed -> g0 -> g1 ... -> head.  Each stage is a LARGE operator
    taking (params, x); the params input node fans out to every stage (the
    controller's LD_CONST of per-tile configuration).
    """
    from repro.core.graph import Graph
    from repro.core.patterns import Operator, TileClass

    b, s = batch_shape
    spec = model_spec(cfg)
    abstract_params = pm.abstract(spec)

    g = Graph(f"{cfg.name}.fwd")
    p_in = g.input_tree("params", abstract_params)
    tok = g.input("tokens", (b, s), jnp.int32)

    embed_op = Operator(f"{cfg.name}/embed", 2,
                        lambda p, t: tfm.embed_tokens(p, t, cfg),
                        TileClass.LARGE)
    h = g.apply(embed_op, p_in, tok)

    positions = jnp.arange(s)
    emb = h
    for gi, (unit, rep) in enumerate(cfg.blocks):
        if cfg.hybrid_layers:   # the shared blocks also read the embedding
            def stage_fn(p, x, e, _gi=gi, _unit=unit, _rep=rep):
                y, _, _ = tfm.group_fwd(p[f"g{_gi}"], x, _unit, _rep, cfg,
                                        positions=positions, emb=e,
                                        shared=p["shared"])
                return y
            op = Operator(f"{cfg.name}/g{gi}", 3, stage_fn, TileClass.LARGE)
            h = g.apply(op, p_in, h, emb)
            continue

        def stage_fn(p, x, _gi=gi, _unit=unit, _rep=rep):
            y, _, _ = tfm.group_fwd(p[f"g{_gi}"], x, _unit, _rep, cfg,
                                    positions=positions)
            return y
        op = Operator(f"{cfg.name}/g{gi}", 2, stage_fn, TileClass.LARGE)
        h = g.apply(op, p_in, h)

    head_op = Operator(
        f"{cfg.name}/head", 2,
        lambda p, x: tfm.unembed(p, tfm.rmsnorm_fwd(
            p["final_norm"], x, cfg.norm_eps), cfg),
        TileClass.LARGE)
    out = g.apply(head_op, p_in, h)
    g.output(out)
    return g

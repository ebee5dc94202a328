"""Plain reference of a dense decoder, and its lower-precision control.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``, with no kernel, cache, chunking or batching of the
program's: the whole sequence at once, causal softmax attention, layer by
layer.  The weights are made again from the seed by ``bench.weights``, one
layer at a time, so the reference takes nothing that the program built and
fits beside whatever the process still holds.

Equations (the repo's dense decoder, which follows the published models):

    h  = embed[tokens] * embed_scale
    h += rs * Wo . attn(rope(Wq . n1(h)), rope(Wk . n1(h)), Wv . n1(h))
    h += rs * Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))
    logits = head . nf(h)            (head = embed^T where tied)

with ``n(x) = x / sqrt(mean(x^2) + eps) * scale``, rotate-half RoPE over
``head_dim`` at positions ``0..T-1``, scores scaled by ``head_dim^-0.5``.

The control is the same computation with every matrix product taken in
float8 (e4m3, one absmax scale per tensor, for the weight and for the
activation that meets it): the next precision below the bfloat16 the
configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Dims, layer_weights, root_key, top_weight

F32 = jnp.float32
GROUP = 4          # sequences per reference call (padded with dummies)
PAD = 256          # sequence lengths are padded up to a multiple of this


def _q8(x: jax.Array) -> jax.Array:
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x), _q8(w)
    return x @ w


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x: (N, T, H, D), positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs        # (T, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(dims: Dims, h, w, fp8: bool):
    w = {k: v.astype(F32) for k, v in w.items()}
    n, t, _ = h.shape
    hd, hq, hkv = dims.head_dim, dims.heads, dims.kv_heads
    a = _norm(h, w["ln1"], dims.norm_eps)
    q = _rope(_mm(a, w["wq"], fp8).reshape(n, t, hq, hd), dims.rope_theta)
    k = _rope(_mm(a, w["wk"], fp8).reshape(n, t, hkv, hd), dims.rope_theta)
    v = _mm(a, w["wv"], fp8).reshape(n, t, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("nthd,nshd->nhts", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("nhts,nshd->nthd", p, v).reshape(n, t, hq * hd)
    h = h + dims.residual_scale * _mm(o, w["wo"], fp8)
    b = _norm(h, w["ln2"], dims.norm_eps)
    m = jax.nn.silu(_mm(b, w["w_gate"], fp8)) * _mm(b, w["w_up"], fp8)
    return h + dims.residual_scale * _mm(m, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(dims: Dims, emb, tokens):
    return emb.astype(F32)[tokens] * dims.embed_scale


def _logits(dims: Dims, h, final_norm, head, fp8: bool):
    """h: (M, d) -> (M, V).  ``head`` is (d, V), or the (V, d) embedding
    where tied."""
    x = _norm(h, final_norm.astype(F32), dims.norm_eps)
    w = head.astype(F32)
    return _mm(x, w.T if dims.tied else w, fp8)


@functools.partial(jax.jit, static_argnums=(0,))
def _read(dims: Dims, h_ref, h_low, pos, toks, final_norm, head):
    """Gaps at positions ``pos`` of one sequence: of the served ``toks``,
    and (where ``h_low`` is given) of the float8 computation's first
    choice, both below the reference's best."""
    ref = _logits(dims, h_ref[pos], final_norm, head, False)
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(pos.shape[0])
    served = best - ref[rows, toks]
    if h_low is None:
        return served, None
    low = _logits(dims, h_low[pos], final_norm, head, True)
    return served, best - ref[rows, jnp.argmax(low, axis=-1)]


def _groups(seqs: list[list[int]]):
    """Blocks of GROUP token sequences, each padded to one length."""
    order = sorted(range(len(seqs)), key=lambda i: len(seqs[i]))
    for g in range(0, len(order), GROUP):
        idx = order[g:g + GROUP]
        t = PAD * math.ceil(max(len(seqs[i]) for i in idx) / PAD)
        toks = np.zeros((GROUP, t), np.int32)
        for row, i in enumerate(idx):
            toks[row, :len(seqs[i])] = seqs[i]
        yield idx, toks


def served_gaps(dims: Dims, seed: int, served: list[tuple[list[int], list[int]]],
                *, control: bool = False) -> dict[str, np.ndarray]:
    """Teacher-force each (prompt, served tokens) pair through the reference
    and read, for every served token, how far its reference logit lies
    below the reference's best at that position.

    Returns ``{"program": gaps}``, and with ``control`` also
    ``{"control": gaps}``: the reference's gap of the token that the float8
    computation puts first at the same positions.
    """
    root = root_key(seed)
    seqs = [list(p) + list(s[:-1]) for p, s in served]
    with jax.default_matmul_precision("highest"):
        blocks = list(_groups(seqs))
        emb = top_weight(dims, "embed", root)
        streams = [False, True] if control else [False]
        hs = {fp8: [_embed(dims, emb, jnp.asarray(toks)) for _, toks in blocks]
              for fp8 in streams}
        for layer in range(dims.layers):
            w = layer_weights(dims, root, layer)
            for fp8 in streams:
                hs[fp8] = [_layer(dims, h, w, fp8) for h in hs[fp8]]
            del w
        final = top_weight(dims, "final_norm", root)
        head = emb if dims.tied else top_weight(dims, "lm_head", root)
        gaps = {"program": [], "control": []}
        for b, (idx, _) in enumerate(blocks):
            for row, i in enumerate(idx):
                prompt, toks = served[i]
                n = len(toks)
                m = PAD * math.ceil(n / PAD)     # one compiled shape per pad
                pos = np.zeros(m, np.int32)
                pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
                tk = np.zeros(m, np.int32)
                tk[:n] = toks
                low = hs[True][b][row] if control else None
                g_served, g_low = _read(dims, hs[False][b][row], low,
                                        jnp.asarray(pos), jnp.asarray(tk),
                                        final, head)
                gaps["program"].append(np.asarray(g_served)[:n])
                if control:
                    gaps["control"].append(np.asarray(g_low)[:n])
    return {k: np.concatenate(v) for k, v in gaps.items() if v}

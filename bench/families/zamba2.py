"""The zamba2 hybrid family (Zamba2-7B-Instruct): Mamba2 layers, and before
some of them a hybrid layer that calls one of ``num_mem_blocks`` shared
transformer blocks in turn.

What it holds: ``Dims`` from a configuration's published keys (the
catalog's own, at the file's top level, cut to the stage it runs) and its
``stage`` keys; the seeded weights in the program's layout; the plain
float32 reference and its float8 control (``served_gaps``, ``logits``);
and the counts the per-layer readers need.

Equations (Zamba2's published code, HF ``modeling_zamba2``), with ``x`` the
residual stream and ``e`` the token embedding:

    every layer l:      x = x + M_l(n1(x + s_l))             (s_l = 0 unless hybrid)
    hybrid occurrence j, block k = j mod num_mem_blocks:
        t = ln_in[k](concat(x, e))                          width 2 d_model
        a = ln_ff[k](Wo[k] . attn(rope(Wq[k] t), rope(Wk[k] t), Wv[k] t))
        g = a Wgate[k] + (a Adn_j) Aup_j[:, :F];  u = a Wup[k] + (a Adn_j) Aup_j[:, F:]
        s_l = (gelu(g) * u) Wdown[k] L_j                    (exact erf GELU)
    M (Mamba2, ngroups G):
        z, xs, B, C, dt = u Wz, u Wx, u Wb, u Wc, u Wdt;  xs, B, C = silu(conv(xs|B|C) + bias)
        dt = softplus(dt + dt_bias);  A = -exp(A_log);  head h reads B, C of group h // (H / G)
        h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t;  y_t = C_t . h_t + D x_t
        M = Wout . gnorm(y * silu(z))                       (RMSNorm over each group's d_inner / G)
    logits = embed . n_f(x)                                 (tied head)

Attention is causal softmax over all heads (MHA), scores scaled by
``(attention_head_dim / 2) ** -0.5``, rotate-half RoPE over the whole head
at positions ``0..T-1``.  The reference takes no kernel, cache, chunking or
batching of the program's: the whole sequence at once, the SSM as the
per-step recurrence (``lax.scan`` over positions), in float32 under
``default_matmul_precision("highest")``, weights made again from the seed
one layer (and one shared block) at a time.  The control is the same with
every matrix product taken in float8 (e4m3), as ``bench/reference.py``.

Departures from the published model, all shared by program and reference:
the stage holds published layers ``stage.first_layer`` on, whose first
hybrid is occurrence ``stage.first_occurrence``; the tied embedding and
head sit on this stage, and the shared blocks' input ``e`` is that
embedding.  The gated RMSNorm multiplies by its scale in float32 (HF casts
to the activation dtype first).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _groups, _mm, _norm, _read, _rope
from bench.weights import DTYPES, root_key

F32 = jnp.float32
WEIGHT_BYTES = 2        # bfloat16 matrices, conv weights and biases, embedding
F32_BYTES = 4           # norm scales, A_log, D, dt_bias, SSD state


@dataclasses.dataclass(frozen=True)
class Dims:
    """The widths and layer pattern of one stage of a Zamba2 model."""

    kinds: tuple[str, ...]      # per stage layer: "mamba" or "hybrid<k>"
    d_model: int
    mamba_heads: int
    mamba_headdim: int
    d_state: int
    ngroups: int
    d_conv: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    adapter_rank: int
    mem_blocks: int
    vocab: int
    chunk_size: int
    norm_eps: float
    rope_theta: float
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    dtype: str = "bfloat16"
    embed_std: float = 0.02
    tied: bool = True
    embed_scale: float = 1.0

    @classmethod
    def from_config(cls, conf: dict) -> "Dims":
        """From the published keys of ``conf`` (the catalog's, cut to the
        stage: ``layers_block_type`` lists the stage's layers,
        ``hybrid_layer_ids`` their published ids) and ``conf["stage"]``
        (``first_layer``, the published id of the stage's first layer, and
        ``first_occurrence``, the index among all hybrids of its first
        hybrid)."""
        p, st = conf, conf["stage"]
        types = p["layers_block_type"]
        if len(types) != p["num_hidden_layers"] or \
                p["mamba_expand"] * p["hidden_size"] != \
                p["n_mamba_heads"] * p["mamba_headdim"] or \
                p["attention_hidden_size"] != 2 * p["hidden_size"] or \
                p["num_attention_heads"] * p["attention_head_dim"] != \
                p["attention_hidden_size"]:
            raise ValueError("inconsistent zamba2 widths")
        ids = [st["first_layer"] + i for i, t in enumerate(types)
               if t == "hybrid"]
        if ids != list(p["hybrid_layer_ids"]):
            raise ValueError(f"hybrid ids {ids} != {p['hybrid_layer_ids']}")
        kinds, occ = [], st["first_occurrence"]
        for t in types:
            if t == "hybrid":
                kinds.append(f"hybrid{occ % p['num_mem_blocks']}")
                occ += 1
            else:
                kinds.append("mamba")
        return cls(
            kinds=tuple(kinds), d_model=p["hidden_size"],
            mamba_heads=p["n_mamba_heads"], mamba_headdim=p["mamba_headdim"],
            d_state=p["mamba_d_state"], ngroups=p["mamba_ngroups"],
            d_conv=p["mamba_d_conv"], heads=p["num_attention_heads"],
            kv_heads=p["num_key_value_heads"],
            head_dim=p["attention_head_dim"],
            d_ff=p["intermediate_size"], adapter_rank=p["adapter_rank"],
            mem_blocks=p["num_mem_blocks"], vocab=p["vocab_size"],
            chunk_size=p["chunk_size"], norm_eps=p["rms_norm_eps"],
            rope_theta=float(p["rope_theta"]),
            time_step_min=p["time_step_min"],
            time_step_max=p["time_step_max"],
            time_step_floor=p["time_step_floor"],
            dtype=conf.get("dtype", "bfloat16"),
            embed_std=conf.get("embed_std", 0.02))

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_headdim

    @property
    def hybrids(self) -> int:
        return sum(k != "mamba" for k in self.kinds)

    def block(self, layer: int) -> int | None:
        k = self.kinds[layer]
        return None if k == "mamba" else int(k[len("hybrid"):])

    def layer_shapes(self, hybrid: bool) -> dict[str, tuple[int, ...]]:
        """A layer's own leaves: its Mamba2 block, and a hybrid's adapter
        and linear."""
        d, di, h = self.d_model, self.d_inner, self.mamba_heads
        gn, w = self.ngroups * self.d_state, self.d_conv
        out = {"ln1": (d,), "w_z": (d, di), "w_x": (d, di), "w_b": (d, gn),
               "w_c": (d, gn), "w_dt": (d, h), "conv_x": (w, di),
               "conv_b": (w, gn), "conv_c": (w, gn), "conv_bias_x": (di,),
               "conv_bias_b": (gn,), "conv_bias_c": (gn,), "a_log": (h,),
               "d_skip": (h,), "dt_bias": (h,), "gate_norm": (di,),
               "out_proj": (di, d)}
        if hybrid:
            out.update(adapter_down=(d, self.adapter_rank),
                       adapter_up=(self.adapter_rank, 2 * self.d_ff),
                       linear=(d, d))
        return out

    def block_shapes(self) -> dict[str, tuple[int, ...]]:
        """One shared transformer block."""
        d, hd = self.d_model, self.head_dim
        return {"ln_in": (2 * d,), "wq": (2 * d, self.heads * hd),
                "wk": (2 * d, self.kv_heads * hd),
                "wv": (2 * d, self.kv_heads * hd),
                "wo": (self.heads * hd, d), "ln_ff": (d,),
                "w_gate": (d, self.d_ff), "w_up": (d, self.d_ff),
                "w_down": (self.d_ff, d)}

    def top_shapes(self) -> dict[str, tuple[int, ...]]:
        return {"embed": (self.vocab, self.d_model),
                "final_norm": (self.d_model,)}


# ---------------------------------------------------------------------------
# weights: every leaf from its own key, fold_in(fold_in(root, leaf), index)
# with index the stage layer, the shared block, or 0 for the top leaves
# ---------------------------------------------------------------------------
_LEAVES = ("embed", "final_norm", "ln1", "w_z", "w_x", "w_b", "w_c", "w_dt",
           "conv_x", "conv_b", "conv_c", "conv_bias_x", "conv_bias_b",
           "conv_bias_c", "a_log", "d_skip", "dt_bias", "gate_norm",
           "out_proj", "adapter_down", "adapter_up", "linear", "ln_in", "wq",
           "wk", "wv", "wo", "ln_ff", "w_gate", "w_up", "w_down")
_SCALES = ("final_norm", "ln1", "gate_norm", "ln_in", "ln_ff", "d_skip")
_F32 = _SCALES + ("a_log", "dt_bias")


def _draw(key, name: str, shape, dims: Dims) -> jax.Array:
    """Matrices N(0, 1/fan_in); conv weights N(0, 1/d_conv); conv biases
    0.1 N(0, 1); norm scales and D 1 + 0.1 N(0, 1); A_log log U(1, 16);
    dt_bias the inverse softplus of a log-uniform dt in [time_step_min,
    time_step_max] floored at time_step_floor (Mamba2's own init laws);
    the embedding N(0, embed_std^2)."""
    if name in _SCALES:
        return 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "dt_bias":
        lo, hi = math.log(dims.time_step_min), math.log(dims.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, F32, lo, hi))
        dt = jnp.maximum(dt, dims.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.startswith("conv_bias"):
        std = 0.1
    elif name == "embed":
        std = dims.embed_std
    else:
        std = 1.0 / math.sqrt(shape[0])
    w = jax.random.normal(key, shape, F32) * std
    return w.astype(DTYPES[dims.dtype])


def _key(root, name: str, index):
    return jax.random.fold_in(jax.random.fold_in(root, _LEAVES.index(name)),
                              index)


def layer_weights(dims: Dims, root, layer: int) -> dict[str, jax.Array]:
    """The leaves of stage layer ``layer``, as the program is given them."""
    return _layer_weights(dims, root, layer, dims.block(layer) is not None)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_weights(dims: Dims, root, layer, hybrid: bool):
    return {n: _draw(_key(root, n, layer), n, s, dims)
            for n, s in dims.layer_shapes(hybrid).items()}


@functools.partial(jax.jit, static_argnums=(0, 2))
def block_weights(dims: Dims, root, block: int) -> dict[str, jax.Array]:
    return {n: _draw(_key(root, n, block), n, s, dims)
            for n, s in dims.block_shapes().items()}


@functools.partial(jax.jit, static_argnums=(0, 1))
def top_weight(dims: Dims, name: str, root) -> jax.Array:
    return _draw(_key(root, name, 0), name, dims.top_shapes()[name], dims)


_MIXER = ("w_z", "w_x", "w_b", "w_c", "w_dt", "conv_x", "conv_b", "conv_c",
          "conv_bias_x", "conv_bias_b", "conv_bias_c", "a_log", "d_skip",
          "dt_bias", "gate_norm", "out_proj")


def _program_layer(w: dict) -> dict:
    out = {"ln1": w["ln1"], "mixer": {n: w[n] for n in _MIXER}}
    out.update({n: w[n] for n in ("adapter_down", "adapter_up", "linear")
                if n in w})
    return out


def _unit(dims: Dims) -> tuple[tuple[str, ...], int]:
    """The shortest unit whose repetitions make the stage's layers."""
    n = dims.layers
    for u in range(1, n + 1):
        if n % u == 0 and dims.kinds == dims.kinds[:u] * (n // u):
            return dims.kinds[:u], n // u
    raise AssertionError("unreachable")


@functools.partial(jax.jit, static_argnums=(0,))
def program_params(dims: Dims, root) -> dict:
    """Every weight in the program's layout (``models/transformer.py``
    ``model_spec``: one scanned group of the stage's unit, the shared
    blocks at the top level), made on the device in one call."""
    unit, rep = _unit(dims)
    layers = {}
    for i, kind in enumerate(unit):
        per = [_program_layer(layer_weights(dims, root, r * len(unit) + i))
               for r in range(rep)]
        layers[f"{i}:{kind}"] = jax.tree.map(lambda *a: jnp.stack(a), *per)
    blocks = {}
    for k in range(dims.mem_blocks):
        w = block_weights(dims, root, k)
        blocks[f"block{k}"] = {
            "ln_in": w["ln_in"], "ln_ff": w["ln_ff"],
            "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
            "ffn": {n: w[n] for n in ("w_gate", "w_up", "w_down")}}
    return {"embed": top_weight(dims, "embed", root),
            "g0": {"layers": layers}, "shared": blocks,
            "final_norm": top_weight(dims, "final_norm", root)}


def program_config(conf: dict, dims: Dims):
    """The program's registered config of the arch, run at the benchmark's
    widths and stage: it has to be a hybrid of Mamba2 layers and shared
    blocks with the published exact GELU, and every number the benchmark
    states replaces the program's own."""
    from bench.harness import BenchError
    from repro.configs import get_config

    cfg = get_config(conf["arch"])
    act = {"gelu": "gelu_exact"}.get(conf["hidden_act"])
    # a program without hybrid layers has no such property
    if not getattr(cfg, "hybrid_layers", False) or cfg.act != act:
        raise BenchError(f"program config {cfg.name} is not a zamba2 hybrid "
                         f"with {conf['hidden_act']}")
    return cfg.scaled(
        d_model=dims.d_model, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab, blocks=(_unit(dims),),
        query_pre_attn_scalar=dims.head_dim / 2,
        ssm_state=dims.d_state, ssm_head_dim=dims.mamba_headdim,
        ssm_expand=dims.d_inner // dims.d_model, ssm_groups=dims.ngroups,
        ssm_conv_width=dims.d_conv, ssm_chunk=dims.chunk_size,
        num_mem_blocks=dims.mem_blocks, adapter_rank=dims.adapter_rank,
        tie_embeddings=True, embed_scale=1.0, residual_scale=1.0,
        norm_eps=dims.norm_eps, rope_theta=dims.rope_theta, dtype=dims.dtype)


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def _causal_conv(u, w, b):
    """Depthwise causal conv over positions with zero history.
    u: (N, T, C), w: (W, C), b: (C,)."""
    width, t = w.shape[0], u.shape[1]
    full = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(w[i] * full[:, i:i + t] for i in range(width)) + b


def _mamba(dims: Dims, x, w, fp8: bool):
    """x + Mamba2(n1(x)), the SSM as its per-step recurrence."""
    n, t, _ = x.shape
    h, p, s, g = dims.mamba_heads, dims.mamba_headdim, dims.d_state, \
        dims.ngroups
    u = _norm(x, w["ln1"], dims.norm_eps)
    z = _mm(u, w["w_z"], fp8)
    xbc = jnp.concatenate([_mm(u, w[k], fp8) for k in ("w_x", "w_b", "w_c")],
                          -1)
    conv_w = jnp.concatenate([w["conv_x"], w["conv_b"], w["conv_c"]], -1)
    conv_b = jnp.concatenate([w["conv_bias_x"], w["conv_bias_b"],
                              w["conv_bias_c"]], -1)
    xbc = jax.nn.silu(_causal_conv(xbc, conv_w, conv_b))
    xs = xbc[..., :dims.d_inner].reshape(n, t, h, p)
    bm = xbc[..., dims.d_inner:dims.d_inner + g * s].reshape(n, t, g, s)
    cm = xbc[..., dims.d_inner + g * s:].reshape(n, t, g, s)
    # head hh reads group hh // (h / g)
    bm, cm = (jnp.repeat(m, h // g, axis=2) for m in (bm, cm))
    dt = jax.nn.softplus(_mm(u, w["w_dt"], fp8) + w["dt_bias"])   # (N,T,H)
    decay = jnp.exp(dt * -jnp.exp(w["a_log"]))

    def step(state, inp):                                  # state (N,H,S,P)
        dec, dt_t, b_t, c_t, x_t = inp
        state = dec[..., None, None] * state + \
            dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :]
        return state, jnp.einsum("nhs,nhsp->nhp", c_t, state)

    seq = [jnp.moveaxis(a, 1, 0) for a in (decay, dt, bm, cm, xs)]
    _, y = jax.lax.scan(step, jnp.zeros((n, h, s, p), F32), seq)
    y = jnp.moveaxis(y, 0, 1) + w["d_skip"][:, None] * xs         # (N,T,H,P)
    y = y.reshape(n, t, dims.d_inner) * jax.nn.silu(z)
    yg = y.reshape(n, t, g, dims.d_inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + dims.norm_eps)
    y = yg.reshape(n, t, dims.d_inner) * w["gate_norm"]
    return _mm(y, w["out_proj"], fp8)


def _shared(dims: Dims, x, e, w, blk, fp8: bool):
    """The hybrid's shared-block term s_l, through the occurrence's
    adapter and linear."""
    n, t, _ = x.shape
    hd, hq, hkv = dims.head_dim, dims.heads, dims.kv_heads
    a = _norm(jnp.concatenate([x, e], -1), blk["ln_in"], dims.norm_eps)
    q = _rope(_mm(a, blk["wq"], fp8).reshape(n, t, hq, hd), dims.rope_theta)
    k = _rope(_mm(a, blk["wk"], fp8).reshape(n, t, hkv, hd), dims.rope_theta)
    v = _mm(a, blk["wv"], fp8).reshape(n, t, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    sc = jnp.einsum("nthd,nshd->nhts", q, k) * (hd / 2) ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("nhts,nshd->nthd", pr, v).reshape(n, t, hq * hd)
    a = _norm(_mm(o, blk["wo"], fp8), blk["ln_ff"], dims.norm_eps)
    lo = _mm(_mm(a, w["adapter_down"], fp8), w["adapter_up"], fp8)
    gate = _mm(a, blk["w_gate"], fp8) + lo[..., :dims.d_ff]
    up = _mm(a, blk["w_up"], fp8) + lo[..., dims.d_ff:]
    f = _mm(jax.nn.gelu(gate, approximate=False) * up, blk["w_down"], fp8)
    return _mm(f, w["linear"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _layer(dims: Dims, x, e, w, blk, fp8: bool):
    """One stage layer; ``blk`` is its shared block's weights, or None."""
    w = {k: v.astype(F32) for k, v in w.items()}
    if blk is None:
        return x + _mamba(dims, x, w, fp8)
    blk = {k: v.astype(F32) for k, v in blk.items()}
    return x + _mamba(dims, x + _shared(dims, x, e, w, blk, fp8), w, fp8)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(dims: Dims, emb, tokens):
    return emb.astype(F32)[tokens]


def _hidden(dims: Dims, root, blocks: list, streams) -> dict:
    """The final hidden states of each block of token sequences, for each
    stream (float32; float8 where True), layer by layer."""
    emb = top_weight(dims, "embed", root)
    es = [_embed(dims, emb, jnp.asarray(toks)) for toks in blocks]
    hs = {fp8: list(es) for fp8 in streams}
    for layer in range(dims.layers):
        w = layer_weights(dims, root, layer)
        k = dims.block(layer)
        blk = None if k is None else block_weights(dims, root, k)
        for fp8 in streams:
            hs[fp8] = [_layer(dims, h, e, w, blk, fp8)
                       for h, e in zip(hs[fp8], es)]
        del w, blk
    return hs


def logits(dims: Dims, seed: int, tokens: np.ndarray, *,
           fp8: bool = False) -> np.ndarray:
    """The reference's logits at every position of ``tokens`` (N, T)."""
    root = root_key(seed)
    with jax.default_matmul_precision("highest"):
        h = _hidden(dims, root, [tokens], [fp8])[fp8][0]
        x = _norm(h, top_weight(dims, "final_norm", root).astype(F32),
                  dims.norm_eps)
        return np.asarray(_mm(x, top_weight(dims, "embed", root)
                              .astype(F32).T, fp8))


def served_gaps(dims: Dims, seed: int,
                served: list[tuple[list[int], list[int]]], *,
                control: bool = False) -> dict[str, np.ndarray]:
    """As ``bench/reference.py``: teacher-force each (prompt, served
    tokens) pair through the reference and read, for every served token,
    how far its reference logit lies below the reference's best; with
    ``control``, also the gap of the float8 computation's first choice."""
    from bench.reference import PAD

    root = root_key(seed)
    seqs = [list(p) + list(s[:-1]) for p, s in served]
    with jax.default_matmul_precision("highest"):
        blocks = list(_groups(seqs))
        streams = [False, True] if control else [False]
        hs = _hidden(dims, root, [toks for _, toks in blocks], streams)
        final = top_weight(dims, "final_norm", root)
        head = top_weight(dims, "embed", root)
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


# ---------------------------------------------------------------------------
# counts: what serving a token needs (see bench/flops.py)
# ---------------------------------------------------------------------------
def _mamba_matmul(d: Dims) -> int:
    gn = d.ngroups * d.d_state
    return d.d_model * (2 * d.d_inner + 2 * gn + d.mamba_heads) + \
        d.d_inner * d.d_model


def _block_matmul(d: Dims) -> int:
    hd = d.head_dim
    attn = 2 * d.d_model * hd * (d.heads + 2 * d.kv_heads) + \
        d.heads * hd * d.d_model
    return attn + 3 * d.d_model * d.d_ff


def _hybrid_matmul(d: Dims) -> int:
    """A hybrid occurrence's own matrices: adapter and linear."""
    return d.adapter_rank * (d.d_model + 2 * d.d_ff) + d.d_model ** 2


def _leaf_bytes(shapes: dict) -> int:
    return sum(math.prod(s) * (F32_BYTES if n in _F32 else WEIGHT_BYTES)
               for n, s in shapes.items())


def _layer_bytes(d: Dims, layer: int) -> int:
    return _leaf_bytes(d.layer_shapes(d.block(layer) is not None))


def _block_bytes(d: Dims) -> int:
    return _leaf_bytes(d.block_shapes())


def weight_bytes(d: Dims) -> int:
    """Every weight once, as the program holds it: each shared block once,
    the tied embedding once."""
    return sum(_layer_bytes(d, i) for i in range(d.layers)) + \
        d.mem_blocks * _block_bytes(d) + _leaf_bytes(d.top_shapes())


def decode_weight_bytes(d: Dims) -> int:
    """The weights a decode step needs: every layer's once, the tied
    embedding once (as the head; the gathered rows are among its bytes),
    and each shared block once per occurrence: at 668 MB a block cannot
    stay on the chip between its occurrences, so each reads it again."""
    return weight_bytes(d) + (d.hybrids - d.mem_blocks) * _block_bytes(d)


def kv_bytes_per_token(d: Dims) -> int:
    """Keys and values of one position, all hybrid layers (bfloat16)."""
    return 2 * d.hybrids * d.kv_heads * d.head_dim * WEIGHT_BYTES


def ssm_state_bytes(d: Dims) -> int:
    """One sequence's SSM state, all layers: the SSD state in float32 and
    the conv window (``d_conv - 1`` inputs) in bfloat16."""
    ssd = d.mamba_heads * d.d_state * d.mamba_headdim * F32_BYTES
    conv = (d.d_conv - 1) * (d.d_inner + 2 * d.ngroups * d.d_state) * \
        WEIGHT_BYTES
    return d.layers * (ssd + conv)


def token_flops(d: Dims, ctx: int, logits: bool) -> float:
    """Multiply-adds x 2 for one token that attends to ``ctx`` positions
    (itself included) in each hybrid layer: every matrix (each shared block
    at each occurrence), attention, the conv, the SSM recurrence's update
    and read-out (3 multiply-adds per state element), and the head only
    where its logits are needed."""
    f = 2.0 * (d.layers * _mamba_matmul(d)
               + d.hybrids * (_block_matmul(d) + _hybrid_matmul(d)))
    f += 4.0 * d.hybrids * d.heads * d.head_dim * ctx       # QK^T and PV
    f += 2.0 * d.layers * d.d_conv * (d.d_inner + 2 * d.ngroups * d.d_state)
    f += 6.0 * d.layers * d.mamba_heads * d.d_state * d.mamba_headdim
    if logits:
        f += 2.0 * d.d_model * d.vocab
    return f


def decode_step(d: Dims, ctxs: list[int]) -> tuple[float, float]:
    """(flops, bytes) of one batched decode step whose live rows attend to
    ``ctxs`` positions each (the new one included): the weights, each live
    row's SSM state read and written (float32 SSD state, it is carried at
    that precision), the K/V of its filled positions and its new K/V."""
    flops = sum(token_flops(d, c, True) for c in ctxs)
    kv = kv_bytes_per_token(d)
    byts = decode_weight_bytes(d) + 2 * ssm_state_bytes(d) * len(ctxs) \
        + kv * sum(c - 1 for c in ctxs) + kv * len(ctxs)
    return flops, float(byts)


def prefill_chunk(d: Dims, off: int, n: int, last: bool) -> float:
    """Flops of prefilling prompt positions ``off .. off+n-1``; ``last``:
    the chunk holds the prompt's final token, whose logits are needed."""
    return sum(token_flops(d, off + i + 1, last and i == n - 1)
               for i in range(n))


def rmsnorm_bytes(d: Dims, rows: int, act_bytes: int = 2) -> float:
    """Mean bytes of one RMSNorm kernel call of a decode step over ``rows``
    rows (read the rows and the float32 scale, write the rows): the step
    norms each layer's input and the final hidden state at ``d_model``, and
    each hybrid's concatenation at ``2 d_model`` and its attention output
    at ``d_model``."""
    def one(width):
        return 2 * rows * width * act_bytes + width * F32_BYTES
    calls = [one(d.d_model)] * (d.layers + 1) + \
        [one(2 * d.d_model), one(d.d_model)] * d.hybrids
    return sum(calls) / len(calls)


def ssd_bytes(d: Dims, length: int) -> int:
    """Bytes one SSD kernel call needs over one row's chunk of ``length``
    positions: x (bfloat16), the log-decay (float32), B and C per group
    (bfloat16) in; y (float32) and the chunk's state (float32) out."""
    h, p, n = d.mamba_heads, d.mamba_headdim, d.d_state
    read = length * (h * p * WEIGHT_BYTES + h * F32_BYTES
                     + 2 * d.ngroups * n * WEIGHT_BYTES)
    return read + length * h * p * F32_BYTES + h * n * p * F32_BYTES

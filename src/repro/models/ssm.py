"""Mamba-2 (SSD) block — attention-free sequence mixing.

Follows the Mamba-2 reference structure with SPLIT input projections
(z / x / B / C / dt as separate weights rather than one fused in_proj): the
fused projection's output dim (2·d_inner + 2·n + heads) is generally not
divisible by the 16-way model axis, which would force replication; the split
form shards each piece on its natural axis.  Compute is identical (XLA fuses
the five matmuls back together on the MXU).

Pipeline: projections -> causal depthwise conv on [x|B|C] -> softplus dt ->
SSD scan (Pallas chunk kernel) -> D-skip -> gated RMSNorm -> out projection.
B and C come in ``ssm_groups`` groups of ``ssm_state``: heads are split
evenly among the groups in order (zamba2: heads 0-55 read group 0, 56-111
group 1), and the gated RMSNorm normalizes each group's share of the
``d_inner`` channels on its own.
Decode keeps O(1) state: rolling conv window + (h, n, p) SSD state — this is
why mamba2/zamba2 are the archs that run ``long_500k``.

A chunk right-padded past its ``length`` real tokens leaves both states
where the last real token left them: dt is 0 at a padded position (decay
e^0 = 1, input dt·x = 0), and the conv window is taken at the real length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models.params import ParamSpec, dense, norm_scale


def _dims(cfg: ArchConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_head_dim
    return d_inner, nheads


def ssm_spec(cfg: ArchConfig) -> dict:
    d_inner, nheads = _dims(cfg)
    n, w = cfg.ssm_groups * cfg.ssm_state, cfg.ssm_conv_width
    return {
        "w_z": dense(cfg.d_model, d_inner, "embed", "ssm_in"),
        "w_x": dense(cfg.d_model, d_inner, "embed", "ssm_in"),
        "w_b": dense(cfg.d_model, n, "embed", None),
        "w_c": dense(cfg.d_model, n, "embed", None),
        "w_dt": dense(cfg.d_model, nheads, "embed", None),
        "conv_x": ParamSpec((w, d_inner), (None, "ssm_in"), "normal", 0.5),
        "conv_b": ParamSpec((w, n), (None, None), "normal", 0.5),
        "conv_c": ParamSpec((w, n), (None, None), "normal", 0.5),
        "conv_bias_x": ParamSpec((d_inner,), ("ssm_in",), "zeros"),
        "conv_bias_b": ParamSpec((n,), (None,), "zeros"),
        "conv_bias_c": ParamSpec((n,), (None,), "zeros"),
        "a_log": ParamSpec((nheads,), (None,), "ssm_a", dtype=jnp.float32),
        "d_skip": ParamSpec((nheads,), (None,), "ones", dtype=jnp.float32),
        "dt_bias": ParamSpec((nheads,), (None,), "zeros", dtype=jnp.float32),
        "gate_norm": norm_scale(d_inner),
        "out_proj": dense(d_inner, cfg.d_model, "ssm_in", "embed"),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None, length=None):
    """Depthwise causal conv1d. x: (B, S, C), w: (W, C), state: (B, W-1, C).
    The new state is the window that ends at the ``length``-th input (all
    ``S`` where None)."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
        full = jnp.concatenate([pad, x], axis=1)
    else:
        full = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    if width <= 1:
        new_state = None
    elif length is None:
        new_state = full[:, -(width - 1):]
    else:
        new_state = jax.lax.dynamic_slice_in_dim(full, length, width - 1,
                                                 axis=1)
    out = sum(w[i].astype(jnp.float32) *
              jax.lax.slice_in_dim(full.astype(jnp.float32), i,
                                   i + x.shape[1], axis=1)
              for i in range(width))
    return (out + b.astype(jnp.float32)).astype(x.dtype), new_state


def heads_of_groups(t: jax.Array, nheads: int, groups: int) -> jax.Array:
    """(B, S, groups * n) -> (B, S, nheads, n): each head reads its group's
    n channels; heads are split evenly among the groups in order."""
    bsz, s, gn = t.shape
    n = gn // groups
    t = t.reshape(bsz, s, groups, 1, n)
    t = jnp.broadcast_to(t, (bsz, s, groups, nheads // groups, n))
    return t.reshape(bsz, s, nheads, n)


def gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, groups: int,
               eps: float) -> jax.Array:
    """Mamba-2's gated RMSNorm, ``norm(y * silu(z))``, over each group's
    share of the channels."""
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    gf = g.astype(jnp.float32)
    gg = gf.reshape(*gf.shape[:-1], groups, gf.shape[-1] // groups)
    ms = jnp.mean(gg * gg, axis=-1, keepdims=True)
    gf = (gg * jax.lax.rsqrt(ms + eps)).reshape(gf.shape)
    return (gf * scale.astype(jnp.float32)).astype(y.dtype)


def ssm_fwd(p: dict, x: jax.Array, cfg: ArchConfig, *,
            cache: dict | None = None,
            length=None) -> tuple[jax.Array, dict | None]:
    """x: (B, S, d_model) -> (same, updated cache).  ``length``: the number
    of real tokens of a right-padded chunk (a traced scalar); positions at
    or past it leave the conv and SSD state untouched."""
    bsz, s, _ = x.shape
    d_inner, nheads = _dims(cfg)
    n, pdim = cfg.ssm_state, cfg.ssm_head_dim

    z = x @ p["w_z"]
    xs = x @ p["w_x"]
    bmat = x @ p["w_b"]
    cmat = x @ p["w_c"]
    dt_raw = x @ p["w_dt"]

    cs = cache["conv"] if cache is not None else {"x": None, "b": None, "c": None}
    xs, ncx = _causal_conv(xs, p["conv_x"], p["conv_bias_x"], cs["x"], length)
    bmat, ncb = _causal_conv(bmat, p["conv_b"], p["conv_bias_b"], cs["b"],
                             length)
    cmat, ncc = _causal_conv(cmat, p["conv_c"], p["conv_bias_c"], cs["c"],
                             length)
    xs, bmat, cmat = (jax.nn.silu(t) for t in (xs, bmat, cmat))
    new_conv = {"x": ncx, "b": ncb, "c": ncc}

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         p["dt_bias"].astype(jnp.float32))     # (B,S,h)
    if length is not None:
        dt = jnp.where((jnp.arange(s) < length)[None, :, None], dt, 0.0)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))               # (h,)
    a_full = a[None, None] * dt                                # (B,S,h) <= 0

    xh = xs.reshape(bsz, s, nheads, pdim)
    xh = shd.constrain_logical(xh, ("batch", None, "heads", None))
    x_in = (xh.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    b_full = heads_of_groups(bmat, nheads, cfg.ssm_groups)
    c_full = heads_of_groups(cmat, nheads, cfg.ssm_groups)

    # pad the sequence up to a chunk multiple (padding has a=0, x=0: decay
    # e^0 = 1 passes state through, zero input adds nothing — the final
    # state and the real tokens' outputs are unaffected)
    chunk = min(cfg.ssm_chunk, s)
    pad = (-s) % chunk
    if pad and s > 1:
        def padseq(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x_in, a_full, b_full, c_full = (padseq(t) for t in
                                        (x_in, a_full, b_full, c_full))

    if cache is None:
        y = kops.ssd(x_in, a_full, b_full, c_full, chunk=chunk)
        new_ssm = None
    elif s == 1:
        y, new_ssm = kops.ssd_decode_step(
            x_in[:, 0].astype(jnp.float32), a_full[:, 0],
            b_full[:, 0].astype(jnp.float32), c_full[:, 0].astype(jnp.float32),
            cache["ssm"])
        y = y[:, None].astype(x.dtype)
    else:  # chunked prefill carrying state
        y, new_ssm = kops.ssd_with_state(
            x_in, a_full, b_full, c_full, chunk=chunk,
            initial_state=cache["ssm"])
    if pad and s > 1:
        y = y[:, :s]

    y = y.reshape(bsz, s, nheads, pdim) + \
        p["d_skip"].astype(jnp.float32)[None, None, :, None] * \
        xh.astype(jnp.float32)
    y = y.reshape(bsz, s, d_inner).astype(x.dtype)

    g = gated_norm(y, z, p["gate_norm"], cfg.ssm_groups, cfg.norm_eps)
    out = g @ p["out_proj"]
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "ssm": new_ssm}
    return out, new_cache


def ssm_cache_spec(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> dict:
    d_inner, nheads = _dims(cfg)
    w, gn = cfg.ssm_conv_width, cfg.ssm_groups * cfg.ssm_state
    return {
        "conv": {
            "x": ParamSpec((batch, w - 1, d_inner), ("batch", None, "ssm_in"),
                           "zeros", dtype=dtype),
            "b": ParamSpec((batch, w - 1, gn),
                           ("batch", None, None), "zeros", dtype=dtype),
            "c": ParamSpec((batch, w - 1, gn),
                           ("batch", None, None), "zeros", dtype=dtype),
        },
        "ssm": ParamSpec((batch, nheads, cfg.ssm_state, cfg.ssm_head_dim),
                         ("batch", None, None, None), "zeros",
                         dtype=jnp.float32),
    }

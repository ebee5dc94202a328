"""Model dimensions and seeded random weights, owned by the benchmark.

The benchmark, not the program, makes the weights: the program is handed
them in its own parameter layout, and the plain reference makes the very
same values again from the seed, layer by layer, without touching anything
the program built.  Every leaf is drawn from its own key,
``fold_in(fold_in(root, leaf), layer)``, so one jitted call that makes the
whole tree and a call that makes one layer give identical values.

Draws: matrices ``N(0, 1/fan_in)``, the embedding ``N(0, embed_std^2)``
(0.02, the program's own initialisation scale, unless the configuration
states another), norm scales ``1 + 0.1 N(0, 1)`` in float32, so that a norm
whose scale is dropped or misapplied shows.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The widths and equations of one dense decoder, as the config states."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    embed_scale: float
    residual_scale: float
    norm_eps: float
    rope_theta: float
    dtype: str = "bfloat16"
    embed_std: float = 0.02

    @classmethod
    def from_config(cls, conf: dict) -> "Dims":
        """From the published config (``conf["published"]``, under its own
        keys): MiniCPM's ``scale_emb`` is the embedding scale and
        ``scale_depth / sqrt(layers)`` the residual scale; a config that
        names no ``rope_theta`` or ``head_dim`` has 10000 and
        ``hidden_size / num_attention_heads``.  ``conf["embed_std"]``, where
        given, is the scale the embedding is drawn at."""
        p = conf["published"]
        layers, heads = p["num_hidden_layers"], p["num_attention_heads"]
        return cls(
            layers=layers, d_model=p["hidden_size"], heads=heads,
            kv_heads=p["num_key_value_heads"],
            head_dim=p.get("head_dim") or p["hidden_size"] // heads,
            d_ff=p["intermediate_size"], vocab=p["vocab_size"],
            tied=p["tie_word_embeddings"],
            embed_scale=float(p.get("scale_emb", 1.0)),
            residual_scale=(p["scale_depth"] / math.sqrt(layers)
                            if "scale_depth" in p else 1.0),
            norm_eps=p["rms_norm_eps"],
            rope_theta=float(p.get("rope_theta", 10000.0)),
            dtype=p["torch_dtype"],
            embed_std=conf.get("embed_std", 0.02))

    def layer_shapes(self) -> dict[str, tuple[int, ...]]:
        """Per-layer leaves: name -> shape (one layer)."""
        d, hd = self.d_model, self.head_dim
        return {
            "ln1": (d,),
            "wq": (d, self.heads * hd),
            "wk": (d, self.kv_heads * hd),
            "wv": (d, self.kv_heads * hd),
            "wo": (self.heads * hd, d),
            "ln2": (d,),
            "w_gate": (d, self.d_ff),
            "w_up": (d, self.d_ff),
            "w_down": (self.d_ff, d),
        }

    def top_shapes(self) -> dict[str, tuple[int, ...]]:
        out = {"embed": (self.vocab, self.d_model),
               "final_norm": (self.d_model,)}
        if not self.tied:
            out["lm_head"] = (self.d_model, self.vocab)
        return out


_LEAF_ORDER = ("embed", "final_norm", "lm_head", "ln1", "wq", "wk", "wv",
               "wo", "ln2", "w_gate", "w_up", "w_down")


def root_key(seed: int) -> jax.Array:
    """A key for any whole number up to 2**64: the seed is split into two
    32-bit words, so seeds past 2**31 neither overflow nor collide."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, seed >> 32):
        key = jax.random.fold_in(key, jnp.uint32(word))
    return key


def _draw(key, name: str, shape: tuple[int, ...], dims: Dims) -> jax.Array:
    if name in ("ln1", "ln2", "final_norm"):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    std = dims.embed_std if name == "embed" else 1.0 / math.sqrt(shape[0])
    w = jax.random.normal(key, shape, jnp.float32) * std
    return w.astype(DTYPES[dims.dtype])


def _leaf_key(root, name: str):
    return jax.random.fold_in(root, _LEAF_ORDER.index(name))


@functools.partial(jax.jit, static_argnums=(0,))
def layer_weights(dims: Dims, root, layer) -> dict[str, jax.Array]:
    """The leaves of one layer, as the program is given them."""
    return {name: _draw(jax.random.fold_in(_leaf_key(root, name), layer),
                        name, shape, dims)
            for name, shape in dims.layer_shapes().items()}


@functools.partial(jax.jit, static_argnums=(0, 1))
def top_weight(dims: Dims, name: str, root) -> jax.Array:
    return _draw(_leaf_key(root, name), name, dims.top_shapes()[name], dims)


@functools.partial(jax.jit, static_argnums=(0,))
def program_params(dims: Dims, root) -> dict:
    """Every weight in the program's layout (``models/transformer.py``
    ``model_spec``: one scanned group of ``dense`` layers), made on the
    device in one call."""
    layers = jax.vmap(lambda l: layer_weights(dims, root, l))(
        jnp.arange(dims.layers))
    tree = {
        "embed": top_weight(dims, "embed", root),
        "g0": {"layers": {"0:dense": {
            "ln1": layers["ln1"],
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": layers["ln2"],
            "ffn": {k: layers[k] for k in ("w_gate", "w_up", "w_down")},
        }}},
        "final_norm": top_weight(dims, "final_norm", root),
    }
    if not dims.tied:
        tree["lm_head"] = top_weight(dims, "lm_head", root)
    return tree

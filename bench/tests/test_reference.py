"""The plain reference against the program's own prefill and decode, at a
tiny width on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.weights import Dims, program_params, root_key

TINY = dict(layers=2, d_model=128, heads=4, kv_heads=2, head_dim=32,
            d_ff=256, vocab=512, norm_eps=1e-5, rope_theta=10000.0)


def _program(dims):
    from repro.configs import get_config
    return get_config("minicpm-2b" if dims.tied else "phi3-mini-3.8b").scaled(
        d_model=dims.d_model, num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, d_ff=dims.d_ff, vocab_size=dims.vocab,
        blocks=((("dense",), dims.layers),), norm_eps=dims.norm_eps,
        embed_scale=dims.embed_scale, residual_scale=dims.residual_scale,
        tie_embeddings=dims.tied)


def _reference_logits(dims, seed, toks):
    root = root_key(seed)
    with jax.default_matmul_precision("highest"):
        emb = reference.top_weight(dims, "embed", root)
        h = reference._embed(dims, emb, jnp.asarray(toks)[None])
        for layer in range(dims.layers):
            h = reference._layer(dims, h, reference.layer_weights(
                dims, root, layer), False)
        head = emb if dims.tied else reference.top_weight(dims, "lm_head", root)
        return np.asarray(reference._logits(
            dims, h[0], reference.top_weight(dims, "final_norm", root), head,
            False))


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_program_prefill_then_decode(tied):
    from repro.models import model as mdl

    dims = Dims(**TINY, tied=tied, embed_scale=12.0 if tied else 1.0,
                residual_scale=0.5 if tied else 1.0)
    cfg = _program(dims)
    seed = 2**31 + 3
    params = program_params(dims, root_key(seed))
    toks = np.random.default_rng(0).integers(0, dims.vocab, 24).tolist()
    ref = _reference_logits(dims, seed, toks)

    caches = mdl.init_cache(cfg, 1, 64)
    got = []
    logits, caches = mdl.prefill_chunk(params, cfg, jnp.asarray([toks[:16]]),
                                       caches, jnp.asarray(15))
    got.append(np.asarray(logits[0]))
    for i in range(16, 24):
        logits, caches = mdl.decode_step(
            params, cfg, jnp.asarray([[toks[i]]]), caches,
            positions=jnp.asarray([i], jnp.int32))
        got.append(np.asarray(logits[0]))
    got = np.stack(got)
    want = ref[15:24]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 0.05 * scale


def test_served_gaps_of_the_reference_argmax_are_zero():
    dims = Dims(**TINY, tied=False, embed_scale=1.0, residual_scale=1.0)
    prompt = np.random.default_rng(1).integers(0, dims.vocab, 10).tolist()
    seq, served = list(prompt), []
    for _ in range(5):                  # greedy decode with the reference
        tok = int(np.argmax(_reference_logits(dims, 5, seq)[-1]))
        served.append(tok)
        seq.append(tok)
    gaps = reference.served_gaps(dims, 5, [(prompt, served)], control=True)
    assert gaps["program"].shape == (5,)
    assert np.abs(gaps["program"]).max() < 1e-4
    assert (gaps["control"] >= 0).all()


def test_a_wrong_token_shows_as_a_gap():
    dims = Dims(**TINY, tied=False, embed_scale=1.0, residual_scale=1.0)
    prompt = list(range(12))
    ref = _reference_logits(dims, 9, prompt)
    worst = int(np.argmin(ref[-1]))
    gaps = reference.served_gaps(dims, 9, [(prompt, [worst])])
    assert gaps["program"][0] == pytest.approx(ref[-1].max() - ref[-1].min(),
                                               rel=1e-4)

"""The ragged decode writes each row's new K/V in place, in a donated cache.

Self-attention K/V caches ride in the layer scan's carry and each row's
entry is written through an aligned window (``layers.write_rows``).  These
tests hold that path to the full-slice one-hot select it replaced, at the
window edges, with dead rows, at GQA widths and with a sliding-window
layer; and hold the engines to donating the cache on every decode tick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import smoke_config
from repro.core import Overlay
from repro.models import layers
from repro.models import model as mdl
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Request

MAX_LEN = 256
# window edges (0, 127, 128, max_len - 1), a mid-window row, and two dead
# rows: one parked at a stale position, one past the cache's end
POSITIONS = (0, 127, 128, MAX_LEN - 1, 40, 3, MAX_LEN)


def _one_hot_rows(cache, layer, new, pos):
    """The write this path replaced: a select over the layer's whole
    (B, Hkv, Smax, hd) slice."""
    sl = jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)
    sel = jnp.arange(sl.shape[2])[None, :] == pos[:, None]
    sl = jnp.where(sel[:, None, :, None], new.astype(sl.dtype), sl)
    return jax.lax.dynamic_update_index_in_dim(cache, sl, layer, 0)


def _configs():
    gqa = smoke_config("phi3-mini-3.8b").scaled(num_kv_heads=2)
    local = smoke_config("gemma2-27b").scaled(num_kv_heads=2,
                                              sliding_window=64)
    unrolled = gqa.scaled(scan_layers=False)
    moe = smoke_config("granite-moe-1b-a400m")
    return {"dense-gqa": gqa, "local-global-gqa": local,
            "unrolled-layers": unrolled, "moe": moe}


CONFIGS = _configs()


def _filled_cache(cfg, batch, seed):
    """A cache with every K/V entry drawn at random, so any entry a write
    misses or smears shows in the logits."""
    caches = mdl.init_cache(cfg, batch, MAX_LEN)
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    leaves = [jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
              if a.dtype == jnp.bfloat16 else a
              for k, a in zip(keys, leaves)]
    return jax.tree.unflatten(tree, leaves)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_in_place_write_matches_one_hot_select(name, monkeypatch):
    cfg = CONFIGS[name]
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(1))
    b = len(POSITIONS)
    caches = _filled_cache(cfg, b, seed=2)
    tok = jax.random.randint(jax.random.PRNGKey(3), (b, 1), 0,
                             cfg.vocab_size, jnp.int32)
    pos = jnp.asarray(POSITIONS, jnp.int32)

    def decode():       # a fresh function each time: jit traces it anew
        return jax.jit(lambda c: mdl.decode_step(params, cfg, tok, c,
                                                 positions=pos))(caches)

    logits, new = decode()
    monkeypatch.setattr(layers, "write_rows", _one_hot_rows)
    ref_logits, ref_new = decode()

    assert np.array_equal(np.asarray(logits), np.asarray(ref_logits))
    for a, r in zip(jax.tree.leaves(new), jax.tree.leaves(ref_new)):
        assert np.array_equal(np.asarray(a), np.asarray(r))


def test_write_rows_touches_only_each_rows_entry():
    cache = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 2, 300, 8),
                              jnp.float32).astype(jnp.bfloat16)
    new = jnp.full((4, 2, 1, 8), 7.0, jnp.bfloat16)
    pos = jnp.asarray([0, 127, 299, 300], jnp.int32)   # last: out of range
    out = np.asarray(jax.jit(layers.write_rows)(cache, 1, new, pos))
    want = np.asarray(cache).copy()
    for r, p in enumerate([0, 127, 299]):
        want[1, r, :, p, :] = 7.0
    assert np.array_equal(out, want)


CFG = smoke_config("phi3-mini-3.8b")
PARAMS = pm.init(model_spec(CFG), jax.random.PRNGKey(0))
PROMPTS = ((3, 5, 7), (11, 13, 17, 19, 23, 29, 31, 37, 41), (2, 4),
           (9, 8, 7, 6, 5))


@functools.cache
def _serve(chunk: int, overlay: bool):
    """Serve PROMPTS to the end: (ids by request, engine, decode ticks)."""
    eng = EventLoopEngine(PARAMS, CFG, batch=3, max_len=32, chunk=chunk,
                          overlay=Overlay(3, 3) if overlay else None)
    decode_ticks = 0
    tick = eng._decode_tick

    def counted(live):
        nonlocal decode_ticks
        old = jax.tree.leaves(eng.caches)
        out = tick(live)
        decode_ticks += 1
        # the tick consumed its input cache, every leaf of it
        assert all(a.is_deleted() for a in old)
        return out

    eng._decode_tick = counted
    for rid, prompt in enumerate(PROMPTS):
        eng.submit(Request(rid=rid, prompt=list(prompt), max_new_tokens=5))
    done = eng.run_until_drained()
    return {r.rid: r.out for r in done}, eng, decode_ticks


# chunk 64 prefills every prompt in one chunk, chunk 4 in several
@pytest.mark.parametrize("chunk", [4, 64])
def test_overlay_and_plain_engines_serve_identical_ids(chunk):
    plain, _, _ = _serve(chunk, False)
    served, eng, _ = _serve(chunk, True)
    assert served == plain
    assert len(plain) == len(PROMPTS)
    failures = eng.overlay_failures()
    assert failures["dispatch_failures"] == 0
    assert failures["dispatch_fallbacks"] == 0


@pytest.mark.parametrize("overlay", [False, True])
def test_every_decode_tick_donates_its_cache(overlay):
    _, eng, decode_ticks = _serve(64, overlay)
    m = eng.metrics()
    assert decode_ticks > 0
    assert m["kv_donated_ticks"] == decode_ticks
    assert m["ticks"] >= decode_ticks

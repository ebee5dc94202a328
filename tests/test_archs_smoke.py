"""Per-arch smoke tests: reduced same-family config, one forward/train step
on CPU, asserting output shapes + no NaNs (the brief's requirement), plus
prefill/decode consistency for every arch that serves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.configs.archs import smoke_config
from repro.data.pipeline import make_batch
from repro.models import model as mdl
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.optim import adamw_init, adamw_update

# the full arch matrix takes minutes; the tier-1 CI lane skips it
pytestmark = pytest.mark.slow

ARCHS = list_archs()
B, S = 2, 32


def _batch(cfg, step=0):
    return make_batch(cfg, B, S, step=step, seed=0)


@pytest.fixture(scope="module")
def smoke_state():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = smoke_config(name)
            params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
            cache[name] = (cfg, params)
        return cache[name]
    return get


def test_all_ten_assigned_archs_are_registered():
    assert ARCHS == sorted([
        "zamba2-7b", "mistral-large-123b", "phi3-mini-3.8b", "gemma2-27b",
        "minicpm-2b", "mamba2-130m", "granite-moe-1b-a400m",
        "deepseek-v3-671b", "seamless-m4t-medium", "pixtral-12b"])


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_layer_count(arch):
    cfg = get_config(arch)
    expected = {"zamba2-7b": 81, "mistral-large-123b": 88,
                "phi3-mini-3.8b": 32, "gemma2-27b": 46, "minicpm-2b": 40,
                "mamba2-130m": 24, "granite-moe-1b-a400m": 24,
                "deepseek-v3-671b": 61, "seamless-m4t-medium": 24,
                "pixtral-12b": 40}
    assert cfg.num_layers == expected[arch]


@pytest.mark.parametrize("arch,target_b", [
    ("deepseek-v3-671b", 671e9), ("mistral-large-123b", 123e9),
    ("gemma2-27b", 27e9), ("phi3-mini-3.8b", 3.8e9),
    ("pixtral-12b", 12e9), ("minicpm-2b", 2.7e9),
    ("mamba2-130m", 130e6), ("zamba2-7b", 7.35e9)])
def test_full_config_param_count_near_nameplate(arch, target_b):
    n = get_config(arch).param_count()
    assert 0.75 * target_b < n < 1.35 * target_b, f"{arch}: {n/1e9:.2f}B"


def test_deepseek_active_params_about_37b():
    n = get_config("deepseek-v3-671b").active_param_count()
    assert 30e9 < n < 45e9


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_no_nans(arch, smoke_state):
    cfg, params = smoke_state(arch)
    batch = _batch(cfg)
    loss, metrics = mdl.loss_fn(params, batch, cfg)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    assert bool(jnp.isfinite(metrics["acc"]))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step_updates_params_finitely(arch, smoke_state):
    cfg, params = smoke_state(arch)
    opt = adamw_init(params)
    batch = _batch(cfg)
    (loss, _), grads = jax.value_and_grad(
        mdl.loss_fn, has_aux=True)(params, batch, cfg)
    new_params, new_opt, m = adamw_update(params, grads, opt, lr=1e-3)
    assert bool(jnp.isfinite(m["grad_norm"]))
    # at least one parameter changed, none became NaN
    changed = False
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)):
        assert np.isfinite(np.float32(b)).all()
        changed |= bool(jnp.any(a != b))
    assert changed


DECODER_ARCHS = [a for a in ARCHS if not get_config(a).is_encdec]


@pytest.mark.parametrize("arch", DECODER_ARCHS)
def test_prefill_decode_consistency(arch, smoke_state):
    """Logits from (prefill N) + (decode 1) == logits from prefill N+1."""
    cfg, params = smoke_state(arch)
    if cfg.frontend == "vision":
        pytest.skip("vlm prefix handling covered in test_serving")
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 9), 0,
                              cfg.vocab_size)
    max_len = 32
    c1 = mdl.init_cache(cfg, 1, max_len)
    logits_a, c1 = mdl.prefill(params, cfg, toks[:, :8], c1)
    logits_b, _ = mdl.decode_step(params, cfg, toks[:, 8:9], c1)

    c2 = mdl.init_cache(cfg, 1, max_len)
    logits_full, _ = mdl.prefill(params, cfg, toks, c2)
    np.testing.assert_allclose(np.float32(logits_b), np.float32(logits_full),
                               rtol=3e-2, atol=3e-2)


def test_encdec_prefill_and_decode_run():
    cfg = smoke_config("seamless-m4t-medium")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    frames = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.frontend_dim),
                               jnp.bfloat16)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, cfg.vocab_size)
    caches = mdl.init_cache(cfg, 1, 32)
    logits, caches = mdl.prefill(params, cfg, toks, caches, enc_in=frames)
    assert logits.shape == (1, cfg.vocab_size)
    assert np.isfinite(np.float32(logits)).all()
    logits2, _ = mdl.decode_step(
        params, cfg, jnp.argmax(logits, -1)[:, None].astype(jnp.int32), caches)
    assert np.isfinite(np.float32(logits2)).all()


def test_zamba2_shared_attention_is_actually_shared():
    """zamba2's two shared transformer blocks are held once, at the model's
    top level, not per layer or per repetition (the paper's bitstream-reuse
    case), and the 13 hybrid layers call them A, B, A, B, ... by
    occurrence; each hybrid keeps its own Mamba2 block, adapter and linear,
    so the model runs 81 Mamba2 blocks."""
    cfg = smoke_config("zamba2-7b")
    spec = model_spec(cfg)
    assert set(spec["shared"]) == {"block0", "block1"}
    for blk in spec["shared"].values():
        wq = blk["attn"]["wq"]
        assert len(wq.shape) == 2        # NOT stacked with a layer dim
        assert wq.shape[0] == 2 * cfg.d_model   # reads concat(x, embedding)
    full = get_config("zamba2-7b")
    kinds = [k for u, r in full.blocks for _ in range(r) for k in u]
    hybrids = [k for k in kinds if k.startswith("hybrid")]
    assert len(kinds) == 81 and len(hybrids) == 13
    assert hybrids == ["hybrid0", "hybrid1"] * 6 + ["hybrid0"]
    assert [i for i, k in enumerate(kinds) if k in hybrids] == \
        [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77]
    for gi, (unit, _) in enumerate(cfg.blocks):
        for i, kind in enumerate(unit):
            layer = spec[f"g{gi}"]["layers"][f"{i}:{kind}"]
            assert "mixer" in layer
            assert ("linear" in layer) == kind.startswith("hybrid")


def test_gemma2_local_global_alternation_compiles_two_bodies():
    cfg = get_config("gemma2-27b")
    assert cfg.blocks == ((("local", "global"), 23),)
    assert cfg.sliding_window == 4096
    assert cfg.attn_softcap == 50.0 and cfg.final_softcap == 30.0


def test_long500k_applicability_rules():
    from repro.launch import steps as steps_lib
    runnable = {a: steps_lib.applicable(get_config(a), "long_500k")[0]
                for a in ARCHS}
    assert runnable["mamba2-130m"] and runnable["zamba2-7b"]
    assert sum(runnable.values()) == 2   # everything else skips

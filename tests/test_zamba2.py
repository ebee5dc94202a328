"""zamba2-7b on the normal serving path, against the plain reference.

At a small size on the CPU, with seeded random weights from the
benchmark's own zamba2 family (``bench/families/zamba2.py``): 2 periods of
``(mamba, mamba, hybrid-A, mamba, mamba, hybrid-B)``, ``d_model`` 64, 2 B/C
groups of state 16, 2 alternating shared blocks.  The program serves
through ``EventLoopEngine`` (chunked prefill with a padded last chunk, the
pooled cache, ragged decode) and is held to the reference's full forward
pass by logits; a padded chunk must leave the SSM state where the real
tokens left it; one B/C group must reproduce the Mamba2 block as it was
before groups; the family's counts must agree with the program's layout;
and the engine's SSM counters must read what happened.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import families  # noqa: E402
from bench.families import zamba2 as z2  # noqa: E402
from repro.configs.archs import smoke_config  # noqa: E402
from repro.models import model as mdl  # noqa: E402
from repro.models import params as pm  # noqa: E402
from repro.models import ssm as ssm_lib  # noqa: E402
from repro.models.transformer import model_spec  # noqa: E402
from repro.serving import EventLoopEngine, Request  # noqa: E402

SEED = 2**31 + 161
M, H = "mamba", "hybrid"


def _conf(**over) -> dict:
    """A Zamba2 stage at a small size, in the benchmark's config layout."""
    types = [M, M, H] * 4
    pub = {
        "hidden_size": 64, "mamba_expand": 2, "n_mamba_heads": 8,
        "mamba_headdim": 16, "mamba_d_state": 16, "mamba_ngroups": 2,
        "mamba_d_conv": 4, "num_attention_heads": 4,
        "num_key_value_heads": 4, "attention_head_dim": 32,
        "attention_hidden_size": 128, "intermediate_size": 128,
        "ffn_hidden_size": 128, "adapter_rank": 8, "num_mem_blocks": 2,
        "vocab_size": 256, "chunk_size": 256, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 0.0001, "hidden_act": "gelu",
        "num_hidden_layers": len(types), "layers_block_type": types,
        "hybrid_layer_ids": [i for i, t in enumerate(types) if t == H],
    }
    pub.update(over)
    return {"arch": "zamba2-7b", "family": "zamba2", **pub,
            "stage": {"first_layer": 0, "first_occurrence": 0},
            "embed_std": 0.5}


CONF = _conf()
DIMS = z2.Dims.from_config(CONF)
CFG = z2.program_config(CONF, DIMS)


@pytest.fixture(scope="module")
def params():
    return z2.program_params(DIMS, families.root_key(SEED))


def _engine(params, batch, max_len=64, chunk=8):
    return EventLoopEngine(params, CFG, batch=batch, max_len=max_len,
                           chunk=chunk)


def _spy(eng, rec):
    """Record every prefill chunk's and decode tick's logits, with what
    the engine knew when it made them."""
    decode, prefill = eng._decode, eng._prefill_chunk

    def spy_decode(p, toks, c, pos):
        live = np.asarray(eng._live_mask).astype(bool)
        rows = [r if ok else None for r, ok in zip(eng.slot_req, live)]
        logits, c = decode(p, toks, c, pos)
        rec.append(("decode", rows, np.asarray(pos), np.asarray(logits)))
        return logits, c

    def spy_prefill(p, toks, c1, last):
        st = next(st for st in eng._prefilling.values() if st["c1"] is c1)
        logits, c1 = prefill(p, toks, c1, last)
        end = st["off"] + int(last) + 1
        if end == len(st["req"].prompt):
            rec.append(("prefill", [st["req"]], np.asarray([end - 1]),
                        np.asarray(logits)))
        return logits, c1

    eng._decode, eng._prefill_chunk = spy_decode, spy_prefill


def _by_request(rec) -> dict:
    """rid -> [(position, logits)] of every logit row the program made."""
    out: dict = {}
    for _, rows, pos, logits in rec:
        for b, req in enumerate(rows):
            if req is not None:
                out.setdefault(req.rid, []).append((int(pos[b]), logits[b]))
    return out


# the tolerance of (a), in logits of spread ~2.5 (embedding std 0.5, tied
# head): the program carries its activations in bfloat16 (8 significant
# bits, 0.4% a rounding) through 12 layers, each of which normalizes its
# input and amplifies what rounding put there; on this seed it ends 1.04
# from the float32 reference (0.10 after one layer).  The float8 control
# (4 significant bits) ends 7.7 away, and the test holds it above twice
# the bound, so the bound tells the configured precision from the one
# below it.  test_program_matches_the_reference_in_float32 holds the
# equations themselves to 1e-3.
LOGIT_TOL = 2.0


def test_served_logits_match_the_reference(params):
    """(a) Chunked prefill (chunk 8, padded last chunks), then ragged
    decode through the pooled cache, against the full forward pass."""
    lens = (13, 8, 21)              # last chunks of 5 (padded to 8), 8, 5
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, DIMS.vocab, n).tolist(),
                    max_new_tokens=6) for i, n in enumerate(lens)]
    eng = _engine(params, batch=2)           # 3 requests: a slot is reused
    rec = []
    _spy(eng, rec)
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert len(done) == 3 and eng.ssm_pad_tokens == 3 + 3
    got = _by_request(rec)

    seqs = [r.prompt + r.out[:-1] for r in reqs]
    t = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    ref = z2.logits(DIMS, SEED, toks)
    low = z2.logits(DIMS, SEED, toks, fp8=True)
    worst = worst_low = 0.0
    for r in reqs:
        rows = got[r.rid]
        assert [p for p, _ in rows] == list(
            range(len(r.prompt) - 1, len(r.prompt) + 6))
        for p, lg in rows:
            worst = max(worst, float(np.max(np.abs(lg - ref[r.rid, p]))))
            worst_low = max(worst_low, float(np.max(
                np.abs(low[r.rid, p] - ref[r.rid, p]))))
    assert worst < LOGIT_TOL, worst
    assert worst_low > 2 * LOGIT_TOL, worst_low


def test_program_matches_the_reference_in_float32(params):
    """The program's equations, with its weights in float32 at full matmul
    precision: the whole-prompt forward pass (SSD kernel, Pallas RMSNorm,
    flash attention) agrees with the reference to float32 rounding."""
    from repro.models import transformer as tfm

    cfg = CFG.scaled(dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = np.random.default_rng(3).integers(0, DIMS.vocab, (2, 16))
    ref = z2.logits(DIMS, SEED, toks.astype(np.int32))
    with jax.default_matmul_precision("highest"):
        h, _, _ = tfm.forward(p32, cfg, jnp.asarray(toks, jnp.int32))
        got = np.asarray(tfm.unembed(p32, h, cfg))
    assert float(np.max(np.abs(got - ref))) < 1e-3


def _ssm_leaves(c):
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_leaves_with_path(c)
            if any(getattr(k, "key", None) in ("conv", "ssm") for k in path)}


def _prefill(params, prompt, sizes):
    """Prefill ``prompt`` in chunks of ``sizes`` (each right-padded to its
    size with token 0) into a batch-1 cache."""
    c1 = mdl.init_cache(CFG, 1, 64)
    off = 0
    for size in sizes:
        real = prompt[off:off + size]
        toks = np.zeros((1, size), np.int32)
        toks[0, :len(real)] = real
        logits, c1 = mdl.prefill_chunk(params, CFG, jnp.asarray(toks), c1,
                                       jnp.asarray(len(real) - 1, jnp.int32))
        off += len(real)
    return logits, c1


def test_padded_chunk_leaves_ssm_state_as_unpadded(params):
    """(b) 13 tokens as chunks of 8 + 5-padded-to-8 give the same SSM state,
    and the same next-token logits, as chunks of 8 + 5."""
    prompt = np.random.default_rng(1).integers(1, DIMS.vocab, 13).tolist()
    lp, cp = _prefill(params, prompt, (8, 8))
    lu, cu = _prefill(params, prompt, (8, 5))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lu), atol=2e-2)
    sp, su = _ssm_leaves(cp), _ssm_leaves(cu)
    assert sp.keys() == su.keys() and len(sp) == 4 * 6     # unit of 6 layers
    for k in sp:
        # the SSD state is float32; the two chunkings sum in another order
        np.testing.assert_allclose(sp[k].astype(np.float32),
                                   su[k].astype(np.float32),
                                   atol=1e-3, rtol=1e-3, err_msg=k)
    nxt = jnp.asarray([[prompt[5]]], jnp.int32)
    pos = jnp.asarray([13], jnp.int32)
    dp, _ = mdl.decode_step(params, CFG, nxt, cp, positions=pos)
    du, _ = mdl.decode_step(params, CFG, nxt, cu, positions=pos)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(du), atol=2e-2)


def _old_heads_of_groups(t, nheads, groups):
    """B/C per head as the Mamba2 block had it before groups."""
    assert groups == 1
    bsz, s, n = t.shape
    return jnp.broadcast_to(t[:, :, None, :], (bsz, s, nheads, n))


def _old_gated_norm(y, z, scale, groups, eps):
    """The gated RMSNorm over the whole row, as before groups."""
    assert groups == 1
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    gf = g.astype(jnp.float32)
    ms = jnp.mean(gf * gf, axis=-1, keepdims=True)
    return (gf * jax.lax.rsqrt(ms + eps) *
            scale.astype(jnp.float32)).astype(y.dtype)


def _mamba_outputs():
    cfg = smoke_config("mamba2-130m")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 12), 0,
                              cfg.vocab_size)
    caches = mdl.init_cache(cfg, 2, 32)
    pre, caches = jax.jit(lambda p, t, c: mdl.prefill(p, cfg, t, c))(
        params, toks[:, :11], caches)
    dec, caches = jax.jit(lambda p, t, c: mdl.decode_step(p, cfg, t, c))(
        params, toks[:, 11:], caches)
    full, _ = jax.jit(lambda p, t: mdl.loss_fn(
        p, {"tokens": t, "labels": t}, cfg))(params, toks)
    return [np.asarray(a) for a in (pre, dec, full,
                                    *jax.tree.leaves(caches))]


def test_one_group_reproduces_mamba2_exactly(monkeypatch):
    """(c) ``ssm_groups`` 1: prefill, decode, the loss and the carried
    state of mamba2-130m (smoke size) equal, bit for bit, those of the
    ungrouped B/C broadcast and whole-row gated norm."""
    assert smoke_config("mamba2-130m").ssm_groups == 1
    now = _mamba_outputs()
    monkeypatch.setattr(ssm_lib, "heads_of_groups", _old_heads_of_groups)
    monkeypatch.setattr(ssm_lib, "gated_norm", _old_gated_norm)
    before = _mamba_outputs()
    assert len(now) == len(before)
    for a, b in zip(now, before):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def _published_conf():
    import json
    return json.loads((ROOT / "bench" / "configs" /
                       "zamba2-7b.json").read_text())


@pytest.mark.parametrize("which", ["tiny", "zamba2-7b"])
def test_family_contract(which):
    """(d) ``Dims.from_config``; ``program_params`` fits ``model_spec`` leaf
    for leaf; ``weight_bytes`` is the program's parameter bytes and
    ``decode_step`` reads each shared block once per occurrence."""
    conf = CONF if which == "tiny" else _published_conf()
    fam = families.of(conf)
    assert fam is z2
    dims = fam.Dims.from_config(conf)
    cfg = fam.program_config(conf, dims)
    want = pm.abstract(model_spec(cfg))
    got = jax.eval_shape(lambda: fam.program_params(
        dims, families.root_key(SEED)))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(want))
    assert fam.weight_bytes(dims) == nbytes
    block = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(want["shared"]["block0"]))
    assert fam.decode_step(dims, [])[1] == \
        nbytes + (dims.hybrids - dims.mem_blocks) * block
    if which == "zamba2-7b":
        assert dims.kinds == (M,) * 5 + ("hybrid0",) + (M,) * 5 + \
            ("hybrid1",) + (M,) * 5 + ("hybrid0",) + (M,) * 5 + ("hybrid1",)
        assert cfg.blocks == ((dims.kinds[:12], 2),)
        assert (cfg.head_dim, cfg.ssm_groups, cfg.act) == \
            (224, 2, "gelu_exact")
        assert fam.kv_bytes_per_token(dims) == 114_688
        assert math.isclose(nbytes / 1e9, 5.467, abs_tol=1e-3)


def test_ssm_counters_and_stripes(params):
    """(e) ``ssm_pad_tokens`` counts the padded prefill positions and
    ``ssm_donated_ticks`` every decode tick whose SSM state was donated;
    every SSM leaf of a finished prefill lands in its slot of the pool."""
    eng = _engine(params, batch=3)
    stripes = []
    install = eng._install_stripe

    def spy_install(slot, req, c1, tok):
        stripes.append((slot, _ssm_leaves(c1)))
        install(slot, req, c1, tok)
        pool = _ssm_leaves(eng.caches)
        for k, one in stripes[-1][1].items():
            ax = next(a for a in range(one.ndim) if one.shape[a] == 1
                      and pool[k].shape[a] == eng.batch)
            np.testing.assert_array_equal(
                np.take(pool[k], [slot], axis=ax), one, err_msg=k)

    eng._install_stripe = spy_install
    calls = []
    decode = eng._decode
    eng._decode = lambda *a: calls.append(1) or decode(*a)
    rng = np.random.default_rng(2)
    for i, n in enumerate((3, 13, 16)):      # pads 1, 3, 0 at chunk 8
        eng.submit(Request(rid=i, max_new_tokens=4,
                           prompt=rng.integers(0, DIMS.vocab, n).tolist()))
    eng.run_until_drained()
    m = eng.metrics()
    assert m["ssm_pad_tokens"] == 1 + 3
    assert len(stripes) == 3
    assert m["ssm_donated_ticks"] == m["kv_donated_ticks"] == len(calls) > 0


def test_dense_engine_counts_no_ssm():
    """A model without SSM layers pads its prefill chunks too, but holds
    nothing out of any state: both SSM counters stay at 0."""
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    eng = EventLoopEngine(params, cfg, batch=2, max_len=64, chunk=8)
    eng.submit(Request(rid=0, prompt=list(range(1, 12)), max_new_tokens=3))
    eng.run_until_drained()
    m = eng.metrics()
    assert (m["ssm_pad_tokens"], m["ssm_donated_ticks"]) == (0, 0)
    assert m["kv_donated_ticks"] == 3


def test_overlay_assembled_hybrid_step_matches_direct():
    """The overlay's step graph of a hybrid model: each group stage also
    takes the embedding stage's output and the shared blocks, and the
    assembled step gives the direct forward's logits."""
    from repro.core import Overlay
    from repro.models import transformer as tfm

    cfg = smoke_config("zamba2-7b")
    p = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    g = mdl.build_step_graph(cfg, (2, 16))
    acc = Overlay(3, 3, large_fraction=1.0).assemble(g, jit=False)
    h, _, _ = tfm.forward(p, cfg, toks)
    np.testing.assert_allclose(np.float32(acc(p, toks)),
                               np.float32(tfm.unembed(p, h, cfg)),
                               rtol=2e-3, atol=2e-3)

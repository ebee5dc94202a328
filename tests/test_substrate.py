"""Substrate tests: data pipeline, optimizer, checkpoint, supervisor, serving."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.configs.archs import smoke_config
from repro.data.pipeline import SyntheticLM
from repro.models import model as mdl
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.optim import adamw_init, adamw_update, cosine, wsd
from repro.runtime import FailureInjector, Supervisor, TrainLoopConfig
from repro.serving import EventLoopEngine, Request


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def test_pipeline_deterministic_and_resumable():
    ds = SyntheticLM(vocab_size=128, seq_len=16, batch_size=4, seed=7)
    b1 = ds.batch(step=5)
    b2 = ds.batch(step=5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = ds.batch(step=6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_pipeline_shards_partition_batch():
    ds = SyntheticLM(vocab_size=128, seq_len=8, batch_size=8, seed=1)
    s0 = ds.batch(0, shard=0, num_shards=2)
    s1 = ds.batch(0, shard=1, num_shards=2)
    assert s0["tokens"].shape == (4, 8)
    assert not np.array_equal(s0["tokens"], s1["tokens"])


def test_labels_are_next_tokens():
    ds = SyntheticLM(vocab_size=64, seq_len=12, batch_size=2, seed=3)
    b = ds.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_matches_numpy_reference():
    p = {"w": jnp.asarray([[1.0, -2.0], [0.5, 3.0]])}
    g = {"w": jnp.asarray([[0.1, 0.1], [-0.2, 0.3]])}
    st = adamw_init(p)
    lr, b1, b2, eps, wd = 0.1, 0.9, 0.95, 1e-8, 0.1
    new_p, new_st, m = adamw_update(p, g, st, lr=lr, b1=b1, b2=b2, eps=eps,
                                    weight_decay=wd, max_grad_norm=1e9)
    # numpy reference
    gn = np.sqrt(np.sum(np.square(np.asarray(g["w"]))))
    scale = min(1.0, 1e9 / (gn + 1e-9))
    gg = np.asarray(g["w"]) * scale
    mu = (1 - b1) * gg
    nu = (1 - b2) * gg ** 2
    mhat = mu / (1 - b1)
    vhat = nu / (1 - b2)
    want = np.asarray(p["w"]) - lr * (mhat / (np.sqrt(vhat) + eps)
                                      + wd * np.asarray(p["w"]))
    np.testing.assert_allclose(np.asarray(new_p["w"]), want, rtol=1e-5)
    assert int(new_st.step) == 1


def test_grad_clipping_caps_update_norm():
    p = {"w": jnp.zeros((4,))}
    g = {"w": jnp.full((4,), 100.0)}
    st = adamw_init(p)
    _, _, m = adamw_update(p, g, st, lr=1.0, max_grad_norm=1.0)
    assert float(m["grad_norm"]) > 100.0  # reported pre-clip norm


def test_schedules_shapes():
    c = cosine(1e-3, warmup=10, total=100)
    assert float(c(0)) == 0.0
    assert abs(float(c(10)) - 1e-3) < 1e-9
    assert float(c(100)) < float(c(50))
    w = wsd(1e-3, warmup=10, stable=50, decay=20)
    assert abs(float(w(30)) - 1e-3) < 1e-9       # plateau
    assert float(w(80)) < 1e-3                    # decayed


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    d = save_checkpoint(str(tmp_path), 3, tree, extra={"note": "x"})
    restored, manifest = load_checkpoint(d, tree)
    assert manifest["step"] == 3
    np.testing.assert_array_equal(restored["a"], tree["a"])
    np.testing.assert_array_equal(np.float32(restored["b"]["c"]),
                                  np.float32(tree["b"]["c"]))


def test_checkpoint_detects_corruption(tmp_path):
    tree = {"a": jnp.arange(4)}
    d = save_checkpoint(str(tmp_path), 1, tree)
    victim = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(-1, 2)
        f.write(b"\xff")
    with pytest.raises(IOError):
        load_checkpoint(d, tree)


def test_manager_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3):
        mgr.save(s, {"x": jnp.full((2,), float(s))}, blocking=True)
    restored, manifest = mgr.restore_latest(tree)
    assert manifest["step"] == 3
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(dirs) == 2                      # keep_n respected


# ---------------------------------------------------------------------------
# fault-tolerant supervisor
# ---------------------------------------------------------------------------
def _counting_step(state, batch):
    return state + 1, {"loss": float(batch["v"])}


def test_supervisor_runs_to_completion(tmp_path):
    sup = Supervisor(TrainLoopConfig(total_steps=7, ckpt_every=3),
                     str(tmp_path))
    final = sup.run(jnp.zeros(()), _counting_step,
                    lambda s: {"v": jnp.asarray(s)})
    assert int(final) == 7
    assert sup.restarts == 0


def test_supervisor_recovers_from_injected_failures(tmp_path):
    inj = FailureInjector(fail_at=(5,))
    sup = Supervisor(TrainLoopConfig(total_steps=8, ckpt_every=2),
                     str(tmp_path), injector=inj)
    final = sup.run(jnp.zeros(()), _counting_step,
                    lambda s: {"v": jnp.asarray(s)})
    assert int(final) == 8                     # reached the end despite failure
    assert sup.restarts == 1


def test_supervisor_replay_is_exact_after_failure(tmp_path):
    """Deterministic pipeline + checkpoint-restart => same final state as a
    failure-free run."""
    def step(state, batch):
        return state + batch["v"], {}

    clean = Supervisor(TrainLoopConfig(total_steps=9, ckpt_every=3),
                       str(tmp_path / "clean"))
    ref = clean.run(jnp.zeros(()), step, lambda s: {"v": jnp.asarray(s + 1.0)})

    faulty = Supervisor(TrainLoopConfig(total_steps=9, ckpt_every=3),
                        str(tmp_path / "faulty"),
                        injector=FailureInjector(fail_at=(4, 7)))
    out = faulty.run(jnp.zeros(()), step, lambda s: {"v": jnp.asarray(s + 1.0)})
    assert float(out) == float(ref)
    assert faulty.restarts == 2


def test_supervisor_straggler_detection(tmp_path):
    inj = FailureInjector(slow_at=(6,), slow_seconds=0.25)
    sup = Supervisor(TrainLoopConfig(total_steps=8, ckpt_every=100,
                                     straggler_factor=3.0),
                     str(tmp_path), injector=inj)
    sup.run(jnp.zeros(()), _counting_step, lambda s: {"v": jnp.asarray(s)})
    assert sup.straggler_steps >= 1


def test_supervisor_elastic_remesh_hook(tmp_path):
    """A persistently failing step (bad node) triggers the re-mesh hook after
    remesh_after_failures consecutive failures, then the run completes."""
    calls = []
    inj = FailureInjector(fail_at=(2,), repeat=3)   # same step fails 3x
    sup = Supervisor(
        TrainLoopConfig(total_steps=6, ckpt_every=1, max_restarts=10,
                        remesh_after_failures=3),
        str(tmp_path), injector=inj, on_remesh=lambda n: calls.append(n))
    final = sup.run(jnp.zeros(()), _counting_step,
                    lambda s: {"v": jnp.asarray(s)})
    assert int(final) == 6
    assert calls == [1]
    assert sup.restarts == 3


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------
def test_serve_engine_greedy_matches_manual_decode():
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    prompt = list(range(1, 9))

    engine = EventLoopEngine(params, cfg, batch=2, max_len=64)
    engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
    done = engine.run_until_drained()
    # out = 1 prefill-produced token + max_new_tokens decode-step tokens
    assert len(done) == 1 and len(done[0].out) == 6
    assert done[0].decode_steps == 5

    # manual greedy loop
    caches = mdl.init_cache(cfg, 1, 64)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, caches = mdl.prefill(params, cfg, toks, caches)
    want = [int(jnp.argmax(logits[0]))]
    for _ in range(5):
        logits, caches = mdl.decode_step(
            params, cfg, jnp.asarray([[want[-1]]], jnp.int32), caches)
        want.append(int(jnp.argmax(logits[0])))
    assert done[0].out == want


def test_serve_engine_batched_slots_recycle():
    cfg = smoke_config("minicpm-2b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(1))
    engine = EventLoopEngine(params, cfg, batch=2, max_len=32)
    for rid in range(4):                       # 4 requests through 2 slots
        engine.submit(Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=3))
    done = engine.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert all(len(r.out) == 4 for r in done)          # prefill tok + 3 steps
    assert all(r.decode_steps == 3 for r in done)


def test_serve_engine_retires_on_decode_steps_not_prefill_token():
    """Regression: the prefill-produced token sits in req.out before the
    first decode tick; retiring on len(out) finished requests one decode
    step early.  A request asking for N new tokens must take exactly N
    batched decode steps."""
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    engine = EventLoopEngine(params, cfg, batch=1, max_len=64)
    engine.submit(Request(rid=0, prompt=[1, 2, 3, 4], max_new_tokens=3))

    ticks = 0
    done: list[Request] = []
    while not done and ticks < 10:
        done.extend(engine.step())
        ticks += 1
    assert ticks == 3                          # one tick per decode step
    assert done[0].decode_steps == 3
    assert len(done[0].out) == 4               # prefill token + 3 decode


def test_serve_engine_submit_rejects_oversized_prompt():
    """Regression: a prompt longer than the KV budget used to be accepted at
    submit() and only blow up later inside the prefill cache scatter.  The
    engine needs len(prompt) + 1 <= max_len (one decode step of headroom)."""
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    engine = EventLoopEngine(params, cfg, batch=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        engine.submit(Request(rid=0, prompt=list(range(1, 17)),
                              max_new_tokens=1))   # len 16 == max_len: no room
    with pytest.raises(ValueError, match="empty"):
        engine.submit(Request(rid=1, prompt=[], max_new_tokens=1))
    # boundary: len(prompt) + 1 == max_len is admitted and decodes one step
    engine.submit(Request(rid=2, prompt=list(range(1, 16)),
                          max_new_tokens=4))
    done = engine.run_until_drained()
    assert done[0].rid == 2 and done[0].decode_steps == 1  # capped by max_len


def test_serve_engine_run_until_drained_raises_on_tick_exhaustion():
    """Regression: run_until_drained used to return silently with requests
    still queued or resident when max_ticks ran out — a stuck engine looked
    like a drained one."""
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    engine = EventLoopEngine(params, cfg, batch=1, max_len=64)
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=30))
    with pytest.raises(RuntimeError, match="still"):
        engine.run_until_drained(max_ticks=2)
    # the engine is still usable: remaining ticks finish the request
    done = engine.run_until_drained()
    assert done[0].decode_steps == 30
    # an already-drained engine returns immediately regardless of max_ticks
    assert engine.run_until_drained(max_ticks=0) == []

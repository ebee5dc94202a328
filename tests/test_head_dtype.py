"""The output head reads its weight in the dtype it is stored in.

``unembed`` multiplies the final hidden state by the lm_head (or the tied
embedding) with both operands as stored and accumulates in float32, so no
call makes a float32 copy of the head.  On the overlay's generic tier such
a copy was a placed ``cast[float32]`` node whose head-sized output the
product then read.  These tests hold the logits and the training gradient
to the upcast product the head used to compute, and hold the traced
programs to having no upcast of the head, on tiny configs: tied
(minicpm-like), untied (phi3-like), tied with a final softcap
(gemma2-like), and a float32 ``cfg.dtype`` over bfloat16 parameters, where
the product meets at the common type, float32, as it always did.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import ClosedJaxpr, Jaxpr
from repro.configs.archs import smoke_config
from repro.core import trace_to_graph
from repro.core.trace import RESIDUE_PREFIX
from repro.models import model as mdl
from repro.models import params as pm
from repro.models import transformer as tfm
from repro.models.transformer import model_spec

BATCH, MAX_LEN = 2, 32

CASES = {
    "tied": smoke_config("minicpm-2b"),
    "untied": smoke_config("phi3-mini-3.8b"),
    "tied-softcap": smoke_config("gemma2-27b"),
    "float32-over-bf16": smoke_config("phi3-mini-3.8b").scaled(dtype="float32"),
}


def _upcast_unembed(params, h, cfg):
    """The head as it was: both operands copied to float32 first."""
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("case", sorted(CASES))
def test_head_product_reads_stored_dtype(case, monkeypatch):
    cfg = CASES[case]
    act = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(1))
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    assert w.dtype == jnp.bfloat16
    head_shapes = {w.shape, w.shape[::-1]}
    # the common type of the operands: the head is read at it, never below
    common = jnp.promote_types(act, w.dtype)

    # 1. logits: the upcast product's values, in float32
    h = jax.random.normal(jax.random.PRNGKey(2), (BATCH, 3, cfg.d_model),
                          act)
    got = tfm.unembed(params, h, cfg)
    want = _upcast_unembed(params, h, cfg)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))

    # 2. the jaxpr of unembed: the dot reads the head as stored, unless the
    #    hidden state is wider, and converts nothing of the head's shape
    jaxpr = jax.make_jaxpr(lambda p, x: tfm.unembed(p, x, cfg))(params, h)
    eqns = list(_eqns(jaxpr.jaxpr))
    converts = [e for e in eqns if e.primitive.name == "convert_element_type"
                and e.invars[0].aval.shape in head_shapes]
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    assert dots[0].params["preferred_element_type"] == jnp.float32
    assert [v.aval.dtype for v in dots[0].invars] == [common, common]
    assert len(converts) == (0 if common == w.dtype else 1)

    # 3. the decode step as the overlay traces it: the head's dot reads the
    #    weight straight from the graph's parameter input, with no placed
    #    float32 copy between them, unless the hidden state is float32
    caches = mdl.init_cache(cfg, BATCH, MAX_LEN)
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    pos = jnp.zeros((BATCH,), jnp.int32)
    graph = trace_to_graph(
        lambda p, t, c, q: mdl.decode_step(p, cfg, t, c, positions=q),
        params, tok, caches, pos).graph
    casts = [n for n in graph.op_nodes()
             if n.kind == "op" and n.op.name == "cast[float32]"
             and n.aval.shape in head_shapes]
    (dot,) = [n for n in graph.op_nodes() if n.kind == "op"
              and n.op.name == f"{RESIDUE_PREFIX}dot_general]"
              and n.aval.shape[-1] == cfg.vocab_size]
    weight = [graph.nodes[i] for i in dot.inputs
              if graph.nodes[i].aval.shape in head_shapes]
    assert len(weight) == 1
    assert weight[0].aval.dtype == common
    if common == w.dtype:
        assert weight[0].kind == "input" and not casts
    else:
        assert weight[0].op.name == "cast[float32]" and casts == weight

    # 4. training: the gradient matches the upcast head's and keeps the
    #    parameters' dtypes
    tokens = jax.random.randint(jax.random.PRNGKey(3), (BATCH, 8), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    grad = lambda: jax.grad(lambda p: mdl.loss_fn(p, batch, cfg)[0])(params)
    new = grad()
    monkeypatch.setattr(tfm, "unembed", _upcast_unembed)
    old = grad()
    for (path, g_new), g_old, p in zip(
            jax.tree_util.tree_leaves_with_path(new), jax.tree.leaves(old),
            jax.tree.leaves(params)):
        assert g_new.dtype == p.dtype, path
        a = np.asarray(g_new, np.float32)
        b = np.asarray(g_old, np.float32)
        scale = float(np.max(np.abs(b))) or 1.0
        # both round an f32 sum of the same exact products to the leaf's
        # dtype; only the order of accumulation differs, so the gap stays
        # within one bf16 step (2^-7 relative)
        np.testing.assert_allclose(a, b, rtol=2 ** -7, atol=2 ** -8 * scale,
                                   err_msg=jax.tree_util.keystr(path))

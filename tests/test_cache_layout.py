"""The pooled serving cache: ``models.model.install_slot`` scatters a
finished batch-1 prefill cache into one slot of the pool, reading each
leaf's batch axis from the model's cache spec, and ``donation_probes``
names the leaves whose deletion says a decode consumed its cache."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import smoke_config
from repro.models import model as mdl

MAX_LEN = 16


def _filled(cfg, batch: int, seed: int) -> dict:
    """A cache of ``batch`` rows whose every leaf holds distinct values."""
    cache = mdl.init_cache(cfg, batch, MAX_LEN)
    leaves, treedef = jax.tree.flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [jax.random.randint(k, x.shape, 0, 50, x.dtype)
           if x.dtype == jnp.int32 else
           jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
           for k, x in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


def _check_install(cfg, batch: int, slot: int) -> None:
    pool, one = _filled(cfg, batch, 0), _filled(cfg, 1, 1)
    got = mdl.install_slot(cfg, pool, one, slot)
    flat = jax.tree_util.tree_flatten_with_path
    for (path, g), p, o in zip(flat(got)[0], jax.tree.leaves(pool),
                               jax.tree.leaves(one)):
        g, p, o = np.asarray(g), np.asarray(p), np.asarray(o)
        name = jax.tree_util.keystr(path)
        assert g.shape == p.shape and g.dtype == p.dtype, name
        if path[-1].key == "index":
            # a layer's scalar position, shared by every slot
            np.testing.assert_array_equal(g, np.maximum(p, o), err_msg=name)
            continue
        # every other leaf has its batch axis behind the stacked layer axis
        want = p.copy()
        want[:, slot] = o[:, 0]
        np.testing.assert_array_equal(g, want, err_msg=name)


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "zamba2-7b"])
def test_install_slot_writes_only_its_slot(arch):
    """K/V, the conv windows and the SSD state land in row ``slot``; the
    other rows are untouched and the index leaves hold the max."""
    cfg = smoke_config(arch)
    for slot in (0, 2):
        _check_install(cfg, batch=3, slot=slot)


def test_install_slot_into_a_batch_one_pool():
    _check_install(smoke_config("zamba2-7b"), batch=1, slot=0)


def test_install_slot_when_layer_count_equals_batch():
    """The stacked layer axis has the batch's size: the install must still
    write the batch axis, not the first axis that fits."""
    cfg = smoke_config("phi3-mini-3.8b")
    ((_, rep),) = cfg.blocks
    for slot in range(rep):
        _check_install(cfg, batch=rep, slot=slot)


@pytest.mark.parametrize("arch,has_ssm", [("phi3-mini-3.8b", False),
                                          ("zamba2-7b", True)])
def test_donation_probes_find_kv_and_ssm_state(arch, has_ssm):
    cfg = smoke_config(arch)
    caches = mdl.init_cache(cfg, 2, MAX_LEN)
    kv, ssm = mdl.donation_probes(caches)
    assert kv is not None and kv.ndim == 5          # (L, B, Hkv, S, hd)
    assert (ssm is not None) == has_ssm
    if has_ssm:
        assert ssm.dtype == jnp.float32 and ssm.ndim == 5

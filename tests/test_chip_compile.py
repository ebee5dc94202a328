"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

Interpret mode runs a kernel body as plain JAX ops, so it accepts blocks
and stores the TPU compiler refuses.  These tests lower each kernel of the
main path at real widths with ``interpret=False`` against the v5e topology
description and check that the compiled HLO holds the Mosaic kernel
(``tpu_custom_call``).  Nothing runs; a compile takes a second or two.

The served decode step is compiled the same way, as the overlay's generic
tier assembles it (about ten seconds each): its KV cache must be updated in
place, with no relayout copy of the cache or of one layer's slice of it.

The topology is described inside a module-scoped fixture, never at import,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import build_kernel, trace_to_graph
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_scan
from repro.kernels import vmul_reduce as vr
from repro.models import model as mdl
from repro.models import params as pm
from repro.models.transformer import model_spec


@pytest.fixture(scope="module")
def one_chip():
    # libtpu, loaded for its compiler, otherwise writes log files of its own
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_case(name: str, sds):
    """(fn, abstract args) for one kernel at the widths the repo serves."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "rmsnorm":                      # phi3-mini-3.8b, d_model 3072
        return (lambda x, w: rn.rmsnorm(x, w, interpret=False),
                (sds((4, 128, 3072), bf16), sds((3072,), f32)))
    if name == "flash_attention_phi3":         # 32 heads, head_dim 96
        qkv = sds((1, 32, 1024, 96), bf16)
        return (lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
                (qkv, qkv, qkv))
    if name == "flash_attention_gqa":          # 32 q heads over 8 kv heads
        kv = sds((1, 8, 2048, 128), bf16)
        return (lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
                (sds((1, 32, 2048, 128), bf16), kv, kv))
    if name == "ssd_scan":                     # mamba2-130m: 24 heads, p 64
        return (lambda x, a, b, c: ssd_scan.ssd(x, a, b, c, chunk=64,
                                                interpret=False),
                (sds((1, 512, 24, 64), bf16), sds((1, 512, 24), f32),
                 sds((1, 512, 24, 128), bf16), sds((1, 512, 24, 128), bf16)))
    if name == "ssd_scan_zamba2":              # 112 heads, p 64, n 64
        return (lambda x, a, b, c: ssd_scan.ssd(x, a, b, c, chunk=128,
                                                interpret=False),
                (sds((1, 128, 112, 64), bf16), sds((1, 128, 112), f32),
                 sds((1, 128, 112, 64), bf16), sds((1, 128, 112, 64), bf16)))
    if name == "vmul_reduce":                  # 16 M elements per vector
        v = sds((16 * 1024 * 1024,), f32)
        return (lambda a, b: vr.vmul_reduce(a, b, interpret=False), (v, v))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["rmsnorm", "flash_attention_phi3",
                                  "flash_attention_gqa", "ssd_scan",
                                  "ssd_scan_zamba2", "vmul_reduce"])
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    fn, args = _kernel_case(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name.startswith("ssd_scan"):
        # the instruction name a device trace finds the kernel by
        assert re.search(rf"%{ssd_scan.KERNEL_NAME}(\.\d+)? = .* custom-call",
                         compiled.as_text())


# the served cells' widths at two layers (zamba2: two repetitions of a
# mamba layer and a hybrid one): (arch, batch); max_len 1024
DECODE_CASES = [("phi3-mini-3.8b", 4), ("minicpm-2b", 6), ("zamba2-7b", 16)]


def _two_layers(arch: str, layers: int):
    cfg = get_config(arch)
    if cfg.hybrid_layers:
        return cfg.scaled(blocks=((("mamba", "hybrid0"), layers),))
    return cfg.scaled(blocks=((("dense",), layers),))


def _copied_shapes(hlo: str) -> set[tuple[int, ...]]:
    """The shapes every ``copy``/``copy-start`` of the module produces."""
    shapes = set()
    for line in hlo.splitlines():
        m = re.search(r"= (.*?) (copy|copy-start)\(", line)
        if m:
            for dims in re.findall(r"\[([\d,]*)\]", m.group(1)):
                shapes.add(tuple(int(d) for d in dims.split(",") if d))
    return shapes


@pytest.mark.parametrize("arch,batch", DECODE_CASES)
def test_decode_updates_cache_in_place_for_v5e(arch, batch, one_chip,
                                               no_compile_cache):
    layers, max_len = 2, 1024
    cfg = _two_layers(arch, layers)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, pm.abstract(model_spec(cfg)))
    caches = jax.tree.map(sds, jax.eval_shape(
        lambda: mdl.init_cache(cfg, batch, max_len)))
    tok = sds(jax.ShapeDtypeStruct((batch, 1), jnp.int32))
    pos = sds(jax.ShapeDtypeStruct((batch,), jnp.int32))
    step = lambda p, t, c, q: mdl.decode_step(p, cfg, t, c, positions=q)
    graph = trace_to_graph(step, params, tok, caches, pos,
                           name=f"{cfg.name}.decode").graph
    routes = sds(jax.ShapeDtypeStruct((len(graph.edges()),), jnp.int32))
    n_params, n_cache = len(jax.tree.leaves(params)), len(jax.tree.leaves(caches))
    first = 1 + n_params + 1                    # after routes, params, tokens
    donate = tuple(range(first, first + n_cache))
    compiled = jax.jit(build_kernel(graph), donate_argnums=donate).lower(
        routes, *jax.tree.leaves((params, tok, caches, pos))).compile()

    kv = (batch, cfg.num_kv_heads, max_len, cfg.resolved_head_dim)
    assert not _copied_shapes(compiled.as_text()) & {
        (layers, *kv), (1, *kv), kv}
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(caches))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes

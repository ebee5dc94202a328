"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

Interpret mode runs a kernel body as plain JAX ops, so it accepts blocks
and stores the TPU compiler refuses.  These tests lower each kernel of the
main path at real widths with ``interpret=False`` against the v5e topology
description and check that the compiled HLO holds the Mosaic kernel
(``tpu_custom_call``).  Nothing runs; a compile takes a second or two.

The topology is described inside a module-scoped fixture, never at import,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_scan
from repro.kernels import vmul_reduce as vr


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
    if name == "vmul_reduce":                  # 16 M elements per vector
        v = sds((16 * 1024 * 1024,), f32)
        return (lambda a, b: vr.vmul_reduce(a, b, interpret=False), (v, v))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["rmsnorm", "flash_attention_phi3",
                                  "flash_attention_gqa", "ssd_scan",
                                  "vmul_reduce"])
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    fn, args = _kernel_case(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()

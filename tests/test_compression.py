"""Gradient-compression tests: quantization error bounds, error feedback,
and a compressed cross-"pod" psum on forced host devices."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.compression import (CompressedReducer, compression_error,
                                     dequantize, quantize)
from tests.test_distributed import run_with_devices


def test_quantize_roundtrip_error_bound():
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (256, 64))}
    q, s = quantize(g)
    back = dequantize(q, s)
    max_abs = float(jnp.max(jnp.abs(g["w"])))
    # symmetric int8: error <= scale/2 = max_abs / 254
    err = float(jnp.max(jnp.abs(back["w"] - g["w"])))
    assert err <= max_abs / 254 + 1e-6
    assert q["w"].dtype == jnp.int8


def test_error_feedback_accumulates_to_true_sum():
    """Σ compressed(g_t) -> Σ g_t when error feedback carries residuals."""
    key = jax.random.PRNGKey(1)
    grads = [{"w": jax.random.normal(k, (64,)) * 0.01}
             for k in jax.random.split(key, 50)]
    red = CompressedReducer()
    total_c = jnp.zeros((64,))
    total_t = jnp.zeros((64,))
    for g in grads:
        total_c = total_c + red.step(g)["w"]
        total_t = total_t + g["w"]
    # with EF the cumulative compressed sum tracks the true sum tightly
    drift = float(jnp.max(jnp.abs(total_c - total_t)))
    scale = float(jnp.max(jnp.abs(total_t)))
    assert drift < 0.02 * max(scale, 1e-3)


def test_compression_error_is_zero_for_representable():
    g = {"w": jnp.asarray([0.0, 127.0, -127.0, 64.0])}
    e = compression_error(g)
    np.testing.assert_allclose(np.asarray(e["w"]), 0.0, atol=1e-5)


def test_compressed_psum_across_pods():
    out = run_with_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.optim.compression import quantize, dequantize

        mesh = make_mesh((4,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 128)) * 0.01

        def reduce_compressed(g_local):
            q, s = quantize({"g": g_local})
            # int32 accumulate: overflow-safe for <= 2^23 shards
            total = jax.lax.psum(q["g"].astype(jnp.int32), "pod")
            # scales differ per shard; psum the dequantized contribution
            s_all = jax.lax.all_gather(s["g"], "pod")
            # conservative: dequantize with per-shard scale then sum
            deq = jax.lax.psum(q["g"].astype(jnp.float32) * s["g"], "pod")
            return deq / 4.0

        fn = jax.jit(shard_map(reduce_compressed, mesh=mesh,
                               in_specs=P("pod"), out_specs=P(),
                               check_vma=False))
        with mesh:
            mean_c = fn(g).reshape(-1)   # shard_map keeps the local rank
        mean_t = jnp.mean(g, axis=0)
        # int8 error bound: scale/2 per shard ~ max|g|/254 ~ 1.6e-4
        # (abs bound only — rel error is unbounded for near-zero entries)
        np.testing.assert_allclose(np.asarray(mean_c), np.asarray(mean_t),
                                   rtol=0, atol=8e-4)
        print("PSUM_OK")
    """)
    assert "PSUM_OK" in out

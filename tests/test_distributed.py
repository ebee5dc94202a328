"""Distributed-semantics tests, run in subprocesses with forced host devices
(jax locks the device count at first init, so multi-device tests need their
own process).

Covers the invariants the dry-run relies on:
  * EP (shard_map) MoE == local MoE (the §Perf deepseek optimization is
    semantics-preserving),
  * sharded overlay assembly (real ppermute hops) == local assembly,
  * a sharded train step == the single-device train step.
"""

import os
import re
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(n: int, code: str, timeout: int = 420) -> str:
    env = dict(os.environ)
    # drop any inherited device-count flag (e.g. the CI lane's =8): the last
    # occurrence wins in XLA's flag parsing, so an inherited value would
    # silently override the count this test asked for
    inherited = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n} "
                        + inherited)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_ep_moe_matches_local_moe():
    out = run_with_devices(8, """
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding as shd
        from repro.configs.archs import smoke_config
        from repro.models import moe as moe_lib, params as pm

        cfg = smoke_config("granite-moe-1b-a400m").scaled(
            num_experts=8, experts_per_token=2, capacity_factor=8.0)
        p = pm.init(moe_lib.moe_spec(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)

        y_local, aux_local = moe_lib._moe_fwd_local(p, x, cfg)

        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        shd.set_active(mesh, shd.DEFAULT_RULES)
        with mesh:
            y_ep, aux_ep = jax.jit(
                lambda p, x: moe_lib.moe_fwd_ep(p, x, cfg, mesh,
                                                shd.DEFAULT_RULES))(p, x)
        shd.set_active(None)
        np.testing.assert_allclose(np.float32(y_ep), np.float32(y_local),
                                   rtol=5e-2, atol=5e-2)
        print("EP_OK")
    """)
    assert "EP_OK" in out


def test_sharded_overlay_matches_local():
    out = run_with_devices(9, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import (TileGrid, assemble, assemble_sharded,
                                place_dynamic, vmul_reduce_graph, wrap_sharded)
        g = vmul_reduce_graph(4096)
        pl = place_dynamic(g, TileGrid(3, 3))
        a = jax.random.normal(jax.random.PRNGKey(0), (4096,))
        b = jax.random.normal(jax.random.PRNGKey(1), (4096,))
        ref = assemble(g, pl)(a, b)
        from repro.compat import make_mesh
        mesh = make_mesh((9,), ("tiles",))
        acc = assemble_sharded(g, pl, mesh)
        fn = wrap_sharded(acc, g, mesh)
        with mesh:
            out = fn(a, b)
        np.testing.assert_allclose(np.float32(out), np.float32(ref),
                                   rtol=1e-5)
        print("SHARD_OK")
    """)
    assert "SHARD_OK" in out


def test_sharded_train_step_matches_single_device():
    out = run_with_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro import sharding as shd
        from repro.configs.archs import smoke_config
        from repro.data.pipeline import make_batch
        from repro.models import model as mdl, params as pm
        from repro.models.transformer import model_spec
        from repro.launch import steps as steps_lib

        cfg = smoke_config("phi3-mini-3.8b")
        spec = model_spec(cfg)
        params = pm.init(spec, jax.random.PRNGKey(0))
        batch = make_batch(cfg, 4, 32)

        loss_1dev, _ = mdl.loss_fn(params, batch, cfg)

        from repro.compat import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        shd.set_active(mesh, shd.DEFAULT_RULES)
        with mesh:
            loss_mesh, _ = jax.jit(
                lambda p, b: mdl.loss_fn(p, b, cfg))(params, batch)
        shd.set_active(None)
        np.testing.assert_allclose(float(loss_mesh), float(loss_1dev),
                                   rtol=2e-2, atol=2e-2)
        print("TRAIN_OK", float(loss_1dev), float(loss_mesh))
    """)
    assert "TRAIN_OK" in out

"""Fig. 2 + Fig. 3 reproduction: VMUL & Reduce across five 'hardware targets'.

Paper setup (§III): ``sum = Σ A⃗·B⃗`` over 16 KB of data on a 3×3 overlay.
Five targets, mapped per DESIGN.md §2:

  static overlay, scenario 1..3 — VMUL/Reduce placed with 1/2/3 pass-through
      tiles between them (Fig. 2); each pass-through is an
      optimization_barrier'd copy the compiler cannot fuse away
  dynamic overlay               — contiguous placement, zero pass-throughs,
      fully fusable (the paper's contribution)
  fully-custom (HLS)            — one monolithic jit of the expression,
      no overlay structure at all (upper bound)
  ARM software baseline         — eager NumPy

The paper's qualitative claims this must reproduce:
  * static runtime grows monotonically with pass-through count,
  * dynamic ≈ custom (operators contiguous + pipelined),
  * PR overhead excluded from the curve (measured in pr_overhead.py).

    PYTHONPATH=src:. python -m benchmarks.fig3_vmul_reduce [--smoke]
    PYTHONPATH=src:. python -m benchmarks.fig3_vmul_reduce --sharded

``--sharded`` is its own invocation: it puts the process on 9 host CPU
devices (the 3×3 overlay, every hop a real ``ppermute``) before JAX first
touches a backend, and exits non-zero if it does not get them.  No variant
starts a child process.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_call
from repro.configs.archs import PAPER_VECTOR_LEN
from repro.core import (TileGrid, assemble, place_dynamic,
                        place_static, trace_to_graph)


def vmul_reduce_traced(n: int):
    """The paper's workload through the trace frontend: plain source code,
    lowered to the same VMUL -> Reduce graph the hand-built IR produced."""
    def vmul_reduce(a, b):
        return jnp.sum(a * b)
    sds = jax.ShapeDtypeStruct((n,), jnp.float32)
    return trace_to_graph(vmul_reduce, sds, sds).graph


def scenarios(n: int):
    """Fixed placements giving 0/1/2/3 pass-through tiles (Fig. 2).

    The 3×3 grid's LARGE tiles sit at (0,0),(1,1),(2,2); Reduce (LARGE) is
    pinned at (0,0) and VMUL moved progressively further away.
    """
    g = vmul_reduce_traced(n)
    ops = g.op_nodes()
    vmul, red = ops[0].node_id, ops[1].node_id
    grid = TileGrid(3, 3)
    return g, grid, [
        ("static_0pass", {vmul: (0, 1), red: (0, 0)}),   # adjacent
        ("static_1pass", {vmul: (0, 2), red: (0, 0)}),   # manhattan 2
        ("static_2pass", {vmul: (1, 2), red: (0, 0)}),   # manhattan 3
        ("static_3pass", {vmul: (2, 2), red: (0, 0)}),   # manhattan 4
    ]


def bench_size(n: int, label: str) -> list[str]:
    rows = []
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n,))
    b = jax.random.normal(jax.random.PRNGKey(1), (n,))

    g, grid, fixed = scenarios(n)

    for name, placement in fixed:
        pl = place_static(g, grid, placement)
        acc = assemble(g, pl)
        us = time_call(jax.jit(acc.fn), a, b)
        rows.append(row(f"fig3/{label}/{name}", us,
                        f"passthrough={pl.total_passthrough}"))

    pl = place_dynamic(g, grid)
    acc = assemble(g, pl)
    rows.append(row(f"fig3/{label}/dynamic", time_call(jax.jit(acc.fn), a, b),
                    f"passthrough={pl.total_passthrough}"))

    custom = jax.jit(lambda a, b: jnp.sum(a * b))
    rows.append(row(f"fig3/{label}/custom_hls", time_call(custom, a, b),
                    "monolithic_jit"))

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or n <= 1024 * 1024:  # interpreted pallas is python-speed per block
        from repro.kernels import ops as kops
        rows.append(row(
            f"fig3/{label}/pallas_fused",
            time_call(jax.jit(kops.vmul_reduce), a, b),
            "compiled" if on_tpu else "interpret_mode"))

    an, bn = np.asarray(a), np.asarray(b)
    import time as _t
    t0 = _t.perf_counter()
    iters = 50
    for _ in range(iters):
        float(np.dot(an, bn))
    rows.append(row(f"fig3/{label}/software_numpy",
                    (_t.perf_counter() - t0) / iters * 1e6, "eager"))
    return rows


def sharded_main() -> int:
    """The ``--sharded`` invocation: 9 host devices = the 3×3 overlay; every
    hop is a REAL ``ppermute`` transfer between devices (the ICI-faithful
    mode), where Fig. 3's separation reproduces."""
    import os

    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=9 "
                               + os.environ.get("XLA_FLAGS", ""))
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) != 9:
        print(f"fig3 --sharded: needs 9 host devices, got "
              f"{len(jax.devices())} (was JAX already initialised?)")
        return 1

    from repro.compat import make_mesh
    from repro.core import assemble_sharded, wrap_sharded

    n = 4 * 1024 * 1024  # 16 MB per vector: transfers dominate, compute tiny
    mesh = make_mesh((9,), ("tiles",))
    a = jax.random.normal(jax.random.PRNGKey(0), (n,))
    b = jax.random.normal(jax.random.PRNGKey(1), (n,))

    g, grid, fixed = scenarios(n)
    rows, stat = [], []
    for name, placement in fixed:
        pl = place_static(g, grid, placement)
        fn = wrap_sharded(assemble_sharded(g, pl, mesh), g, mesh)
        with mesh:
            us = time_call(fn, a, b, warmup=2, iters=8)
        stat.append(us)
        rows.append(row(f"fig3/sharded_16MB/{name}", us,
                        f"hops={pl.total_hops}"))
    pl = place_dynamic(g, grid)
    fn = wrap_sharded(assemble_sharded(g, pl, mesh), g, mesh)
    with mesh:
        dyn = time_call(fn, a, b, warmup=2, iters=8)
    rows.append(row("fig3/sharded_16MB/dynamic", dyn, f"hops={pl.total_hops}"))
    ok_monotone = all(stat[i] <= stat[i + 1] * 1.15
                      for i in range(len(stat) - 1))
    rows.append(row("fig3/claim_static_monotone_in_passthrough", 0.0,
                    f"holds={ok_monotone}"))
    rows.append(row("fig3/claim_dynamic_beats_static", 0.0,
                    f"holds={dyn <= min(stat) * 1.1}"))
    print("name,us_per_call,derived")
    print("\n".join(rows))
    return 0


def main(smoke: bool = False) -> list[str]:
    if smoke:
        # tiny single-process pass: every local code path executes
        return bench_size(1024, "smoke")
    # the paper's exact data size (16 KB): pass-through cost is sub-µs on a
    # CPU cache, so this point reproduces the SETUP but not the separation;
    # the separation is the --sharded invocation's
    return bench_size(PAPER_VECTOR_LEN, "16KB_paper")


if __name__ == "__main__":
    import sys
    if "--sharded" in sys.argv[1:]:
        raise SystemExit(sharded_main())
    from benchmarks.common import bench_cli
    bench_cli(main)

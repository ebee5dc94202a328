"""Warm restart: cold-start TTFT with and without the persistent store.

The persistent :class:`~repro.core.store.BitstreamStore` (DESIGN.md §11)
serializes every compiled overlay kernel to disk as it lands, so a
RESTARTED serving process rebuilds its working set by deserializing
executables (milliseconds) instead of re-tracing and re-compiling them
through XLA (seconds).  This benchmark measures exactly that boundary:

* boot A — fresh store directory: an :class:`EventLoopEngine` warms up and
  serves one batch of requests, paying every trace + XLA compile.  The
  overlay closes cleanly (persists drain, measurement ledger saved).
* boot B — same directory, new process state: an identical engine serves
  the identical requests; its prefill/decode kernels load off disk.

Reported: time-to-first-token for each boot (overlay construction through
the first emitted token, warmup included — the restart-latency number an
operator sees), the speedup, and store hit counts.  Token streams are
asserted bit-identical between boots: the store must change WHERE the
executable comes from, never what it computes.
"""

from __future__ import annotations

import shutil
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import row
from repro.configs.archs import smoke_config
from repro.core import Overlay
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Request

ARCH = "phi3-mini-3.8b"


def _boot(store_dir: str, *, params, cfg, prompts: list[list[int]],
          max_new: int, batch: int, max_len: int) -> dict:
    """One serving boot against ``store_dir``: build the overlay + engine,
    warm up, serve every prompt to completion.  TTFT is timed from overlay
    construction (params already live — restart reuses checkpoints) to the
    first emitted token."""
    t0 = time.perf_counter()
    overlay = Overlay(3, 3, store_path=store_dir)
    engine = EventLoopEngine(params, cfg, batch=batch, max_len=max_len,
                             overlay=overlay)
    engine.warmup(prompt_lens=tuple(sorted({len(p) for p in prompts})))
    for rid, prompt in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=max_new))
    ttft = None
    done: list[Request] = []
    while engine.queue or any(r is not None for r in engine.slot_req):
        done.extend(engine.step())
        if ttft is None:
            ttft = time.perf_counter() - t0
    overlay.drain()
    overlay.close()
    stats = overlay.cache.stats
    return {
        "ttft_s": ttft if ttft is not None else time.perf_counter() - t0,
        "streams": {r.rid: list(r.out) for r in done},
        "store_hits": stats.store_hits,
        "compile_s": stats.compile_seconds,
        "store_load_s": stats.store_load_seconds,
        "store": overlay.describe()["store"],
    }


def main(smoke: bool = False) -> list[str]:
    cfg = smoke_config(ARCH)
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    batch, max_len = (2, 64) if smoke else (4, 128)
    max_new = 4 if smoke else 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=(8 if smoke else 16,)).tolist()
               for _ in range(batch)]

    store_dir = tempfile.mkdtemp(prefix="repro-warm-restart-")
    try:
        cold = _boot(store_dir, params=params, cfg=cfg, prompts=prompts,
                     max_new=max_new, batch=batch, max_len=max_len)
        warm = _boot(store_dir, params=params, cfg=cfg, prompts=prompts,
                     max_new=max_new, batch=batch, max_len=max_len)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    assert warm["streams"] == cold["streams"], \
        "warm restart changed the token streams"
    assert warm["store_hits"] > 0, \
        "warm boot never hit the bitstream store"
    speedup = cold["ttft_s"] / max(warm["ttft_s"], 1e-9)
    if not smoke:
        # the acceptance bar: restarting next to a populated store must be
        # at least 3x faster to the first token than the first boot
        assert speedup >= 3.0, \
            f"warm restart speedup {speedup:.2f}x < 3x"
    entries = cold["store"]["entries"] if cold["store"] else 0
    return [
        row("warm_restart/cold_boot_ttft", cold["ttft_s"] * 1e6,
            f"compile_s={cold['compile_s']:.3f} "
            f"store_entries={entries}"),
        row("warm_restart/warm_boot_ttft", warm["ttft_s"] * 1e6,
            f"speedup={speedup:.2f} store_hits={warm['store_hits']} "
            f"store_load_s={warm['store_load_s']:.4f} identical=1"),
    ]


if __name__ == "__main__":
    from benchmarks.common import bench_cli
    bench_cli(main)

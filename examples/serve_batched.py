"""Batched serving example: continuous slot recycling on a shared fabric.

Part 1 runs a reduced phi3-family model through the engine: the prefill
chunk and decode are TWO separate accelerators resident on one overlay —
``overlay.jit`` traces each, places them in disjoint tiles under a
footprint budget, and holds the compiled steps in the bitstream cache.
Every tick after the first dispatches straight to the resident
accelerator: no re-trace, no re-place, not even a cache walk (residency
short-circuits above the cache).

Part 2 shares ONE fabric between TWO models: both engines' prefill-chunk
and decode accelerators co-reside, and the fabric report shows
per-resident tile occupancy — the paper's multi-accelerator PR-region
picture.

Part 3 turns on the asynchronous download pipeline
(``Overlay(async_downloads=True)``): the engine prefetches the decode
accelerator while the first prefill chunk runs, early ticks are served by
the traced-function fallback whenever a bitstream is still in flight, and
the compiled accelerators swap in mid-stream — time-to-first-token no
longer waits for any XLA compile.

    PYTHONPATH=src python examples/serve_batched.py
"""

import time

import jax
import numpy as np

from repro.configs.archs import smoke_config
from repro.core import Overlay
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Request


def run_single_model():
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    overlay = Overlay(3, 3)
    engine = EventLoopEngine(params, cfg, batch=4, max_len=96,
                             overlay=overlay)

    rng = np.random.default_rng(0)
    n_requests = 10
    for rid in range(n_requests):
        prompt = rng.integers(0, cfg.vocab_size, size=(12,)).tolist()
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=24))

    t0 = time.perf_counter()
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)}/{n_requests} requests through 4 slots, "
          f"{tokens} tokens in {dt:.2f}s ({tokens/dt:.1f} tok/s)")
    for r in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"  req {r.rid}: first-8 {r.out[:8]}")
    d = overlay.describe()
    fab = d["fabric"]
    print(f"[serve] prefill+decode co-resident: "
          f"{[v['name'] for v in fab['residents'].values()]} "
          f"({fab['tiles_used']}/{fab['tiles']} tiles)")
    print(f"[serve] overlay: trace {d['trace_seconds']*1e3:.0f} ms once, "
          f"downloads {d['downloads']}, reclaims {d['reclaims']}, "
          f"cache {d['cache']}")
    assert len(done) == n_requests
    assert len(fab["residents"]) >= 2          # prefill + decode


def run_multi_model_shared_fabric():
    """Two models served off ONE overlay: four accelerators, one fabric."""
    overlay = Overlay(3, 3)
    engines = {}
    for seed, arch in enumerate(("phi3-mini-3.8b", "minicpm-2b")):
        cfg = smoke_config(arch)
        params = pm.init(model_spec(cfg), jax.random.PRNGKey(seed))
        engines[arch] = EventLoopEngine(params, cfg, batch=2, max_len=48,
                                        overlay=overlay)
        for rid in range(3):
            engines[arch].submit(
                Request(rid=rid, prompt=[1, 2, 3, 4, 5], max_new_tokens=8))

    done = {arch: [] for arch in engines}
    for _ in range(200):                        # interleave the two engines
        for arch, eng in engines.items():
            done[arch].extend(eng.step())
        if all(len(d) == 3 for d in done.values()):
            break

    fab = overlay.describe()["fabric"]
    print(f"[serve-multi] {sum(map(len, done.values()))} requests from "
          f"{len(engines)} models on one {fab['tiles']}-tile fabric:")
    for rid, info in fab["residents"].items():
        print(f"  {info['name']:>24s}  tiles {info['tiles']}")
    print(f"[serve-multi] utilization {fab['utilization']:.0%}, "
          f"fragmentation {fab['fragmentation']:.0%}, "
          f"reclaims {overlay.stats.reclaims}")
    assert all(len(d) == 3 for d in done.values())


def run_async_pipeline():
    """Serving with background PR downloads: prefetch decode, serve from
    fallbacks while bitstreams are in flight, swap without a stalled tick."""
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    overlay = Overlay(3, 3, async_downloads=True)
    engine = EventLoopEngine(params, cfg, batch=4, max_len=96,
                             overlay=overlay)

    rng = np.random.default_rng(0)
    for rid in range(8):
        prompt = rng.integers(0, cfg.vocab_size, size=(12,)).tolist()
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=16))

    t0 = time.perf_counter()
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0
    overlay.drain(60)                      # let the last swap land
    d = overlay.describe()
    tokens = sum(len(r.out) for r in done)
    print(f"[serve-async] {len(done)} requests, {tokens} tokens in {dt:.2f}s; "
          f"prefetches {d['prefetches']} (hits {d['prefetch_hits']}), "
          f"fallback-served calls {d['fallback_calls']}, "
          f"background download {d['scheduler']['download_seconds']:.2f}s "
          f"over {d['scheduler']['completed']} bitstreams")
    for rid_, info in d["fabric"]["residents"].items():
        print(f"  {info['name']:>20s}  tiles {info['tiles']}  "
              f"download_cost {info['download_cost']*1e3:.0f} ms")
    assert len(done) == 8
    assert d["prefetches"] >= 1            # decode was requested during prefill


def main():
    run_single_model()
    run_multi_model_shared_fabric()
    run_async_pipeline()


if __name__ == "__main__":
    main()

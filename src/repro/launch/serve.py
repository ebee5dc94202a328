"""Serving launcher: batched requests through the EventLoopEngine.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch phi3-mini-3.8b --smoke --requests 8 --batch 4

``--overlay`` serves through the JIT-assembled accelerator path: the decode
step is traced by the overlay frontend, placed on a 3x3 tile grid and cached
as a bitstream (paper C1/C3) instead of being jitted directly.

``--fleet N`` serves through a :class:`FleetOverlay` of N member fabrics
(DESIGN.md §8): prefill/decode accelerators are placed across members by
the fleet cost score, hot ones replicate, and dispatches route to the
least-loaded live copy.  Implies the overlay path.

The engine (DESIGN.md §9) interleaves chunked power-of-two-bucketed
prefill with decode ticks behind SLO-aware admission: ``--chunk`` sets the
prefill chunk size, ``--max-queue`` bounds queue depth, and
``--max-queue-delay`` (seconds) sheds requests that would miss their delay
budget.  Shed requests and the engine's latency histograms are reported
after the drain.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.archs import smoke_config
from repro.core import FleetOverlay, Overlay
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Request


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--overlay", action="store_true",
                    help="serve through the JIT-assembled overlay decode path")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through a FleetOverlay of N member fabrics "
                         "(implies --overlay)")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="persistent bitstream store directory: compiled "
                         "overlay kernels are serialized there and a "
                         "restarted server warm-boots from disk instead of "
                         "recompiling (implies --overlay)")
    ap.add_argument("--chunk", type=int, default=64,
                    help="prefill chunk size (power of two)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="shed submissions beyond this queue depth")
    ap.add_argument("--max-queue-delay", type=float, default=None,
                    help="shed requests queued longer than this (seconds)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encdec:
        raise SystemExit("serve launcher targets decoder LMs; use examples/")

    params = pm.init(model_spec(cfg), jax.random.PRNGKey(args.seed))
    if args.fleet > 0:
        overlay = FleetOverlay(args.fleet, rows=3, cols=3,
                               store_path=args.store)
    elif args.overlay or args.store is not None:
        overlay = Overlay(3, 3, store_path=args.store)
    else:
        overlay = None
    engine = EventLoopEngine(
        params, cfg, batch=args.batch, max_len=args.max_len,
        overlay=overlay, chunk=args.chunk, max_queue=args.max_queue,
        max_queue_delay=args.max_queue_delay)

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=(args.prompt_len,)).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=args.max_new))
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0

    tokens = sum(len(r.out) for r in done)
    print(f"[serve] {cfg.name}: {len(done)}/{args.requests} requests, "
          f"{tokens} tokens in {dt:.2f}s ({tokens/dt:.1f} tok/s)")
    if engine.shed:
        print(f"[serve] shed {len(engine.shed)} request(s): "
              f"{[(r.rid, r.shed_reason) for r in engine.shed]}")
    print(f"[serve] metrics: {engine.metrics()}")
    if overlay is not None:
        print(f"[serve] overlay: {overlay.describe()}")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")
    if overlay is not None:
        # drains queued persists and saves the measurement ledger when a
        # --store directory is attached
        overlay.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

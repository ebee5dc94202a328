"""Serve phi3-mini-3.8b at full published width on one TPU, through the overlay.

    python chip_smoke.py [--seed 0]

A smoke run, not a benchmark: it proves the served path starts and answers
correctly on the chip.  One process, one chip:

1. set-up: random bf16 weights of ``phi3-mini-3.8b`` (3.82 B parameters,
   all 32 layers) made on the chip from ``--seed``;
2. overlay: ``EventLoopEngine`` over ``Overlay(3, 3)`` (batch 4, max_len
   1024, chunk 128).  ``warmup`` downloads (compiles) the decode step and
   one prefill-chunk accelerator per bucket; four requests (prompts of 100,
   256, 300 and 700 tokens, 16 new tokens each) are then served;
3. reference: the overlay engine and its KV cache are dropped, and the same
   requests are served by the plain ``jax.jit`` engine (``overlay=None``).

It fails unless every request completes, the greedy token ids of the two
engines are identical, every overlay failure and fallback counter is zero,
and the overlay's compiled prefill and decode hold the rmsnorm Pallas
kernel (``tpu_custom_call``).  Without a TPU it exits non-zero and prints no
result.  The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "phi3-mini-3.8b"
PROMPT_LENS = (100, 256, 300, 700)
MAX_NEW = 16
BATCH, MAX_LEN, CHUNK = 4, 1024, 128
# overlay counters that must stay zero: any of them means a request was
# answered by a fallback instead of the compiled accelerator
ZERO_COUNTERS = ("download_failures", "dispatch_failures",
                 "dispatch_fallbacks", "breaker_opens")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def serve(engine, prompts: list[list[int]]) -> tuple[dict, float]:
    import jax

    from repro.serving import Request
    t0 = time.perf_counter()
    for rid, prompt in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=MAX_NEW))
    done = engine.run_until_drained()
    jax.block_until_ready(engine.caches)
    return {r.rid: r for r in done}, time.perf_counter() - t0


def pallas_kernels(hlo: str) -> set[str]:
    """Names of the jitted wrappers whose ``pallas_call`` lowered to a
    Mosaic kernel in this compiled module."""
    if "tpu_custom_call" not in hlo:
        return set()
    return set(re.findall(r"jit\((\w+)\)/pallas_call", hlo))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    from repro.configs import get_config
    from repro.core import Overlay
    from repro.models import params as pm
    from repro.models.transformer import model_spec
    from repro.serving import EventLoopEngine

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    log(f"compile cache: {cache_dir}")
    cfg = get_config(ARCH)
    spec = model_spec(cfg)
    t0 = time.perf_counter()
    params = pm.init(spec, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    log(f"set-up: {ARCH} {pm.count(spec) / 1e9:.3f} B params, "
        f"{cfg.num_layers} layers, d_model {cfg.d_model}, made on the chip "
        f"in {time.perf_counter() - t0:.2f} s")

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in PROMPT_LENS]
    n_tokens = len(prompts) * (1 + MAX_NEW)

    # -- overlay engine --------------------------------------------------
    ov = Overlay(3, 3)
    engine = EventLoopEngine(params, cfg, batch=BATCH, max_len=MAX_LEN,
                             chunk=CHUNK, overlay=ov)
    t0 = time.perf_counter()
    engine.warmup(PROMPT_LENS)
    log(f"set-up: overlay warm-up {time.perf_counter() - t0:.2f} s "
        f"(trace {ov.stats.trace_seconds:.2f} s)")
    compiled = {}
    for res in ov.fabric.lru_order():
        toks = [n.aval.shape for n in res.graph.nodes
                if n.kind == "input" and n.aval.dtype == np.int32
                and len(n.aval.shape) == 2]
        label = f"{res.name}{list(toks[0]) if toks else ''}"
        hlo = "".join(ov.cache.peek(k).as_text() for k in res.cache_keys)
        compiled[label] = pallas_kernels(hlo)
        log(f"set-up: accelerator {label}: download (compile) "
            f"{res.download_cost:.2f} s, Pallas kernels "
            f"{sorted(compiled[label])}")
    served_ov, dt = serve(engine, prompts)
    log(f"overlay engine: {len(served_ov)}/{len(prompts)} requests, "
        f"{n_tokens} tokens in {dt:.2f} s (smoke run, not a benchmark)")
    failures = ov.failure_ledger()
    stats = ov.stats
    ov_out = {rid: list(r.out) for rid, r in served_ov.items()}
    ov_done = all(r.done for r in served_ov.values()) and not engine.shed
    peak_ov = dev.memory_stats()["peak_bytes_in_use"]
    log(f"overlay engine: peak_bytes_in_use {peak_ov}")
    log(f"overlay failures: {failures}, fallback_calls "
        f"{stats.fallback_calls}")
    ov.close()
    del engine, served_ov, ov
    gc.collect()

    # -- plain jax.jit engine --------------------------------------------
    ref = EventLoopEngine(params, cfg, batch=BATCH, max_len=MAX_LEN,
                          chunk=CHUNK, overlay=None)
    served_ref, dt = serve(ref, prompts)
    log(f"jax.jit engine: {len(served_ref)}/{len(prompts)} requests, "
        f"{n_tokens} tokens in {dt:.2f} s including its compiles "
        f"(smoke run, not a benchmark)")
    ref_out = {rid: list(r.out) for rid, r in served_ref.items()}
    peak = dev.memory_stats()["peak_bytes_in_use"]
    log(f"peak_bytes_in_use {peak}")
    log(f"compile cache: {cache_events['hits']} hits, "
        f"{cache_events['misses']} misses")

    # -- verdict ---------------------------------------------------------
    problems = []
    if len(ov_out) != len(prompts) or not ov_done:
        problems.append("overlay engine left requests unfinished")
    if any(len(o) != 1 + MAX_NEW for o in ov_out.values()):
        problems.append("overlay engine returned short streams")
    if any(not 0 <= t < cfg.vocab_size for o in ov_out.values() for t in o):
        problems.append("overlay engine returned ids outside the vocab")
    if ov_out != ref_out:
        first = {rid: next((i for i, (a, b) in enumerate(
            zip(ov_out[rid], ref_out.get(rid, []))) if a != b), None)
            for rid in ov_out}
        problems.append(f"token ids differ from the jax.jit engine "
                        f"(first differing position per request: {first})")
    bad = {k: failures[k] for k in ZERO_COUNTERS if failures[k]}
    if stats.fallback_calls:
        bad["fallback_calls"] = stats.fallback_calls
    if bad:
        problems.append(f"overlay failure counters not zero: {bad}")
    for label in ("decode", "prefill_chunk"):
        hit = [k for k in compiled if label in k]
        if not hit or not all("rmsnorm" in compiled[k] for k in hit):
            problems.append(f"no rmsnorm Pallas kernel in compiled {label}")
    if problems:
        for p in problems:
            print(f"chip_smoke: FAILED: {p}", file=sys.stderr)
        return 1
    log(f"token ids identical across engines: {ov_out[0][:8]}...")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

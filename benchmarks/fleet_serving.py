"""Fleet serving: aggregate throughput + tail latency, fleet vs one fabric.

Saturating multi-tenant load: several tenants (distinct smoke archs) each
run an :class:`EventLoopEngine` against ONE shared overlay, with prompt
lengths in two chunk buckets so every tenant owns a decode accelerator
plus two prefill-chunk accelerators.  On a single 3x3 fabric the combined working set exceeds the
tile supply — every admission wave reclaims someone else's accelerator and
repays its download (placement churn).  A 4-member :class:`FleetOverlay`
places the same working set across fabrics (cost-score placement), keeps
everything resident, replicates the hot decode accelerators
(``replicate_after`` watermark) and least-loaded-routes their dispatches.

Reported per configuration: aggregate tokens/sec, p99 time-to-first-token
(submit -> first emitted token, queue wait included), downloads paid, and
the fleet's live replica count.  Token streams are asserted bit-identical
between the two runs request-by-request (same params, same prompts, same
greedy argmax — residency policy must never change the math).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import row
from repro.configs.archs import smoke_config
from repro.core import FleetOverlay, Overlay
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Request

SMOKE_TENANTS = ("phi3-mini-3.8b", "minicpm-2b")
# zamba2/deepseek smoke configs trace+compile an order of magnitude slower
# through the overlay path — the benchmark story (churn vs fleet residency)
# needs tenant COUNT, not per-tenant compile weight
FULL_TENANTS = ("phi3-mini-3.8b", "minicpm-2b", "granite-moe-1b-a400m")


def _make_overlay(num_fabrics: int, num_tenants: int):
    if num_fabrics == 1:
        return Overlay(3, 3)
    # low watermarks so replication engages within a benchmark-sized run: a
    # decode accelerator is dispatched every engine tick, so one routing
    # window (scaled to the tenant count — T tenants split each window T
    # ways) gives every decode record ~8 hits, past replicate_after
    return FleetOverlay(num_fabrics, rows=3, cols=3,
                        window=8 * num_tenants,
                        replicate_after=4, drain_below=1, max_replicas=2)


def _run(num_fabrics: int, tenants: tuple[str, ...], *,
         requests_per_tenant: int, prompt_lens: tuple[int, ...],
         max_new: int, batch: int, max_len: int) -> dict:
    overlay = _make_overlay(num_fabrics, len(tenants))
    engines: list[EventLoopEngine] = []
    for t, name in enumerate(tenants):
        cfg = smoke_config(name)
        params = pm.init(model_spec(cfg), jax.random.PRNGKey(t))
        engines.append(EventLoopEngine(params, cfg, batch=batch,
                                       max_len=max_len, overlay=overlay))

    # deterministic prompts (identical for the baseline and the fleet run)
    rng = np.random.default_rng(0)
    reqs: dict[tuple[int, int], Request] = {}
    t0 = time.perf_counter()
    for t, eng in enumerate(engines):
        for rid in range(requests_per_tenant):
            plen = prompt_lens[rid % len(prompt_lens)]
            prompt = rng.integers(1, eng.cfg.vocab_size,
                                  size=(plen,)).tolist()
            req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new)
            reqs[(t, rid)] = req
            eng.submit(req)

    # saturating load: every engine ticks while it has work, round-robin —
    # the multi-tenant interleave the fleet's routing window observes
    ttft: dict[tuple[int, int], float] = {}
    pending = set(range(len(engines)))
    while pending:
        for t in sorted(pending):
            eng = engines[t]
            if not eng.queue and all(r is None for r in eng.slot_req):
                pending.discard(t)
                continue
            eng.step()
            now = time.perf_counter()
            for key, req in reqs.items():
                if key[0] == t and req.out and key not in ttft:
                    ttft[key] = now - t0
    wall = time.perf_counter() - t0

    stats = overlay.describe()
    if num_fabrics == 1:
        downloads = stats["downloads"]
        replicas = replications = 0
    else:
        downloads = sum(m["downloads"] for m in stats["members"])
        replicas = stats["fleet"]["replicas"]
        replications = stats["fleet"]["replications"]
    overlay.close()

    assert all(req.done for req in reqs.values())
    tokens = sum(len(req.out) for req in reqs.values())
    ttfts = sorted(ttft.values())
    p99 = ttfts[min(len(ttfts) - 1, int(round(0.99 * (len(ttfts) - 1))))]
    return {
        "wall": wall,
        "tokens": tokens,
        "tok_s": tokens / wall,
        "ttft_p99_ms": p99 * 1e3,
        "downloads": downloads,
        "replicas": replicas,
        "replications": replications,
        "outs": {key: list(req.out) for key, req in reqs.items()},
    }


def main(smoke: bool = False) -> list[str]:
    tenants = SMOKE_TENANTS if smoke else FULL_TENANTS
    # two prompt-length buckets per tenant: each tenant owns 3 residents
    # (2 prefill chunks + decode) of 3 tiles each (2-tile budget window + the LARGE
    # tile the attention op must own).  3 full-mode tenants want 27 tiles —
    # a single 3x3 fabric (9 tiles) churns every admission wave, while the
    # 4x(3x3) fleet (36 tiles) keeps everything resident WITH free headroom
    # for replicas (replication never reclaims, so it needs real free tiles
    # — a 4th tenant would fill the fleet exactly and starve it)
    knobs = dict(
        requests_per_tenant=4 if smoke else 5,
        prompt_lens=(4, 8),
        max_new=4 if smoke else 6,
        batch=2,
        max_len=32 if smoke else 48,
    )
    base = _run(1, tenants, **knobs)
    fleet = _run(4, tenants, **knobs)

    assert fleet["outs"] == base["outs"], \
        "fleet token streams diverged from single-fabric serving"
    assert fleet["replications"] > 0, "replication never engaged"
    speedup = fleet["tok_s"] / base["tok_s"]

    us_base = base["wall"] / base["tokens"] * 1e6
    us_fleet = fleet["wall"] / fleet["tokens"] * 1e6
    return [
        row("fleet_serving/single_fabric_token", us_base,
            f"tok_s={base['tok_s']:.1f} ttft_p99_ms={base['ttft_p99_ms']:.0f} "
            f"downloads={base['downloads']} tenants={len(tenants)}"),
        row("fleet_serving/fleet4_token", us_fleet,
            f"tok_s={fleet['tok_s']:.1f} "
            f"ttft_p99_ms={fleet['ttft_p99_ms']:.0f} "
            f"downloads={fleet['downloads']} replicas={fleet['replicas']} "
            f"replications={fleet['replications']} "
            f"speedup={speedup:.2f}x bit_identical=True"),
    ]


if __name__ == "__main__":
    from benchmarks.common import bench_cli
    bench_cli(main)

"""Batched serving engine: slot-based continuous batching over a shared
decode step.

The engine owns a fixed pool of ``batch`` sequence slots backed by one
stacked KV cache (so decode is a single batched ``decode_step`` call — the
TPU-efficient shape).  Requests are admitted into free slots, prefilled
one-at-a-time into their slot's cache stripe, then decoded jointly; finished
slots are recycled (continuous batching).  Greedy sampling (argmax) keeps
the engine deterministic for tests; a temperature hook is provided.

Passing ``overlay=`` routes BOTH serving steps through the JIT-assembly
frontend instead of bare ``jax.jit``: prefill and decode become two
*separate accelerators resident on one shared fabric* — each is traced,
lowered onto the operator library (unmapped primitives stay fused XLA
residue), placed into its own tiles under a footprint budget
(``tile_budget``, default a quarter of the fabric so several engines /
prompt-length variants can co-reside), and held in the overlay's bitstream
cache.  This is the paper's multi-accelerator fabric: decode stays hot
(touched every tick) while cold prefill variants are the first reclaimed
under placement pressure.

On an overlay with ``async_downloads=True`` the engine also overlaps the
two downloads: the moment the first prefill starts (the earliest point the
decode-step shapes are known), it *prefetches* the decode accelerator, so
decode's bitstream compiles on the scheduler worker while prefill tokens
stream — by the first decode tick the swap has usually landed and no tick
ever blocks on a compile.

``overlay=`` also accepts a :class:`~repro.core.fleet.FleetOverlay`
(DESIGN.md §8): the same two accelerators are then *placed across member
fabrics* by the fleet's cost score, prompt-length prefill variants spread
over members instead of fighting for one fabric's tiles, and a hot decode
accelerator is replicated and least-loaded-routed — the engine code is
identical because the fleet exposes the single-overlay surface.

Decode is *ragged*: every slot carries its own KV position (``slot_pos``
feeds ``decode_step(positions=...)``), so slots admitted with different
prompt lengths attend against the right cache extent.  Each decode tick
performs ONE fused on-device update (sample + advance positions) and ONE
``jax.device_get`` — no per-slot host round-trips on the hot path.

Admission is FIFO here.  :class:`repro.serving.loop.EventLoopEngine`
(DESIGN.md §9) extends this engine with the serving-under-load path:
priority-ordered admission with SLO-aware shedding (queue-depth bound,
max-queue-delay bound — shed requests are returned/recorded, never
silently dropped) and chunked, power-of-two-bucketed prefill interleaved
with decode ticks.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.fleet import FleetOverlay
from repro.core.overlay import Overlay
from repro.models import model as mdl

# a named span on the profiler's clock: with no profile recording, one
# enter/exit and nothing else
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    out: list[int] = dataclasses.field(default_factory=list)
    decode_steps: int = 0     # batched decode ticks this request has taken
    done: bool = False
    # SLO / event-loop fields (serving/loop.py); inert on the FIFO engine
    priority: int = 0                     # lower value = served first
    submit_time: float | None = None      # engine clock at submit()
    first_token_time: float | None = None
    shed: bool = False
    shed_reason: str | None = None


@jax.jit
def _fused_tick_update(logits, cur_tokens, slot_pos, live):
    """One on-device update for a decode tick: greedy-sample every live
    slot, advance its position, and pack (token, new_position) per slot
    into a single (2, B) int32 array so the host reads the whole tick with
    ONE ``jax.device_get`` instead of 2×B scalar syncs.  Dead slots keep
    their token/position unchanged."""
    live_b = live.astype(bool)
    tok = jnp.where(live_b, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    cur_tokens[:, 0])
    new_pos = slot_pos + live.astype(jnp.int32)
    return tok[:, None], new_pos, jnp.stack([tok, new_pos])


class ServeEngine:
    def __init__(self, params: Any, cfg: ArchConfig, *, batch: int,
                 max_len: int,
                 overlay: "Overlay | FleetOverlay | None" = None,
                 tile_budget: int | None = None):
        self.params = params
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.overlay = overlay
        self.caches = mdl.init_cache(cfg, batch, max_len)
        self.slot_req: list[Request | None] = [None] * batch
        self.slot_pos = jnp.zeros((batch,), jnp.int32)
        self.queue: collections.deque[Request] = collections.deque()
        # ragged decode: every slot decodes at its own KV position, writing
        # its new K/V into the cache it donates (argument 2) in place
        step = lambda p, t, c, pos: mdl.decode_step(p, cfg, t, c,
                                                    positions=pos)
        pf = lambda p, toks, c: mdl.prefill(p, cfg, toks, c)
        if overlay is not None:
            if tile_budget is None:
                tile_budget = max(1, overlay.grid.num_tiles // 4)
            self.tile_budget = tile_budget
            self._decode = overlay.jit(step, strict=False,
                                       name=f"{cfg.name}.decode",
                                       tile_budget=tile_budget,
                                       donate_argnums=(2,))
            self._prefill = overlay.jit(pf, strict=False,
                                        name=f"{cfg.name}.prefill",
                                        tile_budget=tile_budget)
        else:
            self.tile_budget = tile_budget
            self._decode = jax.jit(step, donate_argnums=2)
            self._prefill = jax.jit(pf)
        self.cur_tokens = jnp.zeros((batch, 1), jnp.int32)
        self._live_mask = jnp.zeros((batch,), jnp.int32)
        self._decode_prefetched = False
        # decode ticks whose input cache the step consumed (donated): every
        # tick once the decode executable is downloaded
        self.kv_donated_ticks = 0

    # -- fabric management (relocatable bitstreams, DESIGN.md §6) ------------
    def compact(self) -> int:
        """Close occupancy holes left by departed co-tenants.  Moves are
        relocations — the engine's compiled prefill/decode kernels survive,
        so compaction is safe to call between ticks.  Returns residents
        moved (0 without an overlay)."""
        if self.overlay is None:
            return 0
        return self.overlay.defragment()

    def overlay_failures(self) -> "dict | None":
        """The backing overlay's (or fleet's) failure ledger — retries,
        breaker states, dispatch fallbacks, quarantines, evacuations
        (DESIGN.md §12).  ``None`` without an overlay.  Failures never
        surface as dropped tokens on this engine; they surface HERE (and
        as latency): an admitted request always completes, served by a
        retried download, another replica, or the residue fallback."""
        if self.overlay is None:
            return None
        return self.overlay.failure_ledger()

    def resize(self, tile_budget: int) -> None:
        """Change the engine's per-accelerator footprint cap in place.

        The next prefill/decode dispatch repacks each resident under the
        new budget via relocation (no re-download): grow when co-tenants
        leave, shrink to make room before admitting another engine."""
        if self.overlay is None:
            raise ValueError("resize() needs an overlay-backed engine")
        if tile_budget < 1:
            raise ValueError("tile_budget must be >= 1")
        self.tile_budget = tile_budget
        self._decode.tile_budget = tile_budget
        self._prefill.tile_budget = tile_budget

    def _prefetch_decode(self) -> None:
        """Hide the decode download behind prefill: request it once, as soon
        as traffic arrives (async overlays only — on a synchronous overlay
        the first decode tick pays its download as before).  Decode is the
        per-token serving hot path, so the engine also requests its
        route-constant *specialized* tier eagerly (DESIGN.md §7): the low-
        lane compile lands behind the generic download, and every
        subsequent tick dispatches the zero-hop fused executable."""
        if self._decode_prefetched or self.overlay is None or \
                not getattr(self.overlay, "async_downloads", False):
            return
        self._decode_prefetched = True
        self._decode.prefetch(self.params, self.cur_tokens, self.caches,
                              self.slot_pos)
        self._decode.specialize(self.params, self.cur_tokens, self.caches,
                                self.slot_pos)

    def warmup(self, prompt_lens: "tuple[int, ...]" = ()) -> None:
        """Eagerly download the engine's kernels before traffic arrives:
        the ragged decode step, plus one prefill per prompt length given.
        Shapes only — nothing executes and no engine state changes.

        On a store-backed overlay this is the warm-restart entry point: a
        restarted engine's kernels deserialize off disk here (near-zero
        cost) instead of recompiling on the first request's critical path.
        No-op without an overlay."""
        if self.overlay is None:
            return
        sds = lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                             jnp.result_type(x))
        params_a = jax.tree_util.tree_map(sds, self.params)
        caches_a = jax.tree_util.tree_map(sds, self.caches)
        self._decode.prefetch(params_a,
                              jax.ShapeDtypeStruct((self.batch, 1),
                                                   jnp.int32),
                              caches_a,
                              jax.ShapeDtypeStruct((self.batch,), jnp.int32))
        if prompt_lens:
            c1 = mdl.init_cache(self.cfg, 1, self.max_len)
            c1_a = jax.tree_util.tree_map(sds, c1)
            for n in prompt_lens:
                self._prefill.prefetch(
                    params_a, jax.ShapeDtypeStruct((1, int(n)), jnp.int32),
                    c1_a)

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request for admission.

        Validates the prompt against the engine's KV budget here, at the
        API boundary, instead of failing later inside the prefill cache
        scatter: the prompt must fit in ``max_len`` with at least one
        decode step of headroom (position ``len(prompt)`` writes the first
        decoded token's KV entry)."""
        self._validate_request(req)
        self.queue.append(req)

    def _validate_request(self, req: Request) -> None:
        n = len(req.prompt)
        if n == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if n + 1 > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt of {n} tokens does not fit in "
                f"max_len={self.max_len} with decode headroom (the engine "
                f"needs len(prompt) + 1 <= max_len; got {n + 1})")

    def _admit(self) -> None:
        with _span("engine.admit"):
            for slot in range(self.batch):
                if self.slot_req[slot] is not None or not self.queue:
                    continue
                req = self.queue.popleft()
                self._prefill_slot(slot, req)

    def _prefill_slot(self, slot: int, req: Request) -> None:
        """Prefill a single slot: run the prompt with a batch-1 cache, then
        scatter the stripe into the pooled cache."""
        cfg = self.cfg
        self._prefetch_decode()      # decode bitstream downloads during prefill
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        c1 = mdl.init_cache(cfg, 1, self.max_len)
        logits, c1 = self._prefill(self.params, prompt, c1)
        with _span("engine.install_stripe"):
            self._install_stripe(slot, req, c1, int(jnp.argmax(logits[0])))

    def _install_stripe(self, slot: int, req: Request, c1: dict,
                        tok: int) -> None:
        """Scatter a finished batch-1 prefill cache into the pooled cache
        and mark the slot live for decode."""
        def place(pool, one):
            if one.dtype == jnp.int32:
                # per-layer scalar index leaves — shared across slots, so
                # keep the max; ragged decode never reads them (it uses the
                # per-slot ``slot_pos`` positions instead)
                return jnp.maximum(pool, one.astype(pool.dtype))
            # batch axis differs by cache kind; find the axis of size 1
            for ax in range(one.ndim):
                if one.shape[ax] == 1 and pool.shape[ax] == self.batch:
                    return jax.lax.dynamic_update_slice_in_dim(
                        pool, one.astype(pool.dtype), slot, axis=ax)
            return pool

        self.caches = jax.tree.map(place, self.caches, c1)
        self.slot_pos = self.slot_pos.at[slot].set(len(req.prompt))
        req.out.append(tok)
        self.cur_tokens = self.cur_tokens.at[slot, 0].set(tok)
        self.slot_req[slot] = req
        self._live_mask = self._live_mask.at[slot].set(1)

    # -- decode --------------------------------------------------------------
    def step(self) -> list[Request]:
        """One engine tick: admit, batched-decode, retire. Returns finished."""
        with _span("engine.step"):
            self._admit()
            live = [s for s, r in enumerate(self.slot_req) if r is not None]
            if not live:
                return []
            return self._decode_tick(live)

    def _decode_tick(self, live: list[int]) -> list[Request]:
        """Batched ragged decode over ``live`` slots with ONE host transfer:
        sample/advance happens fused on device and the host reads a single
        packed (token, position) array per tick."""
        probe = jax.tree.leaves(self.caches)[0]
        with _span("engine.decode"):
            logits, self.caches = self._decode(
                self.params, self.cur_tokens, self.caches, self.slot_pos)
        self.kv_donated_ticks += probe.is_deleted()
        with _span("engine.sample"):
            self.cur_tokens, self.slot_pos, packed = _fused_tick_update(
                logits, self.cur_tokens, self.slot_pos, self._live_mask)
        with _span("engine.device_get"):
            toks, poss = jax.device_get(packed)  # the tick's one device->host

        finished: list[Request] = []
        with _span("engine.retire"):
            for slot in live:
                req = self.slot_req[slot]
                req.out.append(int(toks[slot]))
                req.decode_steps += 1
                # retire on decode steps, not len(out): out already holds
                # the prefill-produced token, which is not a decode step —
                # counting it finished requests one decode step early
                if req.decode_steps >= req.max_new_tokens or \
                        int(poss[slot]) + 1 >= self.max_len:
                    req.done = True
                    finished.append(req)
                    self._release_slot(slot)
        return finished

    def _release_slot(self, slot: int) -> None:
        self.slot_req[slot] = None
        self._live_mask = self._live_mask.at[slot].set(0)

    def run_until_drained(self, max_ticks: int = 10_000) -> list[Request]:
        """Tick until every queued and resident request retires.

        Raises :class:`RuntimeError` if ``max_ticks`` is exhausted with
        work still queued or resident — a stuck engine (dead fleet member,
        runaway request) must be visible, not silently dropped."""
        done: list[Request] = []
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.slot_req):
                return done
            done.extend(self.step())
        if self.queue or any(r is not None for r in self.slot_req):
            queued = len(self.queue)
            resident = sum(1 for r in self.slot_req if r is not None)
            raise RuntimeError(
                f"run_until_drained: {max_ticks} ticks exhausted with "
                f"{queued} request(s) still queued and {resident} still "
                f"resident ({len(done)} finished)")
        return done

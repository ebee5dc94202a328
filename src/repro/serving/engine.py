"""The serving engine: slot-based continuous batching, chunked prefill and
SLO-aware admission (DESIGN.md §9).

:class:`EventLoopEngine` owns a fixed pool of ``batch`` sequence slots
backed by one stacked cache, so decode is a single batched ``decode_step``
call — the TPU-efficient shape.  One tick is: admit → one prefill chunk →
one fused decode step → retire.

* **Chunked, bucketed prefill** — a prompt is prefilled into a batch-1
  cache in fixed-size chunks (``chunk`` tokens, a power of two), one chunk
  per tick, interleaved with the batched decode tick, so a long prompt
  never head-of-line-blocks decode for the already-resident slots.  The
  final partial chunk is right-padded to the next power of two (never past
  the cache's end), so the overlay sees a small STABLE set of prefill
  signatures — ``{1, 2, 4, …, chunk}``, bounded by the bucket set, not the
  number of distinct prompt lengths.  The finished stripe is scattered into its slot of the pool by
  ``models.model.install_slot``, which reads each leaf's batch axis from
  the model's cache spec.  Padded positions are causally masked, then
  overwritten by decode before any query can attend to them; SSM layers
  (mamba, zamba2's hybrids) are told each chunk's real length instead, so
  padding never enters their state.

* **Ragged decode** — every slot carries its own KV position (``slot_pos``
  feeds ``decode_step(positions=...)``), so slots admitted with different
  prompt lengths attend against the right cache extent.  Each decode tick
  performs ONE fused on-device update (sample + advance positions) and ONE
  ``jax.device_get`` — no per-slot host round-trips on the hot path.  The
  decode step donates its cache (argument 2) and writes each row in place.

* **SLO-aware admission** — the queue is a priority heap (lower
  ``Request.priority`` first, FIFO within a class).  ``submit`` sheds
  instead of queueing when the queue is full (``max_queue``) or when the
  estimated wait (queue depth × measured tick p50) already exceeds
  ``max_queue_delay``; admission re-checks the delay bound and sheds
  requests that expired while queued.  Shed requests are marked
  (``shed``/``shed_reason``), collected on ``self.shed``, and reported by
  ``metrics()`` — never silently dropped.  Per-tick latency, time-to-first-
  token and queue delay are recorded into fixed-bucket histograms
  (:mod:`repro.serving.metrics`); the tick histogram drives the
  predicted-delay shed.

Passing ``overlay=`` routes both serving steps through the JIT-assembly
frontend instead of bare ``jax.jit``: the prefill chunk and decode become
two *separate accelerators resident on one shared fabric*, each placed
into its own tiles under a footprint budget (``tile_budget``, default a
quarter of the fabric, so several engines can co-reside).  ``overlay=``
also accepts a :class:`~repro.core.fleet.FleetOverlay` (DESIGN.md §8): the
accelerators are then placed across member fabrics by the fleet's cost
score — the engine code is identical because the fleet exposes the
single-overlay surface.  On an overlay with ``async_downloads=True`` the
engine prefetches the decode accelerator (and its specialized tier) the
moment the first prompt is admitted, so decode's download lands while
prefill chunks run.

Greedy sampling (argmax) keeps the engine deterministic.
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.fleet import FleetOverlay
from repro.core.overlay import Overlay
from repro.models import model as mdl
from repro.serving.metrics import Histogram

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


class EventLoopEngine:
    """Event-driven engine: one tick = admit → one prefill chunk → one
    fused decode step.  See the module docstring."""

    def __init__(self, params: Any, cfg: ArchConfig, *, batch: int,
                 max_len: int,
                 overlay: "Overlay | FleetOverlay | None" = None,
                 tile_budget: int | None = None, chunk: int = 64,
                 max_queue: int | None = None,
                 max_queue_delay: float | None = None,
                 clock: Callable[[], float] = time.monotonic):
        if chunk < 1 or chunk & (chunk - 1):
            raise ValueError(f"chunk must be a power of two, got {chunk}")
        self.params = params
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.overlay = overlay
        self.chunk = chunk
        self.max_queue = max_queue
        self.max_queue_delay = max_queue_delay
        self.clock = clock
        self.caches = mdl.init_cache(cfg, batch, max_len)
        self.slot_req: list[Request | None] = [None] * batch
        self.slot_pos = jnp.zeros((batch,), jnp.int32)
        self.cur_tokens = jnp.zeros((batch, 1), jnp.int32)
        self._live_mask = jnp.zeros((batch,), jnp.int32)
        # priority heap of (priority, seq, Request); seq keeps FIFO order
        # within a priority class and makes entries totally ordered
        self.queue: list[tuple[int, int, Request]] = []
        self._seq = 0
        self.shed: list[Request] = []
        self._prefilling: dict[int, dict] = {}   # slot -> {req, c1, off}
        self._pf_rr = 0
        # ragged decode: every slot decodes at its own KV position, writing
        # its new K/V into the cache it donates (argument 2) in place
        step = lambda p, t, c, pos: mdl.decode_step(p, cfg, t, c,
                                                    positions=pos)
        pc = lambda p, toks, c, li: mdl.prefill_chunk(p, cfg, toks, c, li)
        if overlay is not None:
            if tile_budget is None:
                tile_budget = max(1, overlay.grid.num_tiles // 4)
            self._decode = overlay.jit(step, strict=False,
                                       name=f"{cfg.name}.decode",
                                       tile_budget=tile_budget,
                                       donate_argnums=(2,))
            self._prefill_chunk = overlay.jit(
                pc, strict=False, name=f"{cfg.name}.prefill_chunk",
                tile_budget=tile_budget)
        else:
            self._decode = jax.jit(step, donate_argnums=2)
            self._prefill_chunk = jax.jit(pc)
        self.tile_budget = tile_budget
        self._decode_prefetched = False
        self.tick_hist = Histogram()         # whole-tick latency, us
        self.ttft_hist = Histogram()         # submit -> first token, us
        self.queue_delay_hist = Histogram()  # submit -> admission, us
        self.ticks = 0                       # step() calls
        self.prefill_ticks = 0               # of those, ticks that ran a chunk
        # decode ticks whose input K/V and SSM state the step consumed
        # (donated): every tick once the decode executable is downloaded
        self.kv_donated_ticks = 0
        self.ssm_donated_ticks = 0
        # padded prefill positions that the SSM layers held out of their
        # state (``prefill_chunk``'s real length)
        self.ssm_pad_tokens = 0

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
        self._prefill_chunk.tile_budget = tile_budget

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
        """Eagerly download the ragged decode step and the prefill-chunk
        accelerator of every bucket the given prompt lengths will use.
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
        c1_a = jax.eval_shape(lambda: mdl.init_cache(self.cfg, 1,
                                                     self.max_len))
        sizes = {self._chunk_size(int(n) - off, self.max_len - off)
                 for n in prompt_lens for off in range(0, int(n), self.chunk)}
        for size in sorted(sizes):
            self._prefill_chunk.prefetch(
                params_a, jax.ShapeDtypeStruct((1, size), jnp.int32), c1_a,
                jax.ShapeDtypeStruct((), jnp.int32))

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request, or shed it against the SLO bounds.

        Returns ``True`` if queued.  A shed request is returned with
        ``shed=True`` / ``shed_reason`` set and is also appended to
        ``self.shed`` — the caller always learns the outcome.  The prompt
        is validated against the KV budget here, at the API boundary."""
        self._validate_request(req)
        if req.submit_time is None:
            req.submit_time = self.clock()
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return self._shed(req, "queue_full")
        if self.max_queue_delay is not None and self.tick_hist.count:
            est = (len(self.queue) + 1) * \
                self.tick_hist.percentile(0.5) * 1e-6
            if est > self.max_queue_delay:
                return self._shed(req, "predicted_delay")
        heapq.heappush(self.queue, (req.priority, self._seq, req))
        self._seq += 1
        return True

    def _validate_request(self, req: Request) -> None:
        """The prompt must fit in ``max_len`` with at least one decode step
        of headroom (position ``len(prompt)`` writes the first decoded
        token's KV entry)."""
        n = len(req.prompt)
        if n == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if n + 1 > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt of {n} tokens does not fit in "
                f"max_len={self.max_len} with decode headroom (the engine "
                f"needs len(prompt) + 1 <= max_len; got {n + 1})")

    def _shed(self, req: Request, reason: str) -> bool:
        req.shed = True
        req.shed_reason = reason
        self.shed.append(req)
        return False

    def _pop_admissible(self) -> Request | None:
        """Pop the next request, shedding any that outlived the delay SLO
        while queued (better to shed at admission than to burn prefill on a
        request whose client has already timed out)."""
        while self.queue:
            _, _, req = heapq.heappop(self.queue)
            delay = (self.clock() - req.submit_time
                     if req.submit_time is not None else 0.0)
            if self.max_queue_delay is not None and \
                    delay > self.max_queue_delay:
                self._shed(req, "queue_delay")
                continue
            self.queue_delay_hist.record(delay * 1e6)
            return req
        return None

    def _admit(self) -> None:
        with _span("engine.admit"):
            for slot in range(self.batch):
                if self.slot_req[slot] is not None:
                    continue
                req = self._pop_admissible()
                if req is None:
                    return
                self._begin_prefill(slot, req)

    # -- chunked prefill -----------------------------------------------------
    def _begin_prefill(self, slot: int, req: Request) -> None:
        self._prefetch_decode()
        self._prefilling[slot] = {
            "req": req,
            "c1": mdl.init_cache(self.cfg, 1, self.max_len),
            "off": 0,
        }
        # resident (occupies the slot) but not yet live for decode:
        # _live_mask stays 0 until the stripe is installed
        self.slot_req[slot] = req

    def _chunk_size(self, remaining: int, room: int) -> int:
        """Bucket the next chunk: full ``chunk`` while the prompt lasts,
        then the final remainder padded up to the next power of two, but
        no longer than the ``room`` left in the cache (a full chunk always
        fits: ``submit`` leaves room past the prompt): a write past its
        end would be clamped back onto the prompt's own positions."""
        if remaining >= self.chunk:
            return self.chunk
        return min(1 << (remaining - 1).bit_length(), room)

    def _prefill_tick(self) -> bool:
        """Advance ONE in-prefill slot by one chunk (round-robin), so no
        single long prompt monopolizes the tick budget.  Returns whether a
        chunk ran."""
        if not self._prefilling:
            return False
        with _span("engine.prefill_chunk"):
            slots = sorted(self._prefilling)
            slot = slots[self._pf_rr % len(slots)]
            self._pf_rr += 1
            st = self._prefilling[slot]
            req, off = st["req"], st["off"]
            n = len(req.prompt)
            size = self._chunk_size(n - off, self.max_len - off)
            toks = req.prompt[off:off + size]
            last = len(toks) - 1          # last REAL token within this chunk
            if self.cfg.ssm_layers:
                self.ssm_pad_tokens += size - len(toks)
            toks = toks + [0] * (size - len(toks))
            logits, st["c1"] = self._prefill_chunk(
                self.params, jnp.asarray(toks, jnp.int32)[None], st["c1"],
                jnp.asarray(last, jnp.int32))
        st["off"] = off + (last + 1)
        if st["off"] >= n:
            del self._prefilling[slot]
            with _span("engine.install_stripe"):
                self._install_stripe(slot, req, st["c1"],
                                     int(jnp.argmax(logits[0])))
            req.first_token_time = self.clock()
            if req.submit_time is not None:
                self.ttft_hist.record(
                    (req.first_token_time - req.submit_time) * 1e6)
        return True

    def _install_stripe(self, slot: int, req: Request, c1: dict,
                        tok: int) -> None:
        """Scatter a finished batch-1 prefill cache into the pooled cache
        and mark the slot live for decode."""
        self.caches = mdl.install_slot(self.cfg, self.caches, c1, slot)
        self.slot_pos = self.slot_pos.at[slot].set(len(req.prompt))
        req.out.append(tok)
        self.cur_tokens = self.cur_tokens.at[slot, 0].set(tok)
        self.slot_req[slot] = req
        self._live_mask = self._live_mask.at[slot].set(1)

    # -- the event loop tick -------------------------------------------------
    def step(self) -> list[Request]:
        """One tick: admit, one prefill chunk, one fused decode, retire.
        Returns the requests that finished."""
        with _span("engine.step"):
            t0 = time.perf_counter()
            self._admit()
            self.ticks += 1
            if self._prefill_tick():
                self.prefill_ticks += 1
            decoding = [s for s, r in enumerate(self.slot_req)
                        if r is not None and s not in self._prefilling]
            finished = self._decode_tick(decoding) if decoding else []
            self.tick_hist.record((time.perf_counter() - t0) * 1e6)
            return finished

    def _decode_tick(self, live: list[int]) -> list[Request]:
        """Batched ragged decode over ``live`` slots with ONE host transfer:
        sample/advance happens fused on device and the host reads a single
        packed (token, position) array per tick."""
        kv, ssm = mdl.donation_probes(self.caches)
        with _span("engine.decode"):
            logits, self.caches = self._decode(
                self.params, self.cur_tokens, self.caches, self.slot_pos)
        if kv is not None:
            self.kv_donated_ticks += kv.is_deleted()
        if ssm is not None:
            self.ssm_donated_ticks += ssm.is_deleted()
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

    # -- observability -------------------------------------------------------
    def metrics(self) -> dict:
        """JSON-serializable engine metrics (histograms + shed ledger)."""
        return {
            "tick_us": self.tick_hist.summary(),
            "ttft_us": self.ttft_hist.summary(),
            "queue_delay_us": self.queue_delay_hist.summary(),
            "shed": len(self.shed),
            "shed_reasons": {r: sum(1 for q in self.shed
                                    if q.shed_reason == r)
                             for r in {q.shed_reason for q in self.shed}},
            "queued": len(self.queue),
            "ticks": self.ticks,
            "prefill_ticks": self.prefill_ticks,
            "kv_donated_ticks": self.kv_donated_ticks,
            "ssm_donated_ticks": self.ssm_donated_ticks,
            "ssm_pad_tokens": self.ssm_pad_tokens,
            "failures": self.overlay_failures(),
        }

"""Overlay facade — the dynamic overlay the paper's runtime exposes.

The primary programming model is the *trace-based frontend* (the paper's
actual pitch: ordinary source code, no hardware programming model):

    overlay = Overlay(rows=3, cols=3)              # build the fabric

    @overlay.jit                                   # or: acc = overlay.jit(fn)
    def rms(x, w):
        return jnp.sqrt(jnp.sum((x * w) ** 2) * (1.0 / x.size))

    y = rms(sig, win)                              # trace -> place -> assemble
                                                   # -> cached bitstream -> run

``overlay.jit`` captures the function via ``jax.make_jaxpr``, lowers supported
primitives onto the operator library (``patterns.register_op`` dispatch),
builds a :class:`Graph` as IR, and feeds it through placement/ISA/assembly.
Unmapped primitives stay as fused XLA residue unless ``strict=True``.

Also provided, mirroring the paper's runtime controls:

* ``Overlay.aot(fn, *avals)``   — ahead-of-time bitstream-cache population
  (pay the "PR download" before traffic arrives),
* ``Overlay(async_downloads=True)`` — the asynchronous PR-download pipeline
  (DESIGN.md §5): misses are served immediately by a fallback while the
  bitstream compiles on a background scheduler and swaps in atomically;
  ``jitted.prefetch(*args)`` starts downloads ahead of demand,
* ``Overlay.reconfigure()``     — flush the fabric: placements + bitstreams
  (``relocate=True`` moves residents instead — kernels survive),
* ``Overlay.evict(name)``       — free one accelerator's PR regions,
* ``Overlay.defragment()`` / ``Overlay.relocate(graph, placement)`` — move
  residents between placements *without* re-downloading: compiled kernel
  artifacts are placement-free (DESIGN.md §6), only route programs re-emit,
* tiered route specialization (DESIGN.md §7) — stable/contiguous residents
  are background-compiled into a *route-constant* specialized executable
  (hop counts baked in; zero-hop edges vanish, XLA fully fuses the body)
  on the scheduler's low-priority lane and atomically swapped onto the
  dispatch fast path; any relocation instantly despecializes back to the
  always-correct generic kernel.  ``jitted.specialize(*args)`` requests the
  tier eagerly.  Dispatch itself is lock-light: per-entry immutable
  dispatch records revalidated by a single generation read — no
  ``Overlay._lock`` acquisition on a resident hit,
* ``Overlay.assemble(graph)``   — the low-level IR path (hand-built Graphs),
  still public, idempotent and cached: re-assembling the same graph signature
  is a cache *hit* (the paper's "only incurred at startup").

Module-level conveniences ``jit``/``jit_assemble`` run against a process-wide
default 3x3 overlay for scripts that don't manage a fabric explicitly.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
import warnings
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core import interpreter as interp
from repro.core import trace as trace_lib
from repro.core.cache import BitstreamCache
from repro.core.fabric import Fabric, FabricError, ResidentAccelerator
from repro.core.faults import FaultError, FaultPlan
from repro.core.graph import Graph
from repro.core.isa import Program, compile_graph
from repro.core.placement import (Coord, Placement, PlacementError,
                                  PlacementPolicy, TileGrid,
                                  candidate_placements, check_assignment,
                                  place, score_placement)
from repro.core.scheduler import DownloadHandle, DownloadScheduler
from repro.core.store import BitstreamStore
from repro.serving.metrics import Histogram

# a persistently failing background compile stops being retried after this
# many attempts; the entry keeps serving from its fallback
_MAX_DOWNLOAD_FAILURES = 3

# a named span on the profiler's clock: with no profile recording, one
# enter/exit and nothing else
_span = jax.profiler.TraceAnnotation

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class OverlayStats:
    assemblies: int = 0
    reconfigurations: int = 0   # placements changed between assemblies
    traces: int = 0             # frontend captures (jit/aot signatures)
    trace_seconds: float = 0.0  # total trace+lowering time (frontend cost)
    downloads: int = 0          # accelerators placed + admitted to the fabric
    evictions: int = 0          # residents released (explicit or reclaimed)
    reclaims: int = 0           # LRU evictions forced by placement pressure
    defrags: int = 0            # defragmentation passes that moved residents
    relocations: int = 0        # residents moved WITHOUT re-downloading
    defrag_failures: int = 0    # defrag passes aborted by an unplaceable survivor
    prefetches: int = 0         # downloads begun on a hint, not a demand miss
    prefetch_hits: int = 0      # demand requests satisfied by a prior prefetch
    fallback_calls: int = 0     # calls served by a fallback mid-download
    stale_downloads: int = 0    # background results dropped (generation flushed)
    download_failures: int = 0  # download/compile attempts that raised
    download_retries: int = 0   # re-attempts after a backoff window elapsed
    breaker_opens: int = 0      # entries pinned to fallback (failure cap hit)
    breaker_probes: int = 0     # probe downloads while a breaker was open
    breaker_closes: int = 0     # breakers re-closed by a successful probe
    dispatch_failures: int = 0  # resident dispatches that raised
    dispatch_fallbacks: int = 0 # failed dispatches served by the residue
    resident_losses: int = 0    # residents lost at dispatch time (injected)


@dataclasses.dataclass(frozen=True)
class _DispatchRecord:
    """Immutable snapshot the lock-light dispatch fast path runs on.

    Built whenever an entry's executable (re)binds — assembly, background
    swap, relocation rebind, specialize commit — and validated per call by
    a SINGLE generation read against the resident it points at: no fabric
    rid lookup, no ``Overlay._lock``.  Any residency change (evict, reclaim,
    relocate, reconfigure) bumps/kills the generation, so a stale record
    fails closed into the slow path, which rebuilds it."""

    fn: Callable[..., Any]               # ready-to-call bound executable
    res: "ResidentAccelerator"           # the resident it belongs to
    generation: int                      # validity = res.live && gen match
    tier: str                            # "generic" | "specialized"


@dataclasses.dataclass
class _JitEntry:
    """One (signature, static-args) instantiation of a jitted function."""

    lowered: trace_lib.Lowered
    acc: interp.AssembledAccelerator | None   # None: traced but not assembled
    trace_seconds: float            # capture + jaxpr->Graph lowering
    assemble_seconds: float = 0.0   # placement + ISA compile + cache insert
    closed: Callable[..., Any] | None = None  # traced closure (eager fallback)
    pending: DownloadHandle | None = None     # in-flight background download
    jit_kwargs: dict[str, Any] | None = None  # last demand's kwargs (donation)
    download_failures: int = 0                # consecutive failed compiles
    record: _DispatchRecord | None = None     # lock-light hot-path snapshot
    # deterministic retry/backoff clock (DESIGN.md §12): `calls` ticks once
    # per slow-path call and every retry decision keys on it — never on
    # wall-clock — so a failure schedule replays exactly.  The breaker pins
    # a repeatedly-failing entry to its fallback; while "open" only probe
    # downloads (every `probe_interval` calls, doubling per failed probe)
    # are attempted, and one success re-closes it.
    calls: int = 0                            # slow-path call counter
    retry_at: int = 0                         # earliest call allowed to retry
    breaker: str = "closed"                   # "closed" | "open"
    breaker_opened_at: int = 0                # call count at open/last probe
    probe_interval: int = 0                   # calls between probes when open


@dataclasses.dataclass
class _PendingDownload:
    """Frozen snapshot handed to the background compile: everything the
    commit needs to publish the bitstream — or to recognize it went stale."""

    rid: str
    generation: int
    key: str
    base: interp.AssembledAccelerator   # un-jitted; placed at `generation`
    avals: tuple
    jit_kwargs: dict[str, Any] | None = None   # the key includes these, so
                                               # the executable must honor them


@dataclasses.dataclass
class _PendingSpecialize:
    """Frozen snapshot for a background route-constant compile.  Unlike a
    download (``same_residency`` guard — kernels are placement-free), a
    specialize commit validates the EXACT generation: the baked hop
    constants describe one placement, so any relocation in flight makes the
    result garbage and it must be dropped."""

    rid: str
    generation: int                    # exact — relocation invalidates
    key: str                           # generic kernel key being specialized
    spec_key: str                      # key + baked hop vector
    graph: Graph
    hops: tuple                        # Python-int hop vector (trace consts)
    avals: tuple
    jit_kwargs: dict[str, Any] | None = None


class JitAssembled:
    """Callable wrapper returned by :meth:`Overlay.jit`.

    Per input signature (flat shapes/dtypes + static argument values) the
    wrapper traces once, assembles once, then dispatches straight to the
    cached accelerator.  Pytree arguments/results are supported; the graph
    sees one input per flat leaf.
    """

    def __init__(self, overlay: "Overlay", fn: Callable[..., Any], *,
                 strict: bool = False, name: str | None = None,
                 fixed: dict[int, Coord] | None = None,
                 static_argnums: tuple[int, ...] = (),
                 donate_argnums: tuple[int, ...] = (),
                 tile_budget: int | None = None) -> None:
        self.overlay = overlay
        self.fn = fn
        self.strict = strict
        self.name = name or getattr(fn, "__name__", None) or "jit"
        self.fixed = fixed
        self.static_argnums = tuple(static_argnums)
        self.donate_argnums = tuple(donate_argnums)
        self.tile_budget = tile_budget
        self._entries: dict[str, _JitEntry] = {}
        self.__name__ = self.name
        self.__doc__ = getattr(fn, "__doc__", None)
        overlay._register(self)

    # -- signature handling ---------------------------------------------------
    @staticmethod
    def _sig_key(dyn: tuple, static_repr: str):
        """The entry-table key: flat abstract signature + pytree structure +
        static-argument values.  One definition — ``__call__``/``lower``/
        ``prefetch`` must never disagree on it.  A hashable tuple, NOT a
        repr string: this runs on the dispatch fast path, where repr() of
        shapes/dtypes would cost more than the dispatch itself."""
        leaves, treedef = jax.tree_util.tree_flatten(dyn)
        return (tuple(cache_lib.leaf_signature(a) for a in leaves),
                treedef, static_repr)

    def _split(self, args: tuple):
        """Split positional args into (dynamic args, closed fn, static repr)."""
        if not self.static_argnums:
            return args, self.fn, ""
        static = {i: args[i] for i in self.static_argnums if i < len(args)}
        dyn = tuple(a for i, a in enumerate(args) if i not in static)

        def closed(*dyn_args, _static=static, _n=len(args)):
            it = iter(dyn_args)
            full = [_static[i] if i in _static else next(it) for i in range(_n)]
            return self.fn(*full)

        closed.__name__ = self.name
        return dyn, closed, repr(sorted(static.items()))

    def _donate_leaf_indices(self, args: tuple) -> tuple[int, ...]:
        """Expand user-level donate_argnums to flat-leaf indices."""
        if not self.donate_argnums:
            return ()
        out, offset = [], 0
        for i, a in enumerate(args):
            if i in self.static_argnums:
                continue
            n = len(jax.tree.leaves(a))
            if i in self.donate_argnums:
                out.extend(range(offset, offset + n))
            offset += n
        return tuple(out)

    def _traced(self, key: str, closed: Callable[..., Any],
                dyn: tuple) -> _JitEntry:
        """The (possibly assembly-less) entry for a signature, tracing at
        most once: ``lower()`` and ``__call__`` share the memo."""
        entry = self._entries.get(key)
        if entry is None:
            t0 = time.perf_counter()
            lowered = trace_lib.trace_to_graph(closed, *dyn, name=self.name,
                                               strict=self.strict)
            dt = time.perf_counter() - t0
            self.overlay.stats.traces += 1
            self.overlay.stats.trace_seconds += dt
            entry = _JitEntry(lowered=lowered, acc=None, trace_seconds=dt,
                              closed=closed)
            self._entries[key] = entry
        return entry

    def _jit_kwargs(self, args: tuple) -> dict[str, Any] | None:
        donate = self._donate_leaf_indices(args)
        return {"donate_argnums": donate} if donate else None

    def _swap(self, entry: _JitEntry, acc, t0: float,
              handle: DownloadHandle | None) -> None:
        """Background-download completion: atomically publish the assembled
        accelerator (``acc is None`` = download cancelled, stale, or
        failed — clear the pending marker so the next call re-requests)."""
        if handle is not None and entry.pending is not None \
                and entry.pending is not handle:
            # a superseded job's late delivery (e.g. the pre-reconfigure
            # download, flushed and replaced): the live download owns the
            # entry — don't clobber its pending marker
            return
        if acc is not None:
            entry.acc = acc
            # the handle's measured worker time is the download cost; the
            # submit->delivery wall clock would also bill queue wait
            entry.assemble_seconds = (handle.seconds if handle is not None
                                      and handle.seconds > 0.0
                                      else time.perf_counter() - t0)
            self._note_download_success(entry)
            self.overlay._publish_record(entry)
        elif handle is not None and handle.error is not None:
            self._note_download_failure(entry, handle.error)
        entry.pending = None

    # -- retry / circuit breaker (DESIGN.md §12) ------------------------------
    def _download_allowed(self, entry: _JitEntry) -> bool:
        """Whether an attempt may start NOW, per the entry's deterministic
        retry clock.  Closed breaker: allowed once the exponential-backoff
        window (in slow-path calls, not seconds) has elapsed.  Open
        breaker: only a probe every ``probe_interval`` calls."""
        ov = self.overlay
        if entry.breaker == "open":
            if entry.calls - entry.breaker_opened_at < entry.probe_interval:
                return False
            entry.breaker_opened_at = entry.calls
            ov.stats.breaker_probes += 1
            return True
        if entry.download_failures and entry.calls < entry.retry_at:
            return False
        if entry.download_failures:
            ov.stats.download_retries += 1
        return True

    def _note_download_failure(self, entry: _JitEntry,
                               error: BaseException | Exception) -> None:
        """Book one failed download attempt: schedule the deterministic
        backoff, open the breaker at the threshold, double the probe window
        on a failed probe.  The fallback keeps serving throughout."""
        ov = self.overlay
        entry.download_failures += 1
        ov.stats.download_failures += 1
        if entry.breaker == "open":
            # failed probe: re-arm with a doubled (capped) window
            entry.probe_interval = min(256, max(1, entry.probe_interval * 2))
            entry.breaker_opened_at = entry.calls
            return
        if entry.download_failures >= ov.breaker_threshold:
            entry.breaker = "open"
            entry.breaker_opened_at = entry.calls
            entry.probe_interval = ov.breaker_probe_after
            ov.stats.breaker_opens += 1
            warnings.warn(
                f"PR downloads for {self.name!r} failed "
                f"{entry.download_failures} times ({error!r}); breaker "
                f"open — pinned to the fallback, probing every "
                f"{entry.probe_interval} calls.",
                RuntimeWarning, stacklevel=2)
        else:
            entry.retry_at = entry.calls + ov.retry_backoff * (
                2 ** (entry.download_failures - 1))
            if entry.download_failures == 1:
                warnings.warn(
                    f"background PR download for {self.name!r} failed "
                    f"({error!r}); serving from the fallback and retrying "
                    f"with backoff.",
                    RuntimeWarning, stacklevel=2)

    def _note_download_success(self, entry: _JitEntry) -> None:
        ov = self.overlay
        if entry.breaker == "open":
            entry.breaker = "closed"
            ov.stats.breaker_closes += 1
        entry.download_failures = 0
        entry.retry_at = 0

    def _submit(self, entry: _JitEntry, *, kind: str = "demand",
                reclaim: bool = True, low: bool = False
                ) -> DownloadHandle | None:
        """Request this entry's download; deterministic backoff + circuit
        breaker on compile failure (the fallback keeps serving either way).
        After ``overlay.close()`` no new downloads start but calls keep
        being served."""
        if self.overlay.scheduler.closed:
            return None
        if not self._download_allowed(entry):
            return None
        t0 = time.perf_counter()
        # clear first: an immediate completion (cached bitstream) delivers
        # on_done before submit_download returns, and _swap must not mistake
        # the previous outage's done handle for a live download
        entry.pending = None
        handle = self.overlay.submit_download(
            entry.lowered.graph, fixed=self.fixed,
            jit_kwargs=entry.jit_kwargs, tile_budget=self.tile_budget,
            kind=kind, reclaim=reclaim, low=low,
            on_done=lambda acc2, h: self._swap(entry, acc2, t0, h))
        entry.pending = handle
        return handle

    def _entry(self, args: tuple, *, aot: bool = False,
               _presplit=None) -> _JitEntry:
        dyn, closed, static_repr = _presplit or self._split(args)
        entry = self._traced(self._sig_key(dyn, static_repr), closed, dyn)
        acc = entry.acc
        if acc is not None and self.overlay.resident_current(acc):
            if not self.overlay.repack(acc.resident_id, self.tile_budget):
                # hot path: still resident in the fabric — just bump recency
                self.overlay.fabric.touch(acc.resident_id)
                self.overlay._note_demand(acc.resident_id)
                return entry
            # the budget changed and the resident relocated: fall through so
            # the (cheap) re-assembly below rebinds the entry to its routes
        # first assembly for this signature, or the accelerator was evicted
        # from the fabric since (LRU reclaim / reconfigure): re-place and
        # re-download
        if aot or not self.overlay.async_downloads:
            if not self._download_allowed(entry):
                return entry               # backing off / breaker open
            t0 = time.perf_counter()
            entry.jit_kwargs = self._jit_kwargs(args)
            try:
                entry.acc = self.overlay.assemble(entry.lowered.graph,
                                                  fixed=self.fixed,
                                                  jit_kwargs=entry.jit_kwargs,
                                                  aot=aot,
                                                  tile_budget=self.tile_budget)
            except (PlacementError, FabricError):
                raise                      # structural — must propagate
            except Exception as exc:
                from repro.analysis.check import InvariantError
                if isinstance(exc, InvariantError):
                    raise                  # sanitizer verdict: a bug, not
                                           # an outage — never degrade it
                # compile/download failure on the sync path (injected or
                # real): degrade to the eager residue and retry later on
                # the deterministic backoff clock
                self._note_download_failure(entry, exc)
                entry.pending = None
                return entry
            entry.assemble_seconds = time.perf_counter() - t0
            entry.pending = None
            self._note_download_success(entry)
            self.overlay._publish_record(entry)
            return entry
        # asynchronous pipeline: serve from the fallback.  The download
        # itself is requested by ``__call__`` *after* the response is
        # produced (and by :meth:`prefetch`), so a request never contends
        # with its own download for the CPU/GIL.
        return entry

    def _ensure_download(self, entry: _JitEntry, args: tuple) -> None:
        """Request the background download once per outage; the scheduler
        coalesces repeats by residency key."""
        if not self.overlay.async_downloads:
            # synchronous overlays retry eagerly through _entry on a later
            # call — they must never start background work
            return
        if entry.pending is not None and not entry.pending.done():
            # demanded while the download is in flight: keep the resident's
            # recency honest (handle.key IS the rid) — a hot accelerator
            # must not look like the LRU victim just because its bitstream
            # hasn't landed yet
            self.overlay.fabric.touch(entry.pending.key)
            return
        entry.jit_kwargs = self._jit_kwargs(args)
        self._submit(entry)

    # -- public surface -------------------------------------------------------
    def lower(self, *args) -> trace_lib.Lowered:
        """The lowered IR for this signature — traced at most once and
        memoized into the entry table (a later ``__call__`` assembles the
        already-traced graph instead of re-tracing)."""
        dyn, closed, static_repr = self._split(args)
        return self._traced(self._sig_key(dyn, static_repr),
                            closed, dyn).lowered

    def accelerator(self, *args) -> interp.AssembledAccelerator:
        """The assembled accelerator for this signature (traces if needed)."""
        return self._entry(args).acc

    def timings(self, *args) -> dict[str, float]:
        """Frontend vs backend split for this signature (pr_overhead bench)."""
        e = self._entry(args)
        return {"trace_seconds": e.trace_seconds,
                "assemble_seconds": e.assemble_seconds}

    def prefetch(self, *args, low: bool = False,
                 reclaim: bool = True) -> DownloadHandle | None:
        """Hint: download this signature's bitstream before traffic needs it.

        ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct`` pytrees.
        On an asynchronous overlay the place+compile runs on the scheduler's
        worker (returns the in-flight :class:`DownloadHandle`); on a
        synchronous overlay the download is paid eagerly right here (AOT
        population).  Already-resident signatures are a no-op.

        ``low=True`` routes the background compile to the scheduler's LOW
        lane (background optimization — fleet replication uses this so a
        replica download never delays a demand download or relocation);
        ``reclaim=False`` raises :class:`PlacementError` under placement
        pressure instead of displacing live residents (ignored on a
        synchronous overlay, where the eager path reclaims as assemble does).
        """
        presplit = self._split(args)
        dyn, closed, static_repr = presplit
        entry = self._traced(self._sig_key(dyn, static_repr), closed, dyn)
        ov = self.overlay
        acc = entry.acc
        if acc is not None and ov.resident_current(acc):
            return None                              # already downloaded
        if not ov.async_downloads:
            self._entry(args, aot=True, _presplit=presplit)
            ov.stats.prefetches += 1
            if entry.acc is not None:     # eager assemble may have degraded
                ov._prefetched.add(entry.acc.resident_id)
            return None
        if entry.pending is not None and not entry.pending.done():
            return entry.pending                     # already on its way
        entry.jit_kwargs = self._jit_kwargs(args)
        return self._submit(entry, kind="prefetch", reclaim=reclaim, low=low)

    def _prefetch_known(self) -> int:
        """Re-request downloads for every signature this wrapper has seen —
        the post-``reconfigure()`` warm-up (the flush dropped all residents,
        but the traced graphs are still in the entry table)."""
        ov = self.overlay
        n = 0
        for entry in list(self._entries.values()):
            acc = entry.acc
            if acc is not None and ov.resident_current(acc):
                continue
            if not ov.fabric.free():
                break            # fabric full: warm-up must not reclaim-
            try:                 # cascade through just-prefetched residents
                submitted = self._submit(entry, kind="prefetch",
                                         reclaim=False)
            except PlacementError:
                break            # no room for this one ⇒ stop warming
            if submitted is not None:
                n += 1
        return n

    def specialize(self, *args) -> DownloadHandle | None:
        """Request the route-constant *specialized* tier for this signature
        (DESIGN.md §7).  ``args`` may be concrete arrays or
        ``jax.ShapeDtypeStruct`` pytrees.

        On an asynchronous overlay the specialize compile is queued on the
        scheduler's LOW lane (it never delays a download or relocation) and
        the dispatch record swaps to the specialized executable when it
        commits; on a synchronous overlay the compile is paid eagerly right
        here.  Admits/downloads the generic tier first if needed.  A later
        relocation instantly despecializes back to the generic kernel.
        Returns the in-flight handle, or None (done inline / not needed).
        """
        ov = self.overlay
        presplit = self._split(args)
        dyn, closed, static_repr = presplit
        entry = self._traced(self._sig_key(dyn, static_repr), closed, dyn)
        acc = entry.acc
        if acc is None or not ov.resident_current(acc):
            if ov.async_downloads:
                self.prefetch(*args)       # admit + download generic first
            else:
                self._entry(args, aot=True, _presplit=presplit)
        if entry.jit_kwargs is None:
            entry.jit_kwargs = self._jit_kwargs(args)
        graph = entry.lowered.graph
        avals = tuple(graph.toposorted()[i].aval for i in graph.input_ids)
        res = ov.fabric.get(ov._resident_key(graph, avals, self.fixed))
        if res is None or res.tier != "generic" or res.spec_pending:
            return None
        if ov.async_downloads and not ov.scheduler.closed:
            with ov._lock:
                return ov._submit_specialize_locked(entry, res)
        ov._specialize_now(entry, res)
        return None

    def __call__(self, *args):
        with _span("overlay.dispatch"):
            presplit = self._split(args)
            entry = self._entries.get(self._sig_key(presplit[0],
                                                    presplit[2]))
            if entry is not None:
                rec = entry.record
                if rec is not None:
                    res = rec.res
                    # the ENTIRE hot-path validation: liveness + one
                    # generation read (+ the wrapper's budget, when capped).
                    # Anything that could invalidate the executable — evict,
                    # reclaim, flush, relocation, budget repack — changes one
                    # of these, and the stale record fails closed into the
                    # slow path below.
                    if res.live and res.generation == rec.generation and \
                            (self.tile_budget is None
                             or res.tile_budget == self.tile_budget):
                        return self._dispatch_fast(args, entry, rec, res,
                                                   presplit)
            return self._call_slow(args, presplit)

    def _dispatch_fast(self, args, entry: _JitEntry, rec: _DispatchRecord,
                       res: ResidentAccelerator, presplit):
        """Resident-hit dispatch without the overlay lock: recency bump,
        tier bookkeeping, call.  Also the specialization trigger point —
        a contiguous (zero-hop) or dispatch-stable generic resident queues
        its route-constant compile on the scheduler's low lane."""
        ov = self.overlay
        plan = ov.faults
        if plan is not None and plan.fires("resident_loss", res.rid):
            # injected PR-region loss: the resident silently vanishes and
            # this call degrades to the slow path (fallback + re-download)
            ov._lose_resident(res.rid)
            return self._call_slow(args, presplit)
        ov.fabric.touch_resident(res)
        if ov._prefetched:
            ov._note_demand(res.rid)
        if rec.tier == "specialized":
            ov.cache.spec_stats.specialized_hits += 1
        elif ov._auto_specialize and res.tier == "generic" \
                and not res.spec_pending \
                and res.spec_failures < _MAX_DOWNLOAD_FAILURES:
            # the failure-cap read keeps a permanently-failing resident from
            # re-acquiring the overlay lock on every dispatch forever
            res.stable_dispatches += 1
            if res.zero_hop or res.stable_dispatches >= ov.specialize_after:
                ov._request_specialize(entry, res)
        flat = jax.tree.leaves(presplit[0])
        t0 = time.perf_counter()
        try:
            if plan is not None and plan.fires("dispatch", res.rid):
                raise FaultError(
                    f"injected dispatch failure on {res.rid!r}")
            with _span("overlay.execute"):
                out = rec.fn(*flat)
        except (PlacementError, FabricError):
            raise
        except Exception as exc:
            return self._dispatch_failed(entry, res, exc, args, presplit)
        us = (time.perf_counter() - t0) * 1e6
        res.dispatch_hist.record(us)
        ov.dispatch_hist.record(us)
        n_out = len(entry.lowered.graph.output_ids)
        leaves = list(out) if n_out > 1 else [out]
        return jax.tree_util.tree_unflatten(entry.lowered.out_tree, leaves)

    def _dispatch_failed(self, entry: _JitEntry, res: ResidentAccelerator,
                         exc: BaseException, args, presplit):
        """A resident dispatch raised: evict the suspect resident (its tile
        state is unknown), serve THIS request from the eager residue, and
        re-request the download — an admitted call never surfaces the
        failure, it shows up as latency and failure-ledger counters."""
        ov = self.overlay
        ov.stats.dispatch_failures += 1
        logger.warning("dispatch on %r (%s) failed: %r — serving the "
                       "residue fallback", res.rid, self.name, exc)
        with ov._lock:
            res.dispatch_failures += 1
            if ov.fabric.get(res.rid) is res:
                ov._evict_resident(res.rid)
            entry.record = None
        ov.stats.dispatch_fallbacks += 1
        ov.stats.fallback_calls += 1
        with _span("overlay.fallback"):
            out = entry.closed(*presplit[0])
        self._ensure_download(entry, args)
        return out

    def _call_slow(self, args, presplit):
        with _span("overlay.slow_path"):
            entry = self._entry(args, _presplit=presplit)
            entry.calls += 1               # the deterministic retry clock
            ov = self.overlay
            acc = entry.acc
            if acc is None:
                # nothing assembled yet: serve the request from the traced
                # residue function, executed *eagerly* (the paper's "software
                # fallback while the bitstream downloads").  Eager dispatch
                # needs no whole-graph compile, so time-to-first-result never
                # waits on XLA; the download is requested after the response is
                # computed and the accelerator swaps in underneath.
                ov.stats.fallback_calls += 1
                with _span("overlay.fallback"):
                    out = entry.closed(*presplit[0])
                self._ensure_download(entry, args)
                return out
            if not ov.resident_current(acc):
                # mid-re-download: the prior-generation executable lost its PR
                # regions but is still a correct pure function — keep serving
                # it while the fabric re-downloads this signature
                ov.stats.fallback_calls += 1
                flat = jax.tree.leaves(presplit[0])
                with _span("overlay.execute"):
                    out = acc.fn(*flat)
                self._ensure_download(entry, args)
            else:
                # a resident hit that missed the fast path (first dispatch, or
                # a just-invalidated record): republish, then dispatch through
                # the record so this call already serves the best live tier
                ov._publish_record(entry)
                rec = entry.record
                fn = acc.fn if rec is None else rec.fn
                if rec is not None and rec.tier == "specialized":
                    ov.cache.spec_stats.specialized_hits += 1
                flat = jax.tree.leaves(presplit[0])
                t0 = time.perf_counter()
                try:
                    with _span("overlay.execute"):
                        out = fn(*flat)
                except (PlacementError, FabricError):
                    raise
                except Exception as exc:
                    res = rec.res if rec is not None \
                        else ov.fabric.get(acc.resident_id)
                    if res is None:
                        raise
                    return self._dispatch_failed(entry, res, exc, args,
                                                 presplit)
                us = (time.perf_counter() - t0) * 1e6
                if rec is not None and rec.res.dispatch_hist is not None:
                    rec.res.dispatch_hist.record(us)
                ov.dispatch_hist.record(us)
            n_out = len(entry.lowered.graph.output_ids)
            leaves = list(out) if n_out > 1 else [out]
            return jax.tree_util.tree_unflatten(entry.lowered.out_tree,
                                                leaves)


class Overlay:
    """A rows×cols dynamic overlay with a shared fabric and bitstream cache.

    All accelerators assembled through one ``Overlay`` co-reside on one
    :class:`~repro.core.fabric.Fabric`: each assembly packs into the tiles
    the current residents leave free, and when the fabric is full the
    overlay reclaims least-recently-used residents (releasing their tiles
    *and* evicting their bitstreams — the paper's PR-region replacement).

    Args:
      rows/cols: tile grid dimensions (paper evaluates 3×3).
      policy: DYNAMIC (paper's contribution) or STATIC (baseline).
      large_fraction: fraction of LARGE tiles (paper: 1/4).
      mesh / tile_axis: optional JAX mesh for real-ICI assembly
        (:func:`interpreter.assemble_sharded`); otherwise local assembly.
      cache_capacity: bitstream cache slots.
      auto_defragment: re-place surviving residents contiguously after every
        LRU reclaim (costs their bitstreams — moved accelerators re-download
        on next use).
      async_downloads: run PR downloads (place + eager XLA compile) on a
        background :class:`~repro.core.scheduler.DownloadScheduler` and serve
        jit misses from a fallback until the bitstream swaps in.  The default
        (False) is the deterministic synchronous mode: every miss pays its
        download on the critical path, exactly the pre-scheduler behavior.
        Ignored (forced off) when a mesh is given — sharded assembly wraps
        its own collectives and stays synchronous.
      download_workers: scheduler worker threads (async mode only).
      cost_aware_reclaim: reclaim the resident with the best
        age/re-download-cost ratio instead of pure LRU.  Defaults to
        following ``async_downloads`` (the pipeline measures real compile
        seconds; synchronous lazy mode has no meaningful costs to weigh).
      auto_specialize: background-compile the route-constant *specialized*
        tier for residents whose placement is contiguous (zero pass-through
        hops) or whose routes have been stable for ``specialize_after``
        dispatches, and swap the dispatch fast path onto it (DESIGN.md §7).
        Specialize jobs ride the scheduler's LOW lane — strictly below
        downloads and relocations.  Defaults to following
        ``async_downloads``; ``jitted.specialize(*args)`` works either way.
      specialize_after: dispatch-stability threshold for the non-contiguous
        trigger (a placement that keeps its routes this many hits in a row
        is worth baking them into).
      store / store_path: attach a persistent :class:`BitstreamStore`
        (DESIGN.md §11) — compiled kernel artifacts are serialized to disk
        on the scheduler's low lane, and a fresh overlay pointed at the
        same directory warms its cache from disk instead of recompiling
        (warm restarts; fleet members share one store).  Store-attached
        overlays compile eagerly on the sync path (lazy jit wrappers don't
        serialize).  Pass an existing ``store`` instance to share it, or
        ``store_path`` to open/create one.
      cost_model_placement: replace first-fit packing with the cost-model
        planner (DESIGN.md §11) — candidate placements at several footprint
        budgets are scored in seconds-equivalent cost (measured per-hop
        dispatch latency, co-location crowding, tile scarcity), and
        pressure reclaims pick the victim with the cheapest modeled
        re-download (near-zero for store-backed residents).  Defaults to
        on iff a store is attached.
      autotune_thresholds: re-derive ``specialize_after`` and the
        auto-defragment trigger from live measurements instead of the
        fixed defaults (DESIGN.md §11).  Defaults to on iff a store is
        attached.
    """

    def __init__(self, rows: int = 3, cols: int = 3, *,
                 policy: PlacementPolicy = PlacementPolicy.DYNAMIC,
                 large_fraction: float = 0.25,
                 mesh: jax.sharding.Mesh | None = None,
                 tile_axis: str = "tiles",
                 cache_capacity: int = 256,
                 auto_defragment: bool = False,
                 async_downloads: bool = False,
                 download_workers: int = 1,
                 cost_aware_reclaim: bool | None = None,
                 auto_specialize: bool | None = None,
                 specialize_after: int = 32,
                 sanitize: bool | None = None,
                 store: "BitstreamStore | None" = None,
                 store_path: "str | None" = None,
                 cost_model_placement: bool | None = None,
                 autotune_thresholds: bool | None = None,
                 faults: "FaultPlan | None" = None,
                 breaker_threshold: int = _MAX_DOWNLOAD_FAILURES,
                 retry_backoff: int = 1,
                 breaker_probe_after: int = 8,
                 download_deadline: float | None = None,
                 drain_timeout: float = 30.0) -> None:
        self.grid = TileGrid(rows, cols, large_fraction)
        self.policy = policy
        self.mesh = mesh
        self.tile_axis = tile_axis
        self.cache = BitstreamCache(cache_capacity)
        self.fabric = Fabric(self.grid)
        self.auto_defragment = auto_defragment
        self.async_downloads = bool(async_downloads) and mesh is None
        self.cost_aware_reclaim = (self.async_downloads
                                   if cost_aware_reclaim is None
                                   else bool(cost_aware_reclaim))
        self._auto_specialize = (self.async_downloads
                                 if auto_specialize is None
                                 else bool(auto_specialize))
        if specialize_after < 1:
            raise ValueError("specialize_after must be >= 1")
        self.specialize_after = int(specialize_after)
        # failure model (DESIGN.md §12): deterministic fault injection,
        # retry/backoff + per-entry circuit breaker, download deadlines
        self.faults = faults
        if breaker_threshold < 1 or retry_backoff < 1 \
                or breaker_probe_after < 1:
            raise ValueError("breaker_threshold, retry_backoff and "
                             "breaker_probe_after must be >= 1")
        self.breaker_threshold = int(breaker_threshold)
        self.retry_backoff = int(retry_backoff)
        self.breaker_probe_after = int(breaker_probe_after)
        self.download_deadline = download_deadline
        self.drain_timeout = float(drain_timeout)
        self.scheduler = DownloadScheduler(workers=download_workers,
                                           drain_timeout=drain_timeout)
        # persistent bitstream store + cost-model planner (DESIGN.md §11)
        if store is not None and store_path is not None:
            raise ValueError("pass store= or store_path=, not both")
        if store is None and store_path is not None:
            store = BitstreamStore(store_path, faults=faults)
        self.store = store
        self.cost_model_placement = ((store is not None)
                                     if cost_model_placement is None
                                     else bool(cost_model_placement))
        self.autotune_thresholds = ((store is not None)
                                    if autotune_thresholds is None
                                    else bool(autotune_thresholds))
        # adaptive auto-defragment gate (only consulted when autotuning):
        # fragmentation fraction below which a post-reclaim defrag is skipped
        self.defrag_threshold = 0.25
        # consecutive admissions that each paid >=1 reclaim — the planner's
        # churn detector (flips victim selection to anti-thrash MRU)
        self._reclaim_streak = 0
        # sanitizer mode (DESIGN.md §10): run the repro.analysis.check
        # invariant suite at every mutation edge.  Off by default; the
        # dispatch fast path does ZERO extra work when disabled (hooks sit
        # on admit/evict/relocate/spec-commit, all behind this flag).
        if sanitize is None:
            sanitize = os.environ.get("REPRO_SANITIZE", "") not in ("", "0")
        self.sanitize = bool(sanitize)
        self.stats = OverlayStats()
        # optional victim-pool narrowing for pressure reclaims: residents
        # satisfying this predicate are sacrificed first (a FleetOverlay
        # installs one per member so replicated copies go before sole ones)
        self.reclaim_prefer: "Callable[[ResidentAccelerator], bool] | None" \
            = None
        self._last_placement: Placement | None = None
        # one lock for all fabric/cache mutation: foreground assemblies and
        # background download commits serialize on it
        self._lock = threading.RLock()
        self._wrappers: "weakref.WeakSet[JitAssembled]" = weakref.WeakSet()
        self._prefetched: set[str] = set()   # rids downloaded ahead of demand
        # dispatch observability (DESIGN.md §9): overlay-wide roll-ups of
        # the per-resident ledgers — end-to-end dispatch latency (us, both
        # tiers) and total route hops per admitted/relocated placement
        self.dispatch_hist = Histogram()
        self.route_cost_hist = Histogram()
        if self.store is not None:
            # warm boot: re-seed the fabric's measurement ledger so the
            # planner prices reclaims from history instead of starting blind
            ledger = self.store.load_ledger()
            if ledger:
                with self._lock:
                    self.fabric.seed_ledger(ledger)

    # -- async bookkeeping ----------------------------------------------------
    def _register(self, wrapper: "JitAssembled") -> None:
        self._wrappers.add(wrapper)

    def _sanity_check(self) -> None:
        """Sanitizer hook: run the full invariant suite (caller holds the
        overlay lock).  Only reached when ``self.sanitize`` is on — the
        import stays out of every default-mode code path."""
        from repro.analysis import check as _check

        _check.ensure(_check.check_overlay(self))

    def _note_demand(self, rid: str) -> None:
        """First demand access of a prefetched resident = one prefetch hit."""
        if rid in self._prefetched:
            self._prefetched.discard(rid)
            self.stats.prefetch_hits += 1

    # -- failure model (DESIGN.md §12) ----------------------------------------
    def _inject_download_fault(self, key: str) -> None:
        """Chaos choke point for the bitstream compile (sync and async
        paths): optionally sleep first (slow download), optionally raise
        :class:`FaultError` (failed download).  No-op without a plan."""
        plan = self.faults
        if plan is None:
            return
        if plan.slow_seconds > 0.0 and plan.fires("slow_download", key):
            time.sleep(plan.slow_seconds)
        if plan.fires("download", key):
            raise FaultError(f"injected download failure for {key!r}")

    def _lose_resident(self, rid: str) -> None:
        """Injected dispatch-time resident loss (the chaos analogue of an
        SEU / power glitch wiping a PR region): the resident leaves the
        fabric through the one true evict path; the caller degrades to the
        slow path and re-downloads."""
        with self._lock:
            if self.fabric.get(rid) is not None:
                self.stats.resident_losses += 1
                self._evict_resident(rid)

    def failure_ledger(self) -> dict[str, Any]:
        """One-stop failure accounting: retries, breaker state, dispatch
        fallbacks, watchdog timeouts.  Serving layers surface this through
        ``metrics()``; the analysis report prints it."""
        open_breakers = 0
        for wrapper in list(self._wrappers):
            for entry in list(wrapper._entries.values()):
                if entry.breaker == "open":
                    open_breakers += 1
        return {
            "download_failures": self.stats.download_failures,
            "download_retries": self.stats.download_retries,
            "breaker_opens": self.stats.breaker_opens,
            "breaker_probes": self.stats.breaker_probes,
            "breaker_closes": self.stats.breaker_closes,
            "breakers_open": open_breakers,
            "dispatch_failures": self.stats.dispatch_failures,
            "dispatch_fallbacks": self.stats.dispatch_fallbacks,
            "resident_losses": self.stats.resident_losses,
            "timed_out_downloads": self.scheduler.stats.timed_out,
        }

    # -- lock-light dispatch records ------------------------------------------
    def _publish_record(self, entry: _JitEntry) -> None:
        """(Re)derive an entry's immutable dispatch record from its
        assembled accelerator.  Picks the best live artifact tier: the
        route-constant specialized executable when the resident carries one
        for this entry's kernel key, else the generic routes-bound fn.  A
        non-current residency publishes None (the slow path keeps serving
        its fallback)."""
        acc = entry.acc
        rec = None
        if acc is not None and acc.resident_id is not None:
            res = self.fabric.get(acc.resident_id)
            if res is not None and res.live \
                    and res.generation == acc.generation:
                fn, tier = acc.fn, "generic"
                if res.tier == "specialized" and res.spec_fn is not None \
                        and entry.jit_kwargs == res.spec_jit_kwargs:
                    fn, tier = res.spec_fn, "specialized"
                rec = _DispatchRecord(fn=fn, res=res,
                                      generation=res.generation, tier=tier)
        entry.record = rec

    # -- trace-based frontend -------------------------------------------------
    def jit(self, fn: Callable[..., Any] | None = None, *,
            strict: bool = False, name: str | None = None,
            fixed: dict[int, Coord] | None = None,
            static_argnums: tuple[int, ...] = (),
            donate_argnums: tuple[int, ...] = (),
            tile_budget: int | None = None) -> Callable[..., Any]:
        """Compile a plain JAX function into an overlay accelerator.

        Usable directly (``acc = overlay.jit(fn)``) or as a decorator, with
        or without arguments.  ``strict=True`` errors on primitives without a
        library lowering; the default leaves them as fused XLA residue.
        ``fixed`` pins graph nodes to tiles (static-placement experiments).
        ``tile_budget`` caps this accelerator's fabric footprint so it can
        co-reside with others (large traced graphs otherwise greedily spread
        over every free tile).
        """
        def wrap(f: Callable[..., Any]) -> JitAssembled:
            return JitAssembled(self, f, strict=strict, name=name, fixed=fixed,
                                static_argnums=static_argnums,
                                donate_argnums=donate_argnums,
                                tile_budget=tile_budget)
        return wrap if fn is None else wrap(fn)

    def aot(self, fn: Callable[..., Any], *abstract_args,
            strict: bool = False, name: str | None = None,
            fixed: dict[int, Coord] | None = None,
            tile_budget: int | None = None) -> JitAssembled:
        """Ahead-of-time assembly: populate the bitstream cache for a
        signature before traffic arrives (pay the PR download at startup).

        ``abstract_args`` are ``jax.ShapeDtypeStruct`` pytrees (concrete
        arrays also work).  Returns the jitted wrapper — calling it with
        matching concrete inputs is a pure cache hit.
        """
        jitted = self.jit(fn, strict=strict, name=name, fixed=fixed,
                          tile_budget=tile_budget)
        jitted._entry(abstract_args, aot=True)
        return jitted

    # -- assembly (low-level Graph IR path) -----------------------------------
    def plan(self, graph: Graph, fixed: dict[int, Coord] | None = None, *,
             occupied: "set[Coord] | None" = None,
             tile_budget: int | None = None) -> tuple[Placement, Program]:
        """Placement + ISA program, without building the executable.

        Residency-aware: by default packs around the fabric's current
        residents (pass ``occupied=set()`` to plan against an empty fabric).
        Does NOT admit the placement — a plan holds no tiles.
        """
        occ = self.fabric.occupied() if occupied is None else occupied
        placement = place(graph, self.grid, self.policy, fixed,
                          occupied=occ, max_tiles=tile_budget)
        return placement, compile_graph(graph, placement)

    def _resident_key(self, graph: Graph, avals: tuple,
                      fixed: dict[int, Coord] | None) -> str:
        # `fixed` is part of the accelerator's identity: the same graph
        # pinned to different tiles is a different placement/bitstream
        pins = repr(sorted(fixed.items())) if fixed else ""
        return cache_lib.cache_key(graph.name, cache_lib.signature_of(avals),
                                   placement_desc=pins,
                                   extra="resident:" + graph.fingerprint())

    def resident_current(self, acc: interp.AssembledAccelerator) -> bool:
        """Whether an assembled accelerator still holds its PR regions."""
        return self.fabric.is_current(acc.resident_id, acc.generation)

    def _place_with_reclaim(self, graph: Graph,
                            fixed: dict[int, Coord] | None,
                            tile_budget: int | None) -> Placement:
        """Place into free tiles; on pressure, reclaim residents (tiles +
        bitstreams via the one evict path) until the graph fits or the
        fabric is empty.  Victim order is LRU, or age-per-re-download-cost
        when ``cost_aware_reclaim`` is on.  A graph that cannot fit even an
        *empty* fabric is structurally unplaceable: it re-raises immediately
        rather than evicting innocent residents first.

        With ``cost_model_placement`` the first-fit rule is replaced by the
        cost-model planner (DESIGN.md §11)."""
        if self.cost_model_placement:
            return self._plan_with_cost_model(graph, fixed, tile_budget)
        probed = False
        while True:
            try:
                return place(graph, self.grid, self.policy, fixed,
                             occupied=self.fabric.occupied(),
                             max_tiles=tile_budget)
            except PlacementError:
                victim = self.fabric.reclaim_victim(
                    cost_aware=self.cost_aware_reclaim,
                    prefer=self.reclaim_prefer)
                if victim is None:
                    raise
                if not probed:
                    # propagates the PlacementError when reclaiming could
                    # never help (e.g. a LARGE op on an all-SMALL grid)
                    place(graph, self.grid, self.policy, fixed,
                          occupied=frozenset(), max_tiles=tile_budget)
                    probed = True
                self._evict_resident(victim.rid)
                self.stats.reclaims += 1
                self._maybe_defragment()

    # -- cost-model placement planner (DESIGN.md §11) -------------------------
    # price priors (seconds) for quantities not yet measured in this process
    _RECLAIM_PRIOR_S = 0.05       # unmeasured re-download (cold XLA compile)
    _STORE_LOAD_PRIOR_S = 0.005   # unmeasured store load (deserialize)

    def _reclaim_prior(self) -> float:
        """Neutral re-download price: the mean measured cost, else a prior."""
        mean = self.fabric.mean_download_cost()
        return mean if mean > 0.0 else self._RECLAIM_PRIOR_S

    def _planner_hop_cost(self) -> float:
        """Per-hop steady-state price: a slice of the measured p50 dispatch
        latency (route hops run as extra barrier/permute passes inside the
        kernel), clamped; a fixed default until enough dispatches have
        landed for the p50 to stop reflecting cold first calls (which pay
        their download inline and would inflate the hop price 100x)."""
        if self.dispatch_hist.count >= 16:
            p50_s = self.dispatch_hist.percentile(0.5) * 1e-6
            return min(1e-3, max(1e-5, 0.05 * p50_s))
        return 1e-4

    def _victim_price(self, res: ResidentAccelerator) -> float:
        """Modeled cost of reclaiming ``res`` NOW: what the next admission
        would pay to bring its kernels back.  Near-zero when every kernel it
        owns is store-backed — the store hit replaces the cold compile —
        which is the measurement that lets the planner prefer evicting warm
        store-backed residents over compacting expensive cold ones."""
        if self.store is not None and res.cache_keys \
                and all(k in self.store for k in res.cache_keys):
            st = self.cache.stats
            if st.store_hits:
                return st.store_load_seconds / st.store_hits
            return self._STORE_LOAD_PRIOR_S
        cost = self.fabric.download_cost(res.rid) or res.download_cost
        return cost if cost > 0.0 else self._reclaim_prior()

    def _plan_with_cost_model(self, graph: Graph,
                              fixed: dict[int, Coord] | None,
                              tile_budget: int | None) -> Placement:
        """Cost-model replacement for first-fit: generate feasible candidate
        placements at several footprint budgets and adopt the cheapest in
        seconds-equivalent cost (hops at the measured per-hop price,
        co-location crowding, tile scarcity) — the quadratic scarcity term
        makes footprint increasingly expensive as the fabric fills, so
        admissions *compact into fewer tiles instead of reclaiming*
        whenever crowding is cheaper than the modeled re-download a
        reclaim would cause.  When nothing fits at any budget, the victim with the
        cheapest modeled re-download (store-aware: disk-backed kernels are
        nearly free to bring back) is reclaimed and planning retries."""
        probed = False
        evicted = False
        while True:
            occ = self.fabric.occupied()
            cands = candidate_placements(graph, self.grid, self.policy, fixed,
                                         occupied=occ, max_tiles=tile_budget)
            if cands:
                # the streak counts CONSECUTIVE admissions that each paid a
                # reclaim — the churn detector behind _select_victim_locked
                self._reclaim_streak = (self._reclaim_streak + 1) if evicted \
                    else 0
                hop_s = self._planner_hop_cost()
                return min(cands, key=lambda p: score_placement(
                    p, hop_cost_s=hop_s, crowd_cost_s=2.0 * hop_s,
                    occupied_tiles=len(occ), num_tiles=self.grid.num_tiles,
                    tile_pressure_s=self._reclaim_prior()))
            victim = self._select_victim_locked()
            if victim is None:
                # empty fabric and still unplaceable: let place() raise the
                # structural PlacementError
                return place(graph, self.grid, self.policy, fixed,
                             occupied=occ, max_tiles=tile_budget)
            if not probed:
                # as in the first-fit path: a graph that cannot fit an empty
                # fabric must not evict innocent residents first
                place(graph, self.grid, self.policy, fixed,
                      occupied=frozenset(), max_tiles=tile_budget)
                probed = True
            self._evict_resident(victim.rid)
            evicted = True
            self.stats.reclaims += 1
            self._maybe_defragment()

    def _select_victim_locked(self) -> "ResidentAccelerator | None":
        """The planner's reclaim victim (caller holds the lock): normally
        the fabric's cost-aware choice under the store-aware price, BUT
        when every one of the last ``len(pool)`` admissions paid a reclaim
        the working set has outgrown the fabric and age-based ordering is
        the pathological policy — a cyclic rotation's LRU resident is
        exactly the accelerator needed next, so every call misses.
        Belady's rule for a loop longer than the cache is to evict the
        entry whose next use is FARTHEST — the most recently used — which
        pins a stable subset resident and converts part of every cycle
        into hits.  Price still gates the flip: only residents within 2x
        of the cheapest modeled re-download are MRU candidates, so an
        expensive-to-rebuild resident is never sacrificed to the
        heuristic."""
        pool = list(self.fabric.residents.values())
        if not pool:
            return None
        if self.reclaim_prefer is not None:
            preferred = [r for r in pool if self.reclaim_prefer(r)]
            if preferred:
                pool = preferred
        if self._reclaim_streak >= len(pool):
            prices = {r.rid: self._victim_price(r) for r in pool}
            cheapest = min(prices.values())
            mru_pool = [r for r in pool
                        if prices[r.rid] <= 2.0 * cheapest + 1e-9]
            return max(mru_pool, key=lambda r: r.last_used)
        return self.fabric.reclaim_victim(
            cost_aware=True, prefer=self.reclaim_prefer,
            price=self._victim_price)

    def _maybe_defragment(self) -> None:
        """Post-reclaim defragment gate.  Plain ``auto_defragment`` keeps
        the fixed behavior (a pass after every reclaim); with
        ``autotune_thresholds`` the pass only runs once the fabric-wide
        fragmentation metric crosses an adaptive threshold, which
        self-adjusts on observed usefulness: a pass that moved nobody
        raises the bar, a pass that compacted lowers it."""
        if not self.auto_defragment:
            return
        if not self.autotune_thresholds:
            self.defragment()
            return
        if self.fabric.fragmentation() < self.defrag_threshold:
            return
        moved = self.defragment()
        if moved == 0:
            self.defrag_threshold = min(0.9,
                                        self.defrag_threshold * 1.5 + 0.01)
        else:
            self.defrag_threshold = max(0.02, self.defrag_threshold * 0.75)

    def _autotune_locked(self) -> None:
        """Measurement-driven re-derivation of ``specialize_after`` (caller
        holds the lock; no-op unless ``autotune_thresholds``): amortize the
        measured mean specialize-compile cost over dispatches at the
        measured p50 latency, assuming a conservative 25% per-dispatch
        saving from the route-constant tier, clamped to [8, 512].  Cheap
        compiles against slow dispatches specialize sooner; expensive
        compiles against fast dispatches demand longer stability."""
        if not self.autotune_thresholds:
            return
        ss = self.cache.spec_stats
        if not ss.specializations or not self.dispatch_hist.count:
            return
        spec_cost = ss.compile_seconds / ss.specializations
        p50_s = self.dispatch_hist.percentile(0.5) * 1e-6
        if p50_s <= 0.0 or spec_cost <= 0.0:
            return
        self.specialize_after = min(512, max(8, int(spec_cost
                                                    / (0.25 * p50_s))))

    # -- persistent bitstream store (DESIGN.md §11) ---------------------------
    def _store_load_locked(self, key: str):
        """Try to satisfy a cache miss from the on-disk bitstream store
        (caller holds the lock).  Returns ``(exe, seconds)`` on success and
        books the load into the cache (as a miss that paid a store hit
        instead of a compile), or ``None`` — plain miss, header/payload
        validation failure, or deserialize failure — in which case the
        caller cold-compiles.  A blob whose *executable* fails to
        deserialize (e.g. XLA refused the payload) is expunged so the next
        boot does not trip over it again."""
        if self.store is None or self.mesh is not None:
            return None
        blob = self.store.load_blob(key)
        if blob is None:
            return None
        t0 = time.perf_counter()
        try:
            exe = BitstreamStore.unpack_executable(blob)
        except Exception as exc:  # noqa: BLE001 — any failure = cold compile
            self.store.note_unusable(key)
            logger.warning("bitstream store: entry for %r failed to "
                           "deserialize (%s); cold compiling", key, exc)
            return None
        dt = time.perf_counter() - t0
        self.cache.insert_loaded(key, exe, dt)
        return exe, dt

    def _persist_artifact_locked(self, key: str, exe) -> None:
        """Queue ``exe`` for persistence over the scheduler's LOW lane
        (caller holds the lock) — a persist never delays a demand
        download.  Serialization (the expensive half) runs on a worker
        with no locks held; the disk write commits back under the lock
        only if the artifact is still cached (evicted-while-serializing
        entries are dropped, not resurrected on disk)."""
        if self.store is None or self.scheduler.closed \
                or not isinstance(exe, jax.stages.Compiled) \
                or key in self.store:
            return
        self.scheduler.submit(
            f"persist:{key}",
            lambda: BitstreamStore.pack_executable(exe),
            lambda blob, dt: self._commit_persist(key, blob, "kernel"),
            kind="persist", low=True)

    def _commit_persist(self, key: str, blob: bytes, store_kind: str):
        """Write a serialized artifact to the store (worker, takes the
        lock).  Liveness-guarded like a download commit: persists only
        entries the cache still serves, so an evict that raced the
        serialization wins and the disk never holds a resurrected key."""
        with self._lock:
            if self.store is None:
                return None
            if store_kind == "specialized":
                alive = self.cache.specialized(key) is not None
            else:
                alive = key in self.cache
            if not alive:
                return None
            ok = self.store.save(key, blob, kind=store_kind)
            if ok:
                # piggyback the measurement ledger on every successful
                # persist — restarts re-seed EWMA costs + latency histograms
                self.store.save_ledger(self.fabric.export_ledger())
            return ok or None

    def _persist_spec_locked(self, pending: _PendingSpecialize) -> None:
        """Queue the route-constant tier for persistence (caller holds the
        lock).  The live spec tier is a warmed ``jax.jit`` — not
        serializable — so the worker AOT-compiles the same route-constant
        kernel into a ``Compiled`` for the disk copy (cheap: XLA's
        compilation cache was just warmed by the live compile)."""
        if self.store is None or self.scheduler.closed \
                or self.mesh is not None or pending.spec_key in self.store:
            return
        self.scheduler.submit(
            f"persist:{pending.spec_key}",
            lambda: self._build_spec_blob(pending),
            lambda blob, dt: self._commit_persist(pending.spec_key, blob,
                                                  "specialized"),
            kind="persist", low=True)

    def _build_spec_blob(self, pending: _PendingSpecialize) -> bytes:
        """Worker half of a spec persist (no locks held): AOT-compile the
        route-constant kernel and serialize it."""
        kernel = interp.specialize_kernel(pending.graph, pending.hops)
        routes_aval = jax.ShapeDtypeStruct((len(pending.hops),), "int32")
        exe = cache_lib.aot_compile(
            kernel, (routes_aval,) + pending.avals,
            jit_kwargs=cache_lib.kernel_jit_kwargs(pending.jit_kwargs))
        return BitstreamStore.pack_executable(exe)

    def _kernel_key(self, graph: Graph, avals: tuple,
                    jit_kwargs: dict[str, Any] | None) -> str:
        """Placement-FREE identity of the compiled kernel artifact: one
        executable serves every placement of this graph (the routes vector
        is a runtime argument) — the relocatable-bitstream invariant."""
        return cache_lib.kernel_key(
            graph.name, cache_lib.signature_of(avals),
            mesh_desc=str(self.mesh.shape) if self.mesh else "local",
            fingerprint=graph.fingerprint(),
            extra=repr(sorted((jit_kwargs or {}).items())))

    def _get_or_admit(self, graph: Graph, avals: tuple, rid: str,
                      fixed: dict[int, Coord] | None,
                      tile_budget: int | None, *,
                      reclaim: bool = True) -> ResidentAccelerator:
        """Resident lookup-or-admission (the actual PR download decision);
        callers must hold the overlay lock.  ``reclaim=False`` raises
        :class:`PlacementError` under pressure instead of evicting (hint
        paths that must not displace live residents)."""
        resident = self.fabric.get(rid)
        if resident is not None:
            self.fabric.touch(rid)
            if tile_budget is not None and tile_budget != resident.tile_budget:
                # budget repack: re-place under the new footprint cap and
                # RELOCATE — the kernel artifact is placement-free, so a
                # policy-driven resize never pays a re-download
                self._repack_budget(resident, tile_budget)
            return resident
        if reclaim:
            placement = self._place_with_reclaim(graph, fixed, tile_budget)
        else:
            placement = place(graph, self.grid, self.policy, fixed,
                              occupied=self.fabric.occupied(),
                              max_tiles=tile_budget)
        program = compile_graph(graph, placement)
        resident = self.fabric.admit(rid, graph.name, graph, placement,
                                     program, tile_budget=tile_budget,
                                     fixed=fixed)
        self._bind_routes_eager(graph, resident)
        self.stats.downloads += 1
        # only a real re-place/download changes the fabric layout; a
        # resident hit dispatches to tiles already configured
        if self._last_placement is not None and \
                placement.assignment != self._last_placement.assignment:
            self.stats.reconfigurations += 1
        self._last_placement = placement
        if self.sanitize:
            self._sanity_check()
        return resident

    def _bind_routes_eager(self, graph: Graph,
                           resident: ResidentAccelerator) -> None:
        """Build the resident's routes vector ONCE, at admit/relocate time,
        as a device-resident buffer — dispatch never reconstructs it or pays
        the host→device transfer again (the hot path only ever *reads*
        ``resident.routes``)."""
        resident.routes = self.cache.route_program(
            resident.rid, resident.placement.descriptor(),
            lambda: jax.device_put(
                interp.route_vector(graph, resident.placement)))
        hops = interp.route_hops(graph, resident.placement)
        resident.zero_hop = interp.zero_hop(hops)
        resident.route_cost = int(sum(hops))
        self.route_cost_hist.record(resident.route_cost)

    def _base_acc(self, graph: Graph,
                  resident: ResidentAccelerator) -> interp.AssembledAccelerator:
        """The un-jitted assembled accelerator for a resident (built once
        per placement; a relocation clears it and this rebinds — no XLA)."""
        if resident.acc is None:
            if resident.routes is None:
                self._bind_routes_eager(graph, resident)
            routes = resident.routes
            if self.mesh is not None:
                acc = interp.assemble_sharded(graph, resident.placement,
                                              self.mesh, self.tile_axis,
                                              program=resident.program,
                                              routes=routes)
            else:
                acc = interp.assemble(graph, resident.placement,
                                      program=resident.program, routes=routes)
            resident.acc = dataclasses.replace(
                acc, resident_id=resident.rid, generation=resident.generation)
        return resident.acc

    def _repack_budget(self, resident: ResidentAccelerator,
                       tile_budget: int | None) -> None:
        """Re-place a resident under a changed footprint cap via relocation
        (caller holds the lock).  Best-effort: under pressure the old
        placement stands and the new budget applies at the next re-place."""
        occ = self.fabric.occupied() - resident.tiles
        try:
            pl = place(resident.graph, self.grid, self.policy, resident.fixed,
                       occupied=occ, max_tiles=tile_budget)
        except PlacementError:
            resident.tile_budget = tile_budget
            return
        resident.tile_budget = tile_budget
        if pl.assignment != resident.placement.assignment:
            self._relocate_resident(resident.rid, pl)

    def _relocate_resident(self, rid: str, placement: Placement,
                           ignore: "tuple[str, ...]" = ()
                           ) -> ResidentAccelerator:
        """THE relocation path (caller holds the lock): re-emit the
        controller route program for the new placement and rehome the tiles.
        Kernel artifacts, the bitstream cache, and the download-cost ledger
        are untouched — the move costs microseconds, not a PR download.  In
        async mode a priority rebind job refreshes live jit entries so the
        first post-move call already dispatches to the kernel."""
        res = self.fabric.get(rid)
        program = compile_graph(res.graph, placement)
        # routes are about to change: the route-constant tier is garbage the
        # moment they do — despecialize FIRST (instant, non-blocking; the
        # generic kernel keeps serving), then rehome the tiles
        self._despecialize(res)
        # old-placement route programs die with the move (bounds the side
        # table at ~one live entry per resident under sustained churn)
        self.cache.evict_routes(rid)
        res = self.fabric.relocate(rid, placement, program, ignore=ignore)
        self._bind_routes_eager(res.graph, res)
        self.stats.relocations += 1
        if self.async_downloads and not self.scheduler.closed:
            gen = res.generation
            self.scheduler.submit(
                f"relocate:{rid}",
                lambda: None,
                lambda _raw, _dt, rid=rid, gen=gen:
                    self._rebind_resident(rid, gen),
                kind="relocate", priority=True)
        # planned repacks (ignore non-empty) pass through legal transient
        # overlap between movers — the plan driver checks once at the end
        if self.sanitize and not ignore:
            self._sanity_check()
        return res

    def _rebind_resident(self, rid: str, generation: int):
        """Commit half of a relocation job: generation-guarded, cheap (no
        compile).  Rebinds every live jit entry of ``rid`` onto the cached
        kernel artifact with the new placement's routes.  Guarded by
        ``same_residency`` (epoch, not exact generation): back-to-back
        relocations coalesce onto the first job's key, and the rebind must
        still serve the latest move — it reads the resident's CURRENT
        placement, so committing under an older same-epoch generation is
        correct."""
        with self._lock:
            if not self.fabric.same_residency(rid, generation):
                return None
            res = self.fabric.get(rid)
            graph = res.graph
            avals = tuple(graph.toposorted()[i].aval for i in graph.input_ids)
            base = self._base_acc(graph, res)
            for wrapper in list(self._wrappers):
                for entry in list(wrapper._entries.values()):
                    acc = entry.acc
                    if acc is None or acc.resident_id != rid \
                            or acc.generation == res.generation:
                        continue
                    exe = self.cache.peek(
                        self._kernel_key(graph, avals, entry.jit_kwargs))
                    if exe is None:
                        continue   # kernel still downloading — demand path
                    entry.acc = dataclasses.replace(
                        base, fn=interp.bind_routes(exe, base.routes))
                    self._publish_record(entry)
            return base

    # -- tiered route specialization (DESIGN.md §7) ---------------------------
    def _request_specialize(self, entry: _JitEntry,
                            res: ResidentAccelerator
                            ) -> DownloadHandle | None:
        """Dispatch-path trigger: queue a background route-constant compile
        for one entry's resident.  Cheap pre-checks run lock-free; the
        snapshot is built under the lock."""
        if self.scheduler.closed:
            return None
        with self._lock:
            return self._submit_specialize_locked(entry, res)

    def _spec_snapshot_locked(self, entry: _JitEntry,
                              res: ResidentAccelerator
                              ) -> _PendingSpecialize | None:
        """Validated [`_PendingSpecialize`] for (entry, res), or None when
        specialization is impossible/pointless right now (caller holds the
        lock).  One specialized variant per resident at a time; a resident
        whose compile keeps failing stops being retried at these routes
        (the cap resets on relocation — new routes, new chance)."""
        if not res.live or res.tier != "generic" or res.spec_pending \
                or res.spec_failures >= _MAX_DOWNLOAD_FAILURES:
            return None
        acc = entry.acc
        if acc is not None and acc.resident_id != res.rid:
            return None
        graph = entry.lowered.graph
        avals = tuple(graph.toposorted()[i].aval for i in graph.input_ids)
        key = self._kernel_key(graph, avals, entry.jit_kwargs)
        hops = interp.route_hops(graph, res.placement)
        return _PendingSpecialize(
            rid=res.rid, generation=res.generation, key=key,
            spec_key=cache_lib.spec_key(key, hops), graph=graph, hops=hops,
            avals=avals, jit_kwargs=entry.jit_kwargs)

    def _submit_specialize_locked(self, entry: _JitEntry,
                                  res: ResidentAccelerator
                                  ) -> DownloadHandle | None:
        pending = self._spec_snapshot_locked(entry, res)
        if pending is None:
            return None
        res.spec_pending = True
        res.spec_job = f"specialize:{pending.spec_key}"
        return self.scheduler.submit(
            res.spec_job,
            lambda: self._compile_specialized_tier(pending),
            lambda exe, dt: self._commit_specialized(pending, exe, dt),
            on_done=lambda result, h: self._spec_settled(pending, result, h),
            kind="specialize", low=True)

    def _spec_settled(self, pending: _PendingSpecialize, result,
                      handle: DownloadHandle) -> None:
        """Observer for background specialize jobs: a compile that FAILED
        (or was dropped) must not leave the resident wedged in
        ``spec_pending`` — the trigger paths all gate on it.  Failures are
        counted and capped (the generic tier keeps serving regardless)."""
        if result is not None:
            return                       # committed: state already settled
        with self._lock:
            res = self.fabric.get(pending.rid)
            if res is None or res.generation != pending.generation:
                return                   # relocated/evicted: already reset
            res.spec_pending = False
            res.spec_job = None
            if handle.error is not None:
                res.spec_failures += 1
                if res.spec_failures == 1:
                    warnings.warn(
                        f"background specialization for {res.name!r} failed "
                        f"({handle.error!r}); the generic kernel keeps "
                        f"serving. Giving up after "
                        f"{_MAX_DOWNLOAD_FAILURES} attempts.",
                        RuntimeWarning, stacklevel=2)

    def _specialize_now(self, entry: _JitEntry,
                        res: ResidentAccelerator) -> Any:
        """Synchronous specialization (deterministic overlays, explicit
        ``jitted.specialize``): pay the route-constant compile on the caller
        and commit — same generation guard as the background path."""
        with self._lock:
            pending = self._spec_snapshot_locked(entry, res)
            if pending is None:
                return None
            res.spec_pending = True
            res.spec_job = f"specialize:{pending.spec_key}"
        t0 = time.perf_counter()
        try:
            exe = self._compile_specialized_tier(pending)
        except BaseException:
            with self._lock:
                if self.fabric.is_current(pending.rid, pending.generation):
                    res.spec_pending = False
                    res.spec_job = None
                    res.spec_failures += 1
            raise
        return self._commit_specialized(pending, exe,
                                        time.perf_counter() - t0)

    def _compile_specialized_tier(self, pending: _PendingSpecialize):
        """The expensive half of a specialization — eager XLA compile of the
        route-CONSTANT kernel (hop counts baked in at trace time; the
        routes argument survives only as the bit-exactness seed).  Runs on
        a scheduler worker (low lane) or the explicit caller; no locks
        held.

        Returns a WARMED ``jax.jit`` callable, not a ``jax.stages.Compiled``:
        the whole point of this tier is per-call latency, and Compiled
        dispatches through a slow Python path while a warm jit function
        rides the C++ fast path.  Warming = one throwaway execution on
        zero inputs, which pays the XLA compile here in the background."""
        if self.store is not None and self.mesh is None:
            blob = self.store.load_blob(pending.spec_key)
            if blob is not None:
                try:
                    t0 = time.perf_counter()
                    exe = BitstreamStore.unpack_executable(blob)
                    dt = time.perf_counter() - t0
                except Exception as exc:  # noqa: BLE001 — cold compile below
                    self.store.note_unusable(pending.spec_key)
                    logger.warning(
                        "bitstream store: specialized entry for %r failed "
                        "to deserialize (%s); cold compiling",
                        pending.spec_key, exc)
                else:
                    # a Compiled dispatches a touch slower than a warmed
                    # jit, but skipping the route-constant XLA compile is
                    # the far bigger win on a warm restart
                    with self._lock:
                        self.cache.stats.store_hits += 1
                        self.cache.stats.store_load_seconds += dt
                    return exe
        if self.mesh is not None:
            jitted = interp.wrap_sharded_specialized(
                pending.graph, pending.hops, self.mesh, self.tile_axis)
        else:
            kernel = interp.specialize_kernel(pending.graph, pending.hops)
            jitted = jax.jit(
                kernel, **cache_lib.kernel_jit_kwargs(pending.jit_kwargs))
        routes_aval = jax.ShapeDtypeStruct((len(pending.hops),), "int32")
        zeros = [jnp.zeros(a.shape, a.dtype)
                 for a in (routes_aval,) + pending.avals]
        jax.block_until_ready(jitted(*zeros))    # compile + warm the cache
        return jitted

    def _commit_specialized(self, pending: _PendingSpecialize, exe,
                            seconds: float):
        """Publish a finished route-constant compile — generation-guarded
        like a download commit, but against the EXACT generation: a
        relocation in flight changed the routes the constants were baked
        from, so the late specialization is dropped (the resident already
        despecialized to the generic kernel; nothing blocks, nothing is
        evicted)."""
        with self._lock:
            if not self.fabric.is_current(pending.rid, pending.generation):
                self.cache.spec_stats.dropped_stale += 1
                return None
            res = self.fabric.get(pending.rid)
            self.cache.insert_specialized(pending.spec_key, exe, seconds)
            self.fabric.add_cache_key(pending.rid, pending.key)
            res.tier = "specialized"
            res.spec_pending = False
            res.spec_job = None
            # atomic swap: every live entry of this rid/kernel-key starts
            # dispatching the specialized executable on its next call
            fn = interp.bind_routes(exe, res.routes)
            res.spec_fn = fn
            res.spec_jit_kwargs = pending.jit_kwargs
            for wrapper in list(self._wrappers):
                for entry in list(wrapper._entries.values()):
                    acc = entry.acc
                    if acc is None or acc.resident_id != pending.rid \
                            or acc.generation != res.generation \
                            or entry.jit_kwargs != pending.jit_kwargs:
                        continue
                    entry.record = _DispatchRecord(
                        fn=fn, res=res, generation=res.generation,
                        tier="specialized")
            self._persist_spec_locked(pending)
            self._autotune_locked()
            if self.sanitize:
                self._sanity_check()
            return exe

    def _despecialize(self, res: ResidentAccelerator) -> None:
        """Overlay-side half of despecialization (caller holds the lock,
        and MUST follow up with ``Fabric.relocate`` — the single tier-reset
        point): cancel any in-flight specialize job, drop the resident's
        route-constant artifacts, book the despecialization.  Dispatch
        records pointing at the specialized executable die with the
        relocation's generation bump — no blocking, no eviction."""
        if res.spec_job is not None:
            self.scheduler.cancel(res.spec_job)
        self._drop_spec_artifacts(res)
        if res.tier == "specialized":
            self.cache.spec_stats.despecializations += 1

    def _drop_spec_artifacts(self, res: ResidentAccelerator) -> None:
        """Drop exactly THIS resident's route-constant executables (caller
        holds the lock).  Spec keys include the hop vector, so a sibling
        resident sharing the kernel key at different routes keeps its own
        variant — and conversely a specialized artifact never outlives the
        resident it was baked for."""
        hops = interp.route_hops(res.graph, res.placement)
        for k in res.cache_keys:
            self.cache.drop_specialized_exact(cache_lib.spec_key(k, hops))

    def _enqueue_contiguous_specializations(self) -> None:
        """Post-defragment hook (caller holds the lock): residents whose
        placement became contiguous (pass-through-free) queue their
        route-constant tier on the low lane — the steady state after
        compaction should serve zero-hop fused bitstreams."""
        if not (self._auto_specialize and self.async_downloads) \
                or self.scheduler.closed:
            return
        for wrapper in list(self._wrappers):
            for entry in list(wrapper._entries.values()):
                acc = entry.acc
                if acc is None or acc.resident_id is None:
                    continue
                res = self.fabric.get(acc.resident_id)
                if res is None or not res.zero_hop:
                    continue
                self._submit_specialize_locked(entry, res)

    def repack(self, rid: str, tile_budget: int | None) -> bool:
        """Re-place a resident under a changed footprint cap via relocation.
        No-op (False) when ``tile_budget`` is None, unchanged, or the rid is
        not resident; True when the resident actually moved."""
        if tile_budget is None:
            return False
        # lock-free pre-check: this runs on the jit dispatch hot path, which
        # must not contend with a multi-ms assemble() holding the lock when
        # the budget hasn't changed (the overwhelmingly common case)
        res = self.fabric.get(rid)
        if res is None or res.tile_budget == tile_budget:
            return False
        with self._lock:
            res = self.fabric.get(rid)          # re-check under the lock
            if res is None or res.tile_budget == tile_budget:
                return False
            gen = res.generation
            self._repack_budget(res, tile_budget)
            return self.fabric.get(rid).generation != gen

    def relocate(self, target: "Graph | str",
                 placement: Placement) -> ResidentAccelerator:
        """Move a resident accelerator to ``placement`` without paying a
        re-download (public relocation API).  ``target`` is a graph, an
        accelerator name (as :meth:`evict` takes — must name exactly one
        resident), or a resident id.  The new tiles must be free of *other*
        residents.  Returns the relocated resident."""
        with self._lock:
            if isinstance(target, Graph):
                avals = tuple(target.toposorted()[i].aval
                              for i in target.input_ids)
                rid = self._resident_key(target, avals, None)
            else:
                rid = str(target)
                if self.fabric.get(rid) is None:
                    # resolve by accelerator name, like evict() does
                    named = [r.rid for r in self.fabric.residents.values()
                             if r.name == rid]
                    if len(named) > 1:
                        raise FabricError(
                            f"relocate: {rid!r} names {len(named)} residents "
                            f"— pass a specific resident id")
                    if named:
                        rid = named[0]
            res = self.fabric.get(rid)
            if res is None:
                raise FabricError(f"relocate: no resident for {target!r}")
            # internal paths build placements via place(); a user-supplied
            # one must prove the same invariants before touching the fabric
            check_assignment(res.graph, self.grid, placement)
            return self._relocate_resident(rid, placement)

    def assemble(self, graph: Graph, *,
                 fixed: dict[int, Coord] | None = None,
                 jit: bool = True,
                 jit_kwargs: dict[str, Any] | None = None,
                 aot: bool = False,
                 tile_budget: int | None = None) -> interp.AssembledAccelerator:
        """JIT-assemble ``graph`` into a fabric-resident accelerator (cached).

        If the same graph+signature is already resident this is a pure hit:
        its existing placement (and tiles) are reused and its recency is
        bumped.  Otherwise the graph is placed into the free tiles —
        reclaiming residents under pressure — and admitted to the fabric as
        a new resident (a "download").  This path is synchronous: the
        download is paid before returning (the asynchronous pipeline lives
        in :meth:`submit_download`, used by the jit wrappers).

        ``aot=True`` lowers AND compiles the executable eagerly (bitstream
        pre-population); otherwise XLA compiles lazily on first call.
        ``tile_budget`` caps the accelerator's footprint (see :meth:`jit`).
        """
        with self._lock:
            graph.validate()
            avals = tuple(graph.toposorted()[i].aval for i in graph.input_ids)
            rid = self._resident_key(graph, avals, fixed)

            hit = self.fabric.get(rid) is not None
            resident = self._get_or_admit(graph, avals, rid, fixed, tile_budget)
            if hit:
                self._note_demand(rid)
            self.stats.assemblies += 1
            acc = self._base_acc(graph, resident)
            placement = resident.placement

            if not jit:
                return acc

            key = self._kernel_key(graph, avals, jit_kwargs)

            # the BitstreamCache's own LRU may have dropped this resident's
            # kernel while it stayed fabric-resident (finite store below
            # the region count) — recompiling it now is a real re-download;
            # keep the ledger honest instead of reporting a pure hit
            if key in resident.cache_keys and key not in self.cache:
                resident.cache_keys = tuple(k for k in resident.cache_keys
                                            if k in self.cache)
                self.stats.downloads += 1

            base = acc

            if self.store is not None and self.mesh is None:
                # only eagerly-compiled executables serialize — a lazy
                # jax.jit wrapper has nothing to persist, so a
                # store-attached overlay always pays the download up front
                aot = True

            if aot and self.mesh is None:
                cached = self.cache.peek(key)
                if cached is not None and \
                        not isinstance(cached, jax.stages.Compiled):
                    # a lazily-jitted entry cannot satisfy the AOT contract
                    # ("pay the PR download at startup"): drop it so the
                    # rebuild below eagerly compiles — timed as download cost
                    self.cache.evict_keys([key])

            if key in self.cache:
                # pure hit — the kernel artifact is placement-free, so it
                # serves this resident's CURRENT routes (post-relocation too)
                exe = self.cache.get_or_compile(key, lambda: None)
                self.fabric.add_cache_key(rid, key)
                return dataclasses.replace(
                    acc, fn=interp.bind_routes(exe, base.routes))
            loaded = self._store_load_locked(key)
            if loaded is not None:
                # warm restart: the kernel came off disk instead of through
                # XLA — booked as a store hit, and its (near-zero) load time
                # is the resident's honest re-download cost
                exe, load_dt = loaded
                self.fabric.record_download_cost(rid, load_dt)
                self.fabric.add_cache_key(rid, key)
                return dataclasses.replace(
                    acc, fn=interp.bind_routes(exe, base.routes))
            generation = resident.generation
            routes_aval = jax.ShapeDtypeStruct(base.routes.shape,
                                               base.routes.dtype)
        # miss: build OUTSIDE the lock — an AOT compile can run for seconds
        # and must not stall concurrent requests or background commits.
        # What compiles is the placement-invariant KERNEL (routes as arg 0).
        self._inject_download_fault(key)
        t0 = time.perf_counter()
        kernel_kwargs = cache_lib.kernel_jit_kwargs(jit_kwargs)
        if self.mesh is not None:
            exe = interp.wrap_sharded_kernel(base, graph, self.mesh)
        elif aot:
            exe = cache_lib.aot_compile(base.kernel, (routes_aval,) + avals,
                                        jit_kwargs=kernel_kwargs)
        else:
            exe = jax.jit(base.kernel, **kernel_kwargs)
        dt = time.perf_counter() - t0
        with self._lock:
            if self.fabric.same_residency(rid, generation):
                self.cache.insert_compiled(key, exe, dt)
                if aot:
                    # only eager compiles measure a real download; a lazy
                    # jax.jit returns in ~0s of scheduling noise (XLA
                    # compiles at first call) and would pollute the cost
                    # model with jitter
                    self.fabric.record_download_cost(rid, dt)
                self.fabric.add_cache_key(rid, key)
                self._persist_artifact_locked(key, exe)
                # relocated while compiling? the kernel is still valid —
                # rebind it to the resident's routes as they stand now
                res_now = self.fabric.get(rid)
                if res_now is not None and res_now.generation != generation:
                    base = self._base_acc(graph, res_now)
                    acc = base
            # else: the resident was reclaimed while we compiled — don't
            # publish an orphan bitstream; the executable itself is still a
            # correct pure function, so the caller keeps it
        return dataclasses.replace(acc, fn=interp.bind_routes(exe, base.routes))

    # -- asynchronous download pipeline ---------------------------------------
    def submit_download(self, graph: Graph, *,
                        fixed: dict[int, Coord] | None = None,
                        jit_kwargs: dict[str, Any] | None = None,
                        tile_budget: int | None = None,
                        on_done: "Callable[[Any, DownloadHandle], None] | None"
                        = None,
                        kind: str = "demand",
                        reclaim: bool = True,
                        low: bool = False) -> DownloadHandle:
        """Begin an asynchronous PR download for ``graph``.

        Foreground (cheap, under the overlay lock): place the graph —
        reclaiming under pressure — and *admit it immediately*, so the PR
        regions are held while the bitstream is in flight (the paper's
        region-allocated-download-pending state) and concurrent placements
        pack around it.  Background (scheduler worker): the eager XLA
        compile.  Commit (worker, back under the lock): publish executable +
        cache entry + measured download cost — but only if the residency
        ``(rid, generation)`` is still current; a resident evicted or
        flushed mid-download stays evicted and the late bitstream is
        dropped.

        ``on_done`` observers receive the final jit-level
        :class:`~repro.core.interpreter.AssembledAccelerator` (or None).
        If the bitstream is already downloaded this completes synchronously
        with an already-done handle.
        """
        with self._lock:
            graph.validate()
            avals = tuple(graph.toposorted()[i].aval for i in graph.input_ids)
            rid = self._resident_key(graph, avals, fixed)
            resident = self._get_or_admit(graph, avals, rid, fixed,
                                          tile_budget, reclaim=reclaim)
            base = self._base_acc(graph, resident)
            key = self._kernel_key(graph, avals, jit_kwargs)
            if kind == "prefetch":
                self.stats.prefetches += 1
                self._prefetched.add(rid)

            exe = self.cache.peek(key)
            cache_hit = exe is not None
            if not cache_hit:
                loaded = self._store_load_locked(key)
                if loaded is not None:
                    exe, load_dt = loaded
                    self.fabric.record_download_cost(rid, load_dt)
            if exe is not None:
                # kernel already cached (possibly compiled for another
                # placement — it is placement-free) or just loaded off
                # disk: bind this resident's routes and complete inline,
                # no background work needed
                if cache_hit:
                    self.cache.get_or_compile(key, lambda: exe)  # count hit
                self.fabric.add_cache_key(rid, key)
                handle = DownloadHandle(key=rid, kind=kind)
                handle.result = dataclasses.replace(
                    base, fn=interp.bind_routes(exe, base.routes))
                handle.status = "done"
                handle._event.set()
                if on_done is not None:
                    on_done(handle.result, handle)
                return handle

            pending = _PendingDownload(rid=rid, generation=resident.generation,
                                       key=key, base=base, avals=avals,
                                       jit_kwargs=jit_kwargs)
        return self.scheduler.submit(
            rid,
            lambda: self._compile_bitstream(pending),
            lambda exe, dt: self._commit_download(pending, exe, dt),
            on_done=on_done, kind=kind, low=low,
            deadline=self.download_deadline)

    def _compile_bitstream(self, pending: _PendingDownload):
        """The expensive half of a download — eager XLA compile of the
        placement-invariant kernel (routes as argument 0).  Runs on a
        scheduler worker, no locks held."""
        self._inject_download_fault(pending.key)
        base = pending.base
        routes_aval = jax.ShapeDtypeStruct(base.routes.shape,
                                           base.routes.dtype)
        return cache_lib.aot_compile(
            base.kernel, (routes_aval,) + pending.avals,
            jit_kwargs=cache_lib.kernel_jit_kwargs(pending.jit_kwargs))

    def _commit_download(self, pending: _PendingDownload, exe,
                         seconds: float):
        """Publish a finished background compile — the atomic swap.  Runs on
        the worker under the overlay lock; a download whose residency was
        evicted/flushed while compiling must not resurrect it.  A residency
        that merely RELOCATED mid-compile still commits — the kernel is
        placement-free — and is rebound to the routes as they stand now."""
        with self._lock:
            if not self.fabric.same_residency(pending.rid,
                                              pending.generation):
                self.stats.stale_downloads += 1
                return None
            self.cache.insert_compiled(pending.key, exe, seconds)
            self.fabric.add_cache_key(pending.rid, pending.key)
            self.fabric.record_download_cost(pending.rid, seconds)
            self._persist_artifact_locked(pending.key, exe)
            res = self.fabric.get(pending.rid)
            base = pending.base
            if res.generation != pending.generation:
                base = self._base_acc(res.graph, res)   # relocated: new routes
            return dataclasses.replace(
                base, fn=interp.bind_routes(exe, base.routes))

    def prefetch(self, jitted: "JitAssembled", *args) -> DownloadHandle | None:
        """Engine-level prefetch hint: download ``jitted``'s bitstream for
        this signature before traffic needs it.  Equivalent to
        ``jitted.prefetch(*args)``; ``args`` may be concrete arrays or
        ``jax.ShapeDtypeStruct`` pytrees."""
        if jitted.overlay is not self:
            raise ValueError(
                "jitted wrapper belongs to a different overlay")
        return jitted.prefetch(*args)

    def drain(self, timeout: float | None = None) -> bool:
        """Wait until no background download is queued or running (and all
        completion swaps have been delivered)."""
        return self.scheduler.drain(timeout)

    def close(self, *, drain_timeout: float | None = None) -> None:
        """End-of-life for the download pipeline: cancel outstanding
        downloads and retire the scheduler's worker threads.  The overlay
        itself keeps serving — synchronous paths are unaffected, and async
        jit misses permanently serve their fallback (no new downloads
        start).  Optional: idle workers also expire on their own.

        ``drain_timeout`` overrides the constructor's ``drain_timeout``
        for this close; a timed-out drain warns with the undrained job
        count instead of returning silently.

        With a store attached, queued persists drain FIRST (shutdown
        flushes the queue, which would cancel them) and the measurement
        ledger gets a final save — the whole point of closing cleanly is
        the next boot finding everything on disk."""
        limit = self.drain_timeout if drain_timeout is None else drain_timeout
        if self.store is not None and not self.scheduler.closed:
            if not self.scheduler.drain(timeout=limit):
                logger.warning(
                    "overlay close: %d background job(s) still undrained "
                    "after %.1fs; persisting the ledger anyway",
                    self.scheduler.outstanding(), limit)
            self.store.save_ledger(self.fabric.export_ledger())
        self.scheduler.shutdown(wait=True, timeout=limit)

    # -- explicit PR-region management ----------------------------------------
    def _evict_resident(self, rid: str, *, drop_store: bool = False) -> int:
        """THE evict path: release a resident's tiles, cancel any download
        (or pending relocation rebind) still in flight for it, and drop its
        kernel artifacts + route programs in one motion.  Returns cache
        entries removed.

        ``drop_store`` additionally deletes the resident's on-disk
        bitstreams; pressure reclaims leave them (a reclaimed-then-readmitted
        accelerator re-downloading off disk IS the warm-restart win), while
        an explicit :meth:`evict` call means "gone", disk included."""
        resident = self.fabric.release(rid)
        if resident is None:
            return 0
        # a queued download never runs; a running one is stripped of its
        # right to commit (and the generation guard backstops the race)
        self.scheduler.cancel(rid)
        self.scheduler.cancel(f"relocate:{rid}")
        if resident.spec_job is not None:
            self.scheduler.cancel(resident.spec_job)
        if self.store is not None and resident.cache_keys:
            # in-flight persists must not resurrect the evictee on disk
            # (the _commit_persist liveness guard backstops the race)
            hops = interp.route_hops(resident.graph, resident.placement)
            for k in resident.cache_keys:
                self.scheduler.cancel(f"persist:{k}")
                self.scheduler.cancel(
                    f"persist:{cache_lib.spec_key(k, hops)}")
        # the route-constant tier dies with its resident even when the
        # generic kernel key survives via a sharing sibling
        self._drop_spec_artifacts(resident)
        if resident.tier == "specialized":
            self.cache.spec_stats.despecializations += 1
        self._prefetched.discard(rid)
        self.stats.evictions += 1
        self.cache.evict_routes(rid)
        # kernel artifacts are placement-free and may be SHARED (e.g. two
        # pinnings of one graph): only drop keys no surviving resident owns
        live_keys = {k for r in self.fabric.residents.values()
                     for k in r.cache_keys}
        removed = self.cache.evict_keys(
            [k for k in resident.cache_keys if k not in live_keys])
        if drop_store and self.store is not None:
            for k in resident.cache_keys:
                if k not in live_keys:
                    self.store.delete(k)
                    self.store.delete_prefix(f"{k}|spec|")
        if self.sanitize:
            self._sanity_check()
        return removed

    def evict(self, target: "Graph | str") -> int:
        """Free one accelerator's PR regions AND its cached bitstreams
        (by graph or name — all resident signatures of that name).

        Returns the number of cache entries removed.
        """
        with self._lock:
            name = target.name if isinstance(target, Graph) else str(target)
            removed = 0
            for rid in [r.rid for r in self.fabric.residents.values()
                        if r.name == name]:
                removed += self._evict_resident(rid, drop_store=True)
            # sweep bitstreams with no residency record (jit=False
            # assemblies, pre-eviction leftovers) so evict-by-name stays
            # exhaustive
            removed += self.cache.evict_prefix(f"{name}:")
            if self.store is not None:
                self.store.delete_prefix(f"{name}:")
            return removed

    def defragment(self) -> int:
        """Re-place surviving residents contiguously (most-recently-used
        first) to close occupancy holes left by evictions.

        Moves are **relocations**: the compiled kernel artifacts are
        placement-free, so a moved resident keeps its bitstreams and its
        download ledger — only the per-placement route program is re-emitted
        (microseconds, not a PR download).  All-or-nothing: if any survivor
        fails to re-place, nothing moves, ``stats.defrag_failures`` counts
        the aborted pass and a warning names the blocking resident.
        Returns the number of residents moved.
        """
        with self._lock:
            return self._defragment_locked()

    def _plan_repack(self, on_failure: "Callable[[ResidentAccelerator, PlacementError], bool]"
                     ) -> "list[tuple[ResidentAccelerator, Placement]] | None":
        """The shared re-place planner behind defragment() and
        reconfigure(relocate=True): MRU-first plan over movable residents,
        pinned residents anchoring the packing.  ``on_failure(res, exc)``
        decides what an unplaceable survivor means — return True to skip it
        and keep planning, False to abort (None is returned)."""
        survivors = self.fabric.lru_order()[::-1]   # MRU packs first
        plan: list[tuple[ResidentAccelerator, Placement]] = []
        scratch: set[Coord] = set()
        # pinned residents are immovable: their tiles anchor the packing
        for res in survivors:
            if res.fixed is not None:
                scratch |= res.tiles
        for res in survivors:
            if res.fixed is not None:
                continue
            try:
                pl = place(res.graph, self.grid, self.policy,
                           occupied=scratch, max_tiles=res.tile_budget)
            except PlacementError as exc:
                if on_failure(res, exc):
                    continue
                return None
            plan.append((res, pl))
            scratch |= set(pl.assignment.values())
        return plan

    def _defragment_locked(self) -> int:
        def abort(res: ResidentAccelerator, exc: PlacementError) -> bool:
            self.stats.defrag_failures += 1
            logger.warning(
                "defragment aborted: resident %r (%s, %d tiles, "
                "tile_budget=%s) cannot be re-placed — %s",
                res.rid, res.name, len(res.tiles), res.tile_budget, exc)
            return False                       # all-or-nothing: abort the pass

        plan = self._plan_repack(abort)
        if plan is None:
            return 0
        moved = 0
        plan_rids = tuple(res.rid for res, _ in plan)
        for res, pl in plan:
            if pl.assignment == res.placement.assignment:
                continue
            # relocation keeps kernel artifacts AND any in-flight download:
            # the compile is placement-free, so its commit (guarded by
            # Fabric.same_residency) simply rebinds to the new routes
            self._relocate_resident(res.rid, pl, ignore=plan_rids)
            moved += 1
        if moved:
            self.stats.defrags += 1
            # compaction's whole point is the contiguous steady state:
            # queue the zero-hop fused tier for residents that reached it
            self._enqueue_contiguous_specializations()
        if self.sanitize:
            self._sanity_check()
        return moved

    def reconfigure(self, *, policy: PlacementPolicy | None = None,
                    large_fraction: float | None = None,
                    prefetch: bool = True,
                    relocate: bool = False) -> dict[str, Any]:
        """Full-fabric reconfiguration: flush every resident accelerator
        (tiles AND bitstreams; optionally switching placement policy / tile
        mix), so the next assembly re-places and re-downloads from scratch.
        Cache statistics survive the flush.

        ``relocate=True`` is the relocatable-bitstream alternative: instead
        of flushing, every movable resident is *re-placed under the new
        policy/grid via relocation* — kernel artifacts, the bitstream cache
        and the download ledger all survive, so a policy change costs route
        re-emission, not a fabric-wide re-download.  Residents that no
        longer fit the new configuration are evicted (they would have been
        flushed anyway); pinned residents keep their tiles.

        In-flight background downloads belong to flushed generations: queued
        ones are cancelled and running ones lose their right to commit, so a
        late-arriving bitstream cannot resurrect a flushed resident.  On an
        asynchronous overlay the flush is followed (unless ``prefetch=False``)
        by re-requesting downloads for every signature the jit wrappers have
        seen — the fabric rewarms in the background while fallbacks serve.
        """
        if relocate:
            return self._reconfigure_relocating(policy, large_fraction)
        with self._lock:
            # flushed generations may not commit — cancel/stale them first
            self.scheduler.flush()
            self._prefetched.clear()
            if policy is not None:
                self.policy = policy
            if large_fraction is not None:
                self.grid = TileGrid(self.grid.rows, self.grid.cols,
                                     large_fraction)
            # reset() keeps the generation counter monotonic: handles
            # assembled before the flush must not validate against
            # post-flush re-admissions
            flushed = self.fabric.reset(self.grid)
            self.stats.evictions += len(flushed)
            self.cache.clear()                    # stats survive the flush
            if self.store is not None:
                # a reconfigure drops the registries these bitstreams were
                # placed for: their store entries must not survive to serve
                # a future boot against the old configuration
                for k in {k for r in flushed for k in r.cache_keys}:
                    self.store.delete(k)
                    self.store.delete_prefix(f"{k}|spec|")
            self._last_placement = None
            self.stats.reconfigurations += 1
            if self.async_downloads and prefetch:
                for wrapper in list(self._wrappers):
                    wrapper._prefetch_known()
            if self.sanitize:
                self._sanity_check()
        return self.describe()

    def _reconfigure_relocating(self, policy: PlacementPolicy | None,
                                large_fraction: float | None) -> dict[str, Any]:
        """``reconfigure(relocate=True)``: apply the new policy/grid and
        move every movable resident onto it via relocation."""
        with self._lock:
            if policy is not None:
                self.policy = policy
            if large_fraction is not None:
                self.grid = TileGrid(self.grid.rows, self.grid.cols,
                                     large_fraction)
                self.fabric.grid = self.grid
            def evict_and_continue(res: ResidentAccelerator,
                                   exc: PlacementError) -> bool:
                # no longer fits the new configuration — the flush path
                # would have dropped it too
                self._evict_resident(res.rid)
                return True

            plan = self._plan_repack(evict_and_continue)
            plan_rids = tuple(res.rid for res, _ in plan)
            for res, pl in plan:
                if pl.assignment != res.placement.assignment \
                        or pl.policy is not res.placement.policy:
                    self._relocate_resident(res.rid, pl, ignore=plan_rids)
            self._last_placement = None
            self.stats.reconfigurations += 1
            if self.sanitize:
                self._sanity_check()
        return self.describe()

    # -- introspection ----------------------------------------------------------
    def describe(self) -> dict[str, Any]:
        return {
            "grid": (self.grid.rows, self.grid.cols),
            "large_tiles": len(self.grid.large_coords()),
            "policy": self.policy.value,
            "cache": dataclasses.asdict(self.cache.stats),
            "cached_bitstreams": len(self.cache),
            "route_programs": self.cache.route_programs(),
            "routes": dataclasses.asdict(self.cache.route_stats),
            "specialization": {
                **dataclasses.asdict(self.cache.spec_stats),
                "specialized_artifacts": self.cache.specialized_count(),
                "auto": self._auto_specialize,
                "specialize_after": self.specialize_after,
            },
            "fabric": self.fabric.describe(),
            "dispatch_latency": self.dispatch_hist.summary(),
            "route_cost": self.route_cost_hist.summary(),
            "assemblies": self.stats.assemblies,
            "reconfigurations": self.stats.reconfigurations,
            "traces": self.stats.traces,
            "trace_seconds": self.stats.trace_seconds,
            "downloads": self.stats.downloads,
            "evictions": self.stats.evictions,
            "reclaims": self.stats.reclaims,
            "defrags": self.stats.defrags,
            "relocations": self.stats.relocations,
            "defrag_failures": self.stats.defrag_failures,
            "async_downloads": self.async_downloads,
            "cost_aware_reclaim": self.cost_aware_reclaim,
            "prefetches": self.stats.prefetches,
            "prefetch_hits": self.stats.prefetch_hits,
            "fallback_calls": self.stats.fallback_calls,
            "stale_downloads": self.stats.stale_downloads,
            "scheduler": self.scheduler.describe(),
            "failures": self.failure_ledger(),
            "faults": (self.faults.describe()
                       if self.faults is not None else None),
            "store": (self.store.describe()
                      if self.store is not None else None),
            "cost_model_placement": self.cost_model_placement,
            "autotune_thresholds": self.autotune_thresholds,
            "defrag_threshold": round(self.defrag_threshold, 4),
        }


# -----------------------------------------------------------------------------
# Module-level frontend against a process-wide default fabric
# -----------------------------------------------------------------------------
_DEFAULT_OVERLAY: Overlay | None = None


def default_overlay() -> Overlay:
    """The process-wide 3×3 dynamic overlay behind ``jit_assemble``."""
    global _DEFAULT_OVERLAY
    if _DEFAULT_OVERLAY is None:
        _DEFAULT_OVERLAY = Overlay()
    return _DEFAULT_OVERLAY


def jit(fn: Callable[..., Any] | None = None, *,
        overlay: Overlay | None = None, **kwargs) -> Callable[..., Any]:
    """``overlay.jit`` against ``overlay`` or the process default fabric."""
    ov = overlay if overlay is not None else default_overlay()
    if fn is None:
        return lambda f: ov.jit(f, **kwargs)
    return ov.jit(fn, **kwargs)


def jit_assemble(fn: Callable[..., Any] | None = None, **kwargs):
    """Decorator form of the trace frontend::

        @jit_assemble
        def dot(a, b): return jnp.sum(a * b)

        @jit_assemble(strict=True, overlay=my_overlay)
        def f(x): ...
    """
    return jit(fn, **kwargs)

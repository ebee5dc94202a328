"""Fleet overlay — many fabrics behind the one-overlay API surface.

One :class:`~repro.core.overlay.Overlay` is the paper's story on a single
shared PR fabric.  A :class:`FleetOverlay` takes that story to fleet scale:
it owns N member overlays (device groups or simulated hosts) and presents
the same frontend (``jit`` / ``aot`` / ``assemble`` / ``evict`` /
``reconfigure`` / ``defragment`` / ``describe``), adding the three policies
a multi-fabric deployment needs (DESIGN.md §8):

* **Placement** — a new signature is homed on the member with the best
  *placement score*: free-tile headroom, minus the member's share of the
  recently routed dispatch load, minus the price of displacing its current
  residents (their download-cost EWMA ledger — the signal arXiv 1705.02730
  uses for resource-aware JIT placement).
* **Replication** — a signature whose per-window dispatch rate crosses
  ``replicate_after`` gets a *replica*: its bitstream is background-
  downloaded onto another member via the existing
  :class:`~repro.core.scheduler.DownloadScheduler` **low lane** (a replica
  download never delays a demand download or a relocation).  When traffic
  subsides below ``drain_below`` the extra copies are torn down.
* **Routing** — each dispatch goes to the least-loaded *live* copy
  (fewest in-flight calls, then fewest lifetime dispatches — ties
  round-robin), through a lock-light per-signature :class:`_FleetRecord`
  mirroring the single-overlay ``_DispatchRecord`` fast path: the record's
  replica tuple is swapped atomically by rebalances, and per-dispatch
  validation is the member-level liveness read that already exists.
* **Cross-fabric reclaim** — every member's pressure reclaim prefers
  evicting a resident that has a live copy on another member
  (``Overlay.reclaim_prefer`` -> ``Fabric.reclaim_victim(prefer=...)``):
  the fleet sheds redundancy first and never loses the last copy of a
  signature to make room, while routing fails over to the surviving copy.

The members stay fully functional single overlays — per-member async
downloads, relocation, tiered specialization and cost-aware reclaim all
compose underneath the fleet layer unchanged.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import Any, Callable, Sequence

import jax

from repro.core.fabric import ResidentAccelerator
from repro.core.faults import FaultPlan
from repro.core.graph import Graph
from repro.core.overlay import JitAssembled, Overlay
from repro.core.placement import PlacementError
from repro.core.store import BitstreamStore

__all__ = ["FleetOverlay", "FleetJitAssembled", "FleetStats"]


@dataclasses.dataclass
class FleetStats:
    placements: int = 0          # signatures homed on a member
    replications: int = 0        # replicas downloaded onto extra members
    replica_teardowns: int = 0   # replicas torn down (traffic subsided)
    replicas_lost: int = 0       # copies pruned after member-side reclaim/evict
    failovers: int = 0           # dispatches served off-primary (primary dead)
    rebalances: int = 0          # watermark evaluation passes
    routed: int = 0              # total dispatches routed fleet-wide
    quarantines: int = 0         # members pulled from placement (error burst)
    readmissions: int = 0        # quarantined members returned to service
    evacuations: int = 0         # sole copies re-homed off a dead member
    member_deaths: int = 0       # members declared dead (admin or fault plan)
    dispatch_retries: int = 0    # failed dispatches re-served by another copy


@dataclasses.dataclass
class _MemberHealth:
    """Per-member health ledger driving quarantine and routing bias.

    ``healthy -> quarantined`` when a rebalance window observes at least
    ``quarantine_errors`` new member-side failures; ``quarantined ->
    probation`` after ``quarantine_windows`` consecutive clean windows;
    ``probation -> healthy`` after one more clean window (readmission) or
    back to ``quarantined`` on any error.  ``dead`` is terminal and only
    entered through :meth:`FleetOverlay.kill_member`."""

    state: str = "healthy"       # healthy | probation | quarantined | dead
    last_seen: int = 0           # member error total at the last window edge
    window_errors: int = 0       # errors observed in the last window
    clean_windows: int = 0       # consecutive clean windows while quarantined


@dataclasses.dataclass
class _Replica:
    """One copy of a signature on one member.  ``inflight``/``routed`` are
    the least-loaded routing signals; both are bumped lock-free on the
    dispatch path (estimates, not ledgers — the GIL keeps them sane)."""

    member_index: int
    wrapper: JitAssembled
    routed: int = 0              # dispatches routed here (lifetime)
    inflight: int = 0            # calls currently executing


@dataclasses.dataclass
class _FleetRecord:
    """Lock-light routing record for one (fleet wrapper, signature).

    ``replicas`` is replaced wholesale (tuple swap) by placement /
    replication / teardown / pruning under the fleet lock; the dispatch
    path only ever *reads* one snapshot of it and validates each copy with
    the member-level liveness read — no fleet lock per call."""

    label: str                   # JSON-friendly identity ("name#n")
    sig_key: Any                 # JitAssembled entry-table key (hashable)
    args_spec: tuple             # ShapeDtypeStruct-ified args (replication)
    replicas: tuple[_Replica, ...]
    hits: int = 0                # lifetime dispatches
    window_hits: int = 0         # dispatches since the last rebalance


class FleetJitAssembled:
    """Callable returned by :meth:`FleetOverlay.jit` — the fleet analogue
    of :class:`~repro.core.overlay.JitAssembled`.

    Per signature the wrapper homes the accelerator on one member (the
    placement score decides which), keeps a routing record over its live
    copies, and dispatches each call to the least-loaded one.  Member-level
    wrappers are created lazily, one per member that ever hosts a copy;
    each traces independently (trace cost is per member, paid once)."""

    def __init__(self, fleet: "FleetOverlay", fn: Callable[..., Any], *,
                 strict: bool = False, name: str | None = None,
                 static_argnums: tuple[int, ...] = (),
                 donate_argnums: tuple[int, ...] = (),
                 tile_budget: int | None = None) -> None:
        self.fleet = fleet
        self.fn = fn
        self.strict = strict
        self.name = name or getattr(fn, "__name__", None) or "jit"
        self.static_argnums = tuple(static_argnums)
        self.donate_argnums = tuple(donate_argnums)
        self._tile_budget = tile_budget
        self._records: dict[Any, _FleetRecord] = {}
        self._member_wrappers: dict[int, JitAssembled] = {}
        self.__name__ = self.name
        self.__doc__ = getattr(fn, "__doc__", None)
        fleet._register(self)

    # ``EventLoopEngine.resize`` mutates ``tile_budget`` in place — propagate
    # the new cap to every member-level wrapper so their next dispatch
    # repacks the resident via relocation, exactly like a single overlay.
    @property
    def tile_budget(self) -> int | None:
        return self._tile_budget

    @tile_budget.setter
    def tile_budget(self, value: int | None) -> None:
        self._tile_budget = value
        for w in self._member_wrappers.values():
            w.tile_budget = value

    # -- signature handling (must agree with JitAssembled._sig_key) -----------
    def _split(self, args: tuple):
        if not self.static_argnums:
            return args, ""
        static = {i: args[i] for i in self.static_argnums if i < len(args)}
        dyn = tuple(a for i, a in enumerate(args) if i not in static)
        return dyn, repr(sorted(static.items()))

    def _key(self, args: tuple):
        dyn, static_repr = self._split(args)
        return JitAssembled._sig_key(dyn, static_repr)

    def _member_wrapper(self, idx: int) -> JitAssembled:
        w = self._member_wrappers.get(idx)
        if w is None:
            w = self.fleet.members[idx].jit(
                self.fn, strict=self.strict, name=self.name,
                static_argnums=self.static_argnums,
                donate_argnums=self.donate_argnums,
                tile_budget=self._tile_budget)
            self._member_wrappers[idx] = w
        return w

    def _args_spec(self, args: tuple) -> tuple:
        """Replication needs to re-request this signature later, on another
        member, without keeping the original arrays alive: snapshot the
        args as ``ShapeDtypeStruct`` pytrees (``prefetch`` accepts them).
        ``leaf_signature`` keys on (shape, dtype) only, so the spec'd args
        reproduce the exact entry key of the concrete ones."""
        def leaf(a):
            shape = getattr(a, "shape", None)
            dtype = getattr(a, "dtype", None)
            if shape is None or dtype is None:
                return a                     # non-array leaf: keep verbatim
            return jax.ShapeDtypeStruct(tuple(shape), dtype)

        return tuple(a if i in self.static_argnums else jax.tree.map(leaf, a)
                     for i, a in enumerate(args))

    def _record(self, args: tuple) -> _FleetRecord:
        key = self._key(args)
        rec = self._records.get(key)
        if rec is not None:
            return rec
        fleet = self.fleet
        with fleet._lock:
            rec = self._records.get(key)     # re-check under the lock
            if rec is not None:
                return rec
            idx = fleet._best_member()
            rec = _FleetRecord(
                label=f"{self.name}#{len(self._records)}",
                sig_key=key, args_spec=self._args_spec(args),
                replicas=(_Replica(idx, self._member_wrapper(idx)),))
            self._records[key] = rec
            fleet.stats.placements += 1
            return rec

    # -- public surface -------------------------------------------------------
    def __call__(self, *args):
        return self.fleet._dispatch(self._record(args), args)

    def prefetch(self, *args):
        """Home this signature (placement score) and start its download on
        the chosen member ahead of demand.  ``args`` may be concrete arrays
        or ``jax.ShapeDtypeStruct`` pytrees."""
        return self._record(args).replicas[0].wrapper.prefetch(*args)

    def specialize(self, *args):
        """Request the route-constant specialized tier for the signature's
        primary copy (DESIGN.md §7) — replicas specialize on their own
        members through the usual dispatch-stability triggers."""
        return self._record(args).replicas[0].wrapper.specialize(*args)


class FleetOverlay:
    """N member :class:`~repro.core.overlay.Overlay` fabrics behind the
    single-overlay API surface (DESIGN.md §8).

    Args:
      members: the fleet size (members are built as
        ``Overlay(rows, cols, **overlay_kwargs)``), or an explicit sequence
        of already-constructed overlays (heterogeneous fleets).
      rows/cols: member fabric dimensions (fleet-constructed members only).
      window: dispatches between watermark evaluations ("ticks") — the
        replication controller's sampling period.
      replicate_after: a signature routed at least this many times inside
        one window gains a replica on the best non-hosting member.
      drain_below: a replicated signature routed at most this many times
        inside one window loses one replica (default ``replicate_after/4``
        — hysteresis, so a hovering rate doesn't flap).
      max_replicas: cap on live copies per signature (default: fleet size).
      store/store_path: one shared :class:`~repro.core.store.BitstreamStore`
        for the whole fleet — members persist into (and warm-boot from) a
        single directory.  Sharing one in-process store object gives every
        member the same store lock, so concurrent member persists serialize
        at the index instead of racing on files.
      **overlay_kwargs: forwarded to every fleet-constructed member
        (``async_downloads=True`` gives the fleet background replication).
    """

    def __init__(self, members: "int | Sequence[Overlay]" = 4, *,
                 rows: int = 3, cols: int = 3,
                 window: int = 128,
                 replicate_after: int = 32,
                 drain_below: int | None = None,
                 max_replicas: int | None = None,
                 quarantine_errors: int = 3,
                 quarantine_windows: int = 2,
                 faults: "FaultPlan | None" = None,
                 store: "BitstreamStore | None" = None,
                 store_path: "str | None" = None,
                 **overlay_kwargs: Any) -> None:
        if store is not None and store_path is not None:
            raise ValueError("pass store= or store_path=, not both")
        if store is None and store_path is not None:
            store = BitstreamStore(store_path, faults=faults)
        self.store = store
        self.faults = faults
        if isinstance(members, int):
            if members < 1:
                raise ValueError("a fleet needs at least one member")
            if store is not None:
                overlay_kwargs = dict(overlay_kwargs, store=store)
            if faults is not None:
                overlay_kwargs = dict(overlay_kwargs, faults=faults)
            members = [Overlay(rows, cols, **overlay_kwargs)
                       for _ in range(members)]
        else:
            if overlay_kwargs:
                raise ValueError(
                    "overlay kwargs only apply to fleet-constructed members; "
                    "configure explicit member overlays directly")
            if store is not None:
                raise ValueError(
                    "a fleet store only applies to fleet-constructed "
                    "members; pass store= to the explicit member overlays")
            members = list(members)
            if not members:
                raise ValueError("a fleet needs at least one member")
            stores = {id(m.store) for m in members if m.store is not None}
            if len(stores) == 1:
                self.store = next(m.store for m in members
                                  if m.store is not None)
            if faults is None:
                plans = {id(m.faults) for m in members if m.faults is not None}
                if len(plans) == 1:
                    self.faults = next(m.faults for m in members
                                       if m.faults is not None)
        self.members: list[Overlay] = members
        if window < 1:
            raise ValueError("window must be >= 1")
        if replicate_after < 1:
            raise ValueError("replicate_after must be >= 1")
        self.window = int(window)
        self.replicate_after = int(replicate_after)
        self.drain_below = (max(1, self.replicate_after // 4)
                            if drain_below is None else int(drain_below))
        if self.drain_below >= self.replicate_after:
            raise ValueError("drain_below must be < replicate_after "
                             "(hysteresis)")
        self.max_replicas = (len(members) if max_replicas is None
                             else max(1, min(int(max_replicas), len(members))))
        if quarantine_errors < 1 or quarantine_windows < 1:
            raise ValueError("quarantine_errors and quarantine_windows "
                             "must be >= 1")
        self.quarantine_errors = int(quarantine_errors)
        self.quarantine_windows = int(quarantine_windows)
        self._health = [_MemberHealth() for _ in members]
        # sanitizer rides through from the members (fleet-constructed ones
        # pick it up via **overlay_kwargs / REPRO_SANITIZE): any sanitizing
        # member turns on the fleet-level record checks after rebalance
        self.sanitize = any(m.sanitize for m in members)
        self.stats = FleetStats()
        self._lock = threading.RLock()
        self._wrappers: "weakref.WeakSet[FleetJitAssembled]" = \
            weakref.WeakSet()
        self._dispatches = 0
        self._window_routed = [0] * len(members)     # load score input
        self._routed_total = [0] * len(members)      # describe() ledger
        self._graph_homes: dict[str, int] = {}       # low-level assemble path
        for idx, member in enumerate(self.members):
            member.reclaim_prefer = self._replica_preference(idx)

    # -- member compatibility surface (EventLoopEngine and friends) -----------
    @property
    def grid(self):
        """The member fabric geometry (fleets are homogeneous for sizing
        purposes: per-accelerator tile budgets are *per member fabric*)."""
        return self.members[0].grid

    @property
    def async_downloads(self) -> bool:
        return any(m.async_downloads for m in self.members)

    def _register(self, wrapper: FleetJitAssembled) -> None:
        self._wrappers.add(wrapper)

    # -- placement score ------------------------------------------------------
    def _member_score(self, idx: int) -> float:
        """DESIGN.md §8 placement score.  Three signals, all already
        maintained by the member runtimes:

        ``free``   — free-tile fraction (capacity headroom),
        ``load``   — the member's share of the dispatches routed fleet-wide
                     in the current window (observed traffic),
        ``price``  — expected cost of landing under pressure there: the mean
                     download-cost EWMA of its residents (what a reclaim
                     would pay to re-download), squashed to [0, 1) and
                     scaled by occupancy (a mostly-free member rarely
                     reclaims at all),
        ``latency`` — MEASURED dispatch feedback (DESIGN.md §9): the
                     member's p50 dispatch latency relative to the slowest
                     member's, from the overlay-level histograms.  Exactly
                     0 until dispatches have been recorded, so placement
                     on a cold fleet is unchanged; under traffic a member
                     whose dispatches run slow (contended, unspecialized)
                     is deprioritized for NEW placements.
        ``health`` — failure feedback (DESIGN.md §12): a dead member scores
                     ``-inf`` (never placed on), a quarantined one takes a
                     flat -1 (only used when nothing healthier exists), a
                     probationary one -0.25, and recent window errors are
                     a graded penalty so an erroring-but-not-yet-
                     quarantined member already loses placement ties.
        """
        health = self._health[idx]
        if health.state == "dead":
            return float("-inf")
        fab = self.members[idx].fabric
        free = len(fab.free()) / fab.grid.num_tiles
        total = sum(self._window_routed)
        load = (self._window_routed[idx] / total) if total else 0.0
        residents = list(fab.residents.values())
        costs = [fab.download_cost(r.rid) or r.download_cost
                 for r in residents]
        mean_cost = (sum(costs) / len(costs)) if costs else 0.0
        price = (1.0 - free) * mean_cost / (1.0 + mean_cost)
        score = free - 0.5 * load - 0.5 * price
        p50 = self.members[idx].dispatch_hist.percentile(0.5)
        if p50 > 0.0:
            worst = max(m.dispatch_hist.percentile(0.5)
                        for m in self.members)
            if worst > 0.0:
                score -= 0.25 * (p50 / worst)
        if health.state == "quarantined":
            score -= 1.0
        elif health.state == "probation":
            score -= 0.25
        score -= 0.05 * min(health.window_errors, 10)
        return score

    def _best_member(self, exclude: "frozenset[int] | set[int]" = frozenset(),
                     min_free: int = 0) -> int | None:
        """Highest-scoring candidate.  Dead members score ``-inf`` so they
        are only ever picked when *every* candidate is dead — placement
        degrades (a dead member's overlay still serves residue) rather
        than failing outright."""
        best = None
        for i in range(len(self.members)):
            if i in exclude:
                continue
            if min_free and len(self.members[i].fabric.free()) < min_free:
                continue
            score = self._member_score(i)
            if best is None or score > best[0]:
                best = (score, i)
        return None if best is None else best[1]

    # -- routing --------------------------------------------------------------
    def _copy_state(self, rec: _FleetRecord, rep: _Replica) -> str:
        """``live``    — assembled and currently resident on its member,
        ``pending`` — placed/downloading but not yet (or never) resident,
        ``dead``    — was resident and lost its PR regions (reclaim/evict)."""
        entry = rep.wrapper._entries.get(rec.sig_key)
        if entry is None:
            return "pending"
        acc = entry.acc
        if acc is None:
            return "pending"
        return ("live"
                if self.members[rep.member_index].resident_current(acc)
                else "dead")

    def _route(self, rec: _FleetRecord) -> _Replica:
        """Least-loaded live copy on a non-dead member — healthy members
        outrank quarantined/probationary ones, then fewest in-flight calls,
        then fewest lifetime dispatches (equal-load copies round-robin,
        since routing through one bumps its count past the other).  With no
        routable live copy the primary serves — its member wrapper
        re-downloads or falls back, the single-overlay behavior — unless
        the primary's member is dead, in which case any copy on a living
        member is preferred (its wrapper re-downloads there instead)."""
        replicas = rec.replicas
        primary = replicas[0]
        health = self._health
        if len(replicas) == 1:
            return primary
        best = best_rank = None
        for rep in replicas:
            state = health[rep.member_index].state
            if state == "dead":
                continue
            if self._copy_state(rec, rep) != "live":
                continue
            rank = (0 if state == "healthy" else 1,
                    rep.inflight, rep.routed)
            if best is None or rank < best_rank:
                best, best_rank = rep, rank
        if best is None:
            if health[primary.member_index].state == "dead":
                for rep in replicas:
                    if health[rep.member_index].state != "dead":
                        return rep
            return primary
        if best is not primary and self._copy_state(rec, primary) != "live":
            self.stats.failovers += 1
        return best

    def _dispatch(self, rec: _FleetRecord, args: tuple):
        plan = self.faults
        if plan is not None and plan.member_deaths:
            for idx in plan.members_to_kill(self._dispatches):
                self.kill_member(idx)
        rep = self._route(rec)
        rep.inflight += 1
        member = self.members[rep.member_index]
        fails_before = member.stats.dispatch_failures
        try:
            out = rep.wrapper(*args)
        finally:
            rep.inflight -= 1
        if member.stats.dispatch_failures != fails_before:
            # the routed copy's dispatch failed (the member already served
            # this request from its residue, bit-identically): re-serve
            # through another live copy so the answer comes off fabric and
            # the suspect member sheds load.  The delta check can trip on a
            # concurrent failure of an unrelated signature on the same
            # member — a spurious retry returns the same numbers, so the
            # race is harmless.
            out = self._retry_dispatch(rec, rep, args, out)
        rep.routed += 1
        rec.hits += 1
        rec.window_hits += 1
        self.stats.routed += 1
        self._window_routed[rep.member_index] += 1
        self._routed_total[rep.member_index] += 1
        self._dispatches += 1
        if self._dispatches % self.window == 0:
            self._rebalance()
        return out

    def _retry_dispatch(self, rec: _FleetRecord, failed: _Replica,
                        args: tuple, fallback_out):
        """Dispatch-failure failover (DESIGN.md §12): try one other *live*
        copy on a non-dead member before settling for ``fallback_out`` (the
        residue answer the failed member already produced).  Every path
        returns bit-identical numbers; the retry just keeps the answer
        coming off fabric and counts the failover."""
        for rep in rec.replicas:
            if rep is failed or rep.member_index == failed.member_index:
                continue
            if self._health[rep.member_index].state == "dead":
                continue
            if self._copy_state(rec, rep) != "live":
                continue
            self.stats.dispatch_retries += 1
            rep.inflight += 1
            try:
                return rep.wrapper(*args)
            finally:
                rep.inflight -= 1
        return fallback_out

    # -- replication controller ----------------------------------------------
    def _rebalance(self) -> None:
        """One watermark pass over every routing record: prune copies that
        died underneath us, replicate the hot, drain the cold, reset the
        window counters.  Runs at most once per ``window`` dispatches, on
        the dispatching thread, under the fleet lock."""
        with self._lock:
            self.stats.rebalances += 1
            self._update_health()
            for wrapper in list(self._wrappers):
                for rec in list(wrapper._records.values()):
                    self._rebalance_record(wrapper, rec)
            # replication may have minted live copies since the health pass
            # demoted — sweep again so no quarantined member keeps a
            # primary that has a healthy live stand-in
            for idx, health in enumerate(self._health):
                if health.state == "quarantined":
                    self._demote_member(idx)
            self._window_routed = [0] * len(self.members)
            if self.sanitize:
                from repro.analysis import check as _check

                _check.ensure(_check.check_fleet(self, pruned=True))

    def _rebalance_record(self, wrapper: FleetJitAssembled,
                          rec: _FleetRecord) -> None:
        self._prune_record(rec)
        hits = rec.window_hits
        rec.window_hits = 0
        if hits >= self.replicate_after and \
                len(rec.replicas) < self.max_replicas:
            self._replicate(wrapper, rec)
        elif hits <= self.drain_below and len(rec.replicas) > 1:
            self._teardown_one(rec)

    def _prune_record(self, rec: _FleetRecord) -> None:
        """Drop copies whose residents were reclaimed or evicted member-side
        (cross-fabric reclaim took a replica, or a co-tenant displaced the
        primary).  A live copy is promoted to primary so routing and
        teardown keep operating on copies that actually serve; if *nothing*
        survived, the original primary stays — its wrapper knows how to
        re-download on the next demand."""
        states = [(rep, self._copy_state(rec, rep)) for rep in rec.replicas]
        keep = [rep for rep, st in states if st != "dead"]
        if not keep:
            keep = [rec.replicas[0]]
        lost = len(rec.replicas) - len(keep)
        if lost:
            self.stats.replicas_lost += lost
            # stable partition: live copies first (new primary), pending after
            keep.sort(key=lambda rep:
                      0 if self._copy_state(rec, rep) == "live" else 1)
            rec.replicas = tuple(keep)

    def _primary_resident(self, rec: _FleetRecord
                          ) -> ResidentAccelerator | None:
        primary = rec.replicas[0]
        entry = primary.wrapper._entries.get(rec.sig_key)
        acc = entry.acc if entry is not None else None
        if acc is None:
            return None
        return self.members[primary.member_index].fabric.get(acc.resident_id)

    def _replicate(self, wrapper: FleetJitAssembled,
                   rec: _FleetRecord) -> None:
        """Background-download one more copy of a hot signature onto the
        best member not already hosting it.  The download rides the
        scheduler's LOW lane and must not displace live residents — a
        replica is a luxury, not a demand: members without the footprint
        headroom (the primary's tile count) are skipped outright."""
        res = self._primary_resident(rec)
        if res is None:
            return                       # primary still downloading: next tick
        hosted = {rep.member_index for rep in rec.replicas}
        hosted |= {i for i, h in enumerate(self._health)
                   if h.state in ("dead", "quarantined")}
        idx = self._best_member(exclude=hosted, min_free=len(res.tiles))
        if idx is None:
            return                       # no member has headroom — stay put
        member_wrapper = wrapper._member_wrapper(idx)
        try:
            member_wrapper.prefetch(*rec.args_spec, low=True, reclaim=False)
        except PlacementError:
            return                       # lost the race for the free tiles
        rec.replicas = rec.replicas + (_Replica(idx, member_wrapper),)
        self.stats.replications += 1

    def _teardown_one(self, rec: _FleetRecord) -> None:
        """Traffic subsided: evict the least-useful live replica (never the
        primary slot) and return its tiles + bitstreams to the member."""
        live = [rep for rep in rec.replicas[1:]
                if self._copy_state(rec, rep) == "live"]
        if not live:
            return
        victim = min(live, key=lambda rep: rep.routed)
        entry = victim.wrapper._entries.get(rec.sig_key)
        acc = entry.acc if entry is not None else None
        if acc is not None:
            member = self.members[victim.member_index]
            with member._lock:
                if member.resident_current(acc):
                    member._evict_resident(acc.resident_id)
        rec.replicas = tuple(rep for rep in rec.replicas
                             if rep is not victim)
        self.stats.replica_teardowns += 1

    # -- member health: quarantine, death, evacuation (DESIGN.md §12) ---------
    def _member_errors(self, idx: int) -> int:
        """The member-side failure total the health machine samples: every
        failed dispatch plus every failed download on that overlay."""
        stats = self.members[idx].stats
        return stats.dispatch_failures + stats.download_failures

    def _update_health(self) -> None:
        """One health pass per rebalance window, under the fleet lock:
        sample each living member's error delta and step its state machine
        (see :class:`_MemberHealth`).  Quarantined members also get their
        primaries demoted each pass — copies that went live elsewhere since
        the quarantine take over routing."""
        for idx, health in enumerate(self._health):
            if health.state == "dead":
                continue
            total = self._member_errors(idx)
            delta = total - health.last_seen
            health.last_seen = total
            health.window_errors = delta
            if health.state == "healthy":
                if delta >= self.quarantine_errors:
                    self._quarantine(idx)
            elif health.state == "quarantined":
                if delta == 0:
                    health.clean_windows += 1
                    if health.clean_windows >= self.quarantine_windows:
                        health.state = "probation"
                        health.clean_windows = 0
                else:
                    health.clean_windows = 0
            elif health.state == "probation":
                if delta == 0:
                    health.state = "healthy"
                    self.stats.readmissions += 1
                else:
                    self._quarantine(idx)
            if health.state == "quarantined":
                self._demote_member(idx)

    def _quarantine(self, idx: int) -> None:
        health = self._health[idx]
        health.state = "quarantined"
        health.clean_windows = 0
        self.stats.quarantines += 1
        self._demote_member(idx)

    def _demote_member(self, idx: int) -> None:
        """Move the primary slot off member ``idx`` wherever a live copy
        exists elsewhere.  Sole copies stay (a quarantined member keeps
        serving what only it holds — quarantine gates *placement* and
        routing preference, never availability)."""
        for wrapper in list(self._wrappers):
            for rec in list(wrapper._records.values()):
                reps = rec.replicas
                if not reps or reps[0].member_index != idx:
                    continue
                live = [rep for rep in reps[1:]
                        if rep.member_index != idx
                        and self._health[rep.member_index].state != "dead"
                        and self._copy_state(rec, rep) == "live"]
                if not live:
                    continue
                new_primary = live[0]
                rec.replicas = (new_primary,) + tuple(
                    rep for rep in reps if rep is not new_primary)

    def kill_member(self, idx: int) -> None:
        """Declare member ``idx`` dead — by an operator, a test, or the
        fault plan's ``member_deaths`` schedule.  The member's fabric is
        flushed (its residents are gone, as after a real host loss), every
        sole copy it held is evacuated — re-homed via a fresh download on
        the best surviving member — and the health machine stops placing
        or routing there.  Terminal: dead members are never re-admitted."""
        if not 0 <= idx < len(self.members):
            raise ValueError(f"no member {idx} in a fleet of "
                             f"{len(self.members)}")
        with self._lock:
            health = self._health[idx]
            if health.state == "dead":
                return
            health.state = "dead"
            self.stats.member_deaths += 1
            self._evacuate(idx)
            self.members[idx].reconfigure(prefetch=False)
            self._graph_homes = {rid: home for rid, home
                                 in self._graph_homes.items() if home != idx}

    def _evacuate(self, idx: int) -> None:
        """Re-home every record with a copy on dying member ``idx``: a live
        survivor elsewhere is promoted to primary; a *sole* copy is
        re-downloaded onto the best surviving member (counted in
        ``stats.evacuations``).  Runs before the member flush so copy
        states still reflect the pre-death fabric."""
        for wrapper in list(self._wrappers):
            for rec in list(wrapper._records.values()):
                if not any(rep.member_index == idx for rep in rec.replicas):
                    continue
                off = [rep for rep in rec.replicas if rep.member_index != idx]
                live = [rep for rep in off
                        if self._health[rep.member_index].state != "dead"
                        and self._copy_state(rec, rep) == "live"]
                if live:
                    rec.replicas = tuple(
                        live + [rep for rep in off if rep not in live])
                    continue
                new_idx = self._best_member(exclude={idx})
                if new_idx is None or \
                        self._health[new_idx].state == "dead":
                    if off:
                        rec.replicas = tuple(off)
                    continue             # nowhere living to go: re-place later
                member_wrapper = wrapper._member_wrapper(new_idx)
                try:
                    member_wrapper.prefetch(*rec.args_spec)
                except PlacementError:
                    if off:
                        rec.replicas = tuple(off)
                    continue
                rec.replicas = ((_Replica(new_idx, member_wrapper),)
                                + tuple(off))
                self.stats.evacuations += 1

    def health(self) -> list[dict[str, Any]]:
        """Per-member health snapshot (JSON-friendly)."""
        with self._lock:
            return [{"member": i, "state": h.state,
                     "errors": h.last_seen,
                     "window_errors": h.window_errors}
                    for i, h in enumerate(self._health)]

    def failure_ledger(self) -> dict[str, Any]:
        """Fleet-wide failure accounting: the member ledgers summed, plus
        the fleet layer's own health events.  The serving engines surface
        this through ``metrics()``; the analysis report prints it."""
        totals: dict[str, int] = {}
        for member in self.members:
            for key, value in member.failure_ledger().items():
                totals[key] = totals.get(key, 0) + value
        totals.update(
            quarantines=self.stats.quarantines,
            readmissions=self.stats.readmissions,
            evacuations=self.stats.evacuations,
            member_deaths=self.stats.member_deaths,
            fleet_dispatch_retries=self.stats.dispatch_retries,
            quarantined_members=[i for i, h in enumerate(self._health)
                                 if h.state == "quarantined"],
            dead_members=[i for i, h in enumerate(self._health)
                          if h.state == "dead"],
        )
        return totals

    # -- cross-fabric reclaim preference --------------------------------------
    def _replica_preference(self, idx: int
                            ) -> Callable[[ResidentAccelerator], bool]:
        """The predicate installed as member ``idx``'s
        ``Overlay.reclaim_prefer``: under placement pressure, residents
        that are *copies* — another member holds a live resident serving
        the same fleet record — are sacrificed before any sole copy.
        Runs under the member lock; reads fleet records lock-free (the
        record tuples swap atomically) and never takes the fleet lock, so
        the member->fleet lock order cannot deadlock."""
        def prefer(res: ResidentAccelerator) -> bool:
            return self._has_other_live_copy(idx, res.rid)
        return prefer

    def _has_other_live_copy(self, idx: int, rid: str) -> bool:
        for wrapper in list(self._wrappers):
            for rec in list(wrapper._records.values()):
                mine = other = False
                for rep in rec.replicas:
                    entry = rep.wrapper._entries.get(rec.sig_key)
                    acc = entry.acc if entry is not None else None
                    if acc is None:
                        continue
                    member = self.members[rep.member_index]
                    if not member.resident_current(acc):
                        continue
                    if rep.member_index == idx and acc.resident_id == rid:
                        mine = True
                    elif rep.member_index != idx:
                        other = True
                if mine and other:
                    return True
        return False

    # -- trace-based frontend (the Overlay surface) ---------------------------
    def jit(self, fn: Callable[..., Any] | None = None, *,
            strict: bool = False, name: str | None = None,
            static_argnums: tuple[int, ...] = (),
            donate_argnums: tuple[int, ...] = (),
            tile_budget: int | None = None) -> Callable[..., Any]:
        """Compile a plain JAX function into a fleet-managed accelerator —
        same contract as :meth:`Overlay.jit`, minus tile pinning (``fixed``
        names tiles of one fabric; a fleet places across many)."""
        def wrap(f: Callable[..., Any]) -> FleetJitAssembled:
            return FleetJitAssembled(self, f, strict=strict, name=name,
                                     static_argnums=static_argnums,
                                     donate_argnums=donate_argnums,
                                     tile_budget=tile_budget)
        return wrap if fn is None else wrap(fn)

    def aot(self, fn: Callable[..., Any], *abstract_args,
            strict: bool = False, name: str | None = None,
            tile_budget: int | None = None) -> FleetJitAssembled:
        """Ahead-of-time: home the signature and pay (or start) its
        download before traffic arrives.  Mirrors :meth:`Overlay.aot`."""
        jitted = self.jit(fn, strict=strict, name=name,
                          tile_budget=tile_budget)
        jitted.prefetch(*abstract_args)
        return jitted

    def prefetch(self, jitted: FleetJitAssembled, *args):
        """Fleet-level prefetch hint, mirroring :meth:`Overlay.prefetch`."""
        if jitted.fleet is not self:
            raise ValueError("jitted wrapper belongs to a different fleet")
        return jitted.prefetch(*args)

    # -- low-level Graph path -------------------------------------------------
    def assemble(self, graph: Graph, **kwargs: Any):
        """Assemble a hand-built :class:`Graph` on the fleet: the first
        assembly homes the graph on the best-scoring member; re-assemblies
        stick to that home while it stays resident (the member turns them
        into pure residency hits)."""
        with self._lock:
            avals = tuple(graph.toposorted()[i].aval
                          for i in graph.input_ids)
            rid = self.members[0]._resident_key(graph, avals,
                                                kwargs.get("fixed"))
            home = self._graph_homes.get(rid)
            if home is None or self.members[home].fabric.get(rid) is None:
                home = self._best_member()
                self._graph_homes[rid] = home
                self.stats.placements += 1
            return self.members[home].assemble(graph, **kwargs)

    # -- fabric management ----------------------------------------------------
    def evict(self, target: "Graph | str") -> int:
        """Free an accelerator's PR regions and bitstreams on EVERY member
        (by graph or name), and drop its routing records so the next call
        re-places from scratch.  Returns cache entries removed fleet-wide."""
        name = target.name if isinstance(target, Graph) else str(target)
        with self._lock:
            removed = sum(m.evict(target) for m in self.members)
            for wrapper in list(self._wrappers):
                if wrapper.name == name:
                    wrapper._records.clear()
            for rid in [r for r, h in self._graph_homes.items()
                        if self.members[h].fabric.get(r) is None]:
                del self._graph_homes[rid]
            return removed

    def reconfigure(self, **kwargs: Any) -> dict[str, Any]:
        """Reconfigure every member (same kwargs as
        :meth:`Overlay.reconfigure`).  Routing records survive — copies of
        flushed residents read as pending and re-download on demand."""
        with self._lock:
            for member in self.members:
                member.reconfigure(**kwargs)
            self._graph_homes.clear()
        return self.describe()

    def defragment(self) -> int:
        """Defragment every member fabric; returns total residents moved."""
        return sum(m.defragment() for m in self.members)

    def drain(self, timeout: float | None = None) -> bool:
        """Barrier over every member's download scheduler (replica
        downloads included — they are ordinary low-lane jobs).

        ``timeout`` bounds the WHOLE fleet drain: one shared monotonic
        deadline, each member granted only the time remaining — not a full
        ``timeout`` serially per member (a wedged 8-member fleet answers
        after ``timeout``, not ``8 * timeout``)."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        ok = True
        for member in self.members:
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            ok = member.drain(remaining) and ok
        return ok

    def close(self) -> None:
        for member in self.members:
            member.close()

    # -- introspection --------------------------------------------------------
    def describe(self) -> dict[str, Any]:
        """Aggregated, JSON-serializable fleet report: every member's own
        ``describe()`` plus the fleet layer — per-record replica map (who
        holds a copy, where, is it live, how much was routed there),
        per-member routed-dispatch counts and current placement scores."""
        with self._lock:
            records: dict[str, Any] = {}
            replicas_live = 0
            for wrapper in list(self._wrappers):
                for rec in wrapper._records.values():
                    copies = []
                    for i, rep in enumerate(rec.replicas):
                        state = self._copy_state(rec, rep)
                        if state == "live" and i > 0:
                            replicas_live += 1
                        entry = rep.wrapper._entries.get(rec.sig_key)
                        acc = entry.acc if entry is not None else None
                        copies.append({
                            "member": rep.member_index,
                            "rid": None if acc is None else acc.resident_id,
                            "primary": i == 0,
                            "state": state,
                            "routed": rep.routed,
                            "inflight": rep.inflight,
                        })
                    records[rec.label] = {
                        "name": wrapper.name,
                        "hits": rec.hits,
                        "window_hits": rec.window_hits,
                        "copies": copies,
                    }
            return {
                "members": [m.describe() for m in self.members],
                "store": (self.store.describe()
                          if self.store is not None else None),
                "fleet": {
                    "size": len(self.members),
                    "health": [{"member": i, "state": h.state,
                                "errors": h.last_seen,
                                "window_errors": h.window_errors}
                               for i, h in enumerate(self._health)],
                    "window": self.window,
                    "replicate_after": self.replicate_after,
                    "drain_below": self.drain_below,
                    "max_replicas": self.max_replicas,
                    "replicas": replicas_live,
                    "routed_per_member": list(self._routed_total),
                    "scores": [round(self._member_score(i), 4)
                               for i in range(len(self.members))],
                    "dispatch_p50_us": [
                        round(m.dispatch_hist.percentile(0.5), 3)
                        for m in self.members],
                    "dispatch_p99_us": [
                        round(m.dispatch_hist.percentile(0.99), 3)
                        for m in self.members],
                    "records": records,
                    **dataclasses.asdict(self.stats),
                },
            }

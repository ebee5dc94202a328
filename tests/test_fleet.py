"""Fleet overlay tests: placement, replication, routing, cross-fabric
reclaim, describe() shape stability, fleet-backed serving (DESIGN.md §8)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import smoke_config
from repro.core import FleetOverlay, Overlay
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.serving import EventLoopEngine, Request

X = jnp.arange(8, dtype=jnp.float32)
Y = jnp.ones(8, jnp.float32)


def _fleet(n=2, **kw):
    kw.setdefault("rows", 3)
    kw.setdefault("cols", 3)
    kw.setdefault("window", 8)
    kw.setdefault("replicate_after", 4)
    kw.setdefault("drain_below", 1)
    return FleetOverlay(n, **kw)


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------
def test_distinct_accelerators_spread_across_members():
    fleet = _fleet(2)
    fns = [fleet.jit(lambda x, s=float(i): x * s + s, name=f"acc{i}")
           for i in range(4)]
    for f in fns:
        f(X)
    hosts = {i for i in range(2) if len(fleet.members[i].fabric) > 0}
    assert hosts == {0, 1}           # free-tile score spreads the working set
    assert fleet.stats.placements == 4
    fleet.close()


def test_single_member_fleet_degenerates_to_one_overlay():
    fleet = _fleet(1)
    f = fleet.jit(lambda x: x + 1.0, name="inc")
    np.testing.assert_allclose(np.asarray(f(X)), np.arange(8) + 1.0)
    assert fleet.describe()["fleet"]["routed_per_member"] == [len([1])]
    fleet.close()


def test_fleet_validates_watermarks():
    with pytest.raises(ValueError):
        FleetOverlay(2, replicate_after=4, drain_below=4)   # no hysteresis
    with pytest.raises(ValueError):
        FleetOverlay(0)
    with pytest.raises(ValueError):
        FleetOverlay([Overlay(2, 2)], async_downloads=True)  # kwargs clash


# ---------------------------------------------------------------------------
# replication + routing
# ---------------------------------------------------------------------------
def test_hot_accelerator_replicates_and_routing_splits_load():
    fleet = _fleet(2)
    f = fleet.jit(lambda x: x * 2.0 + 1.0, name="hot")
    for _ in range(40):
        out = f(X)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 2.0 + 1.0)
    d = fleet.describe()["fleet"]
    assert d["replications"] >= 1
    assert d["replicas"] >= 1                      # live right now
    assert all(c > 0 for c in d["routed_per_member"])   # least-loaded split
    (rec,) = d["records"].values()
    states = [c["state"] for c in rec["copies"]]
    assert states.count("live") == 2
    fleet.close()


def test_replica_tears_down_when_traffic_subsides():
    fleet = _fleet(2)
    hot = fleet.jit(lambda x: x * 2.0, name="hot")
    for _ in range(16):
        hot(X)                                 # replicate
    assert fleet.describe()["fleet"]["replicas"] == 1
    cold = fleet.jit(lambda x: x * 3.0, name="cold")
    for _ in range(16):
        cold(X)                                # hot's window goes quiet
    d = fleet.describe()["fleet"]
    assert d["replica_teardowns"] >= 1
    assert d["replicas"] == 1                  # cold replicated, hot drained
    fleet.close()


def test_max_replicas_caps_copies():
    fleet = _fleet(3, max_replicas=2)
    f = fleet.jit(lambda x: x + 2.0, name="hot")
    for _ in range(64):
        f(X)
    d = fleet.describe()["fleet"]
    (rec,) = d["records"].values()
    assert len(rec["copies"]) == 2
    fleet.close()


def test_async_replication_rides_low_lane_and_serves_after_drain():
    fleet = _fleet(2, async_downloads=True)
    f = fleet.jit(lambda x: x * 2.0 + 1.0, name="hot")
    for _ in range(16):
        f(X)
    assert fleet.drain(30.0)                   # primary download lands
    for _ in range(8):
        f(X)                                   # next window requests replica
    assert fleet.drain(30.0)                   # replica download lands
    for _ in range(8):
        out = f(X)                             # routed to the fresh copy
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 2.0 + 1.0)
    d = fleet.describe()["fleet"]
    assert d["replications"] >= 1
    assert d["routed_per_member"][1] > 0 and d["routed_per_member"][0] > 0
    fleet.close()


# ---------------------------------------------------------------------------
# cross-fabric reclaim (the satellite policy test)
# ---------------------------------------------------------------------------
def test_reclaim_takes_replica_before_sole_copy_and_routing_fails_over():
    """Under placement pressure a replicated resident loses its replica
    before ANY sole-copy resident is evicted, and routing fails over to
    the surviving copy with no dropped dispatches."""
    fleet = _fleet(2, rows=2, cols=2, window=4, replicate_after=2,
                   drain_below=1)
    budget = 2
    hot = fleet.jit(lambda x, y: x * y + y, name="hot", tile_budget=budget)
    for _ in range(12):
        hot(X, Y)                              # replicated onto both members
    d = fleet.describe()["fleet"]
    assert [c["state"] for c in d["records"]["hot#0"]["copies"]] \
        == ["live", "live"]
    # freeze the replication controller: no further rebalances, so the only
    # force that can remove a copy below is member-side pressure reclaim
    fleet.window = 1_000_000

    # two sole-copy residents per member (1 tile each): both members full
    soles = [fleet.jit(lambda x, s=float(i): x + s, name=f"sole{i}",
                       tile_budget=budget) for i in range(4)]
    for s in soles:
        s(X)
    assert all(not m.fabric.free() for m in fleet.members)
    sole_rids = {i: {rid for rid, r in
                     fleet.members[i].fabric.residents.items()
                     if r.name.startswith("sole")}
                 for i in range(2)}

    # pressure: a newcomer needs tiles on a full member — the hot replica
    # (live copy elsewhere) must be the victim, never a sole copy
    newcomer = fleet.jit(lambda x: x * 4.0, name="newcomer",
                         tile_budget=budget)
    np.testing.assert_allclose(np.asarray(newcomer(X)), np.arange(8) * 4.0)

    d = fleet.describe()["fleet"]
    states = [c["state"] for c in d["records"]["hot#0"]["copies"]]
    assert states.count("live") == 1           # exactly one hot copy lost
    for i in range(2):                         # every sole copy survived
        assert sole_rids[i] <= set(fleet.members[i].fabric.residents)
    reclaims_before = sum(m.stats.reclaims for m in fleet.members)
    assert reclaims_before >= 1                # the replica WAS reclaimed

    # routing keeps serving off the surviving copy — no dropped dispatches
    for _ in range(6):
        out = hot(X, Y)
    np.testing.assert_allclose(np.asarray(out), np.arange(8) + 1.0)
    assert sum(m.stats.reclaims for m in fleet.members) == reclaims_before
    fleet.close()


# ---------------------------------------------------------------------------
# fleet-wide management surface
# ---------------------------------------------------------------------------
def test_fleet_evict_fans_out_and_clears_records():
    fleet = _fleet(2)
    f = fleet.jit(lambda x: x * 5.0, name="victim")
    for _ in range(16):
        f(X)                                   # resident on both members
    assert fleet.evict("victim") >= 1
    assert all("victim" not in {r.name for r in m.fabric.residents.values()}
               for m in fleet.members)
    assert fleet.describe()["fleet"]["records"] == {}
    out = f(X)                                 # re-places from scratch
    np.testing.assert_allclose(np.asarray(out), np.arange(8) * 5.0)
    fleet.close()


def test_fleet_reconfigure_flushes_members_and_keeps_serving():
    fleet = _fleet(2)
    f = fleet.jit(lambda x: x - 1.0, name="dec")
    f(X)
    d = fleet.reconfigure()
    assert d["fleet"]["size"] == 2
    assert all(len(m.fabric) == 0 for m in fleet.members)
    np.testing.assert_allclose(np.asarray(f(X)), np.arange(8) - 1.0)
    fleet.close()


# ---------------------------------------------------------------------------
# describe(): aggregation + shape stability (the satellite)
# ---------------------------------------------------------------------------
def test_describe_shape_is_stable_and_json_serializable():
    fleet = _fleet(2)
    f = fleet.jit(lambda x: x * 2.0, name="acc")
    for _ in range(12):
        f(X)
    d = fleet.describe()
    json.dumps(d)                              # strictly JSON-serializable
    assert len(d["members"]) == 2
    for m in d["members"]:                     # member describes aggregated
        assert {"fabric", "downloads", "grid"} <= set(m)
    fl = d["fleet"]
    assert {"size", "window", "replicate_after", "drain_below",
            "max_replicas", "replicas", "routed_per_member", "scores",
            "records", "placements", "replications", "replica_teardowns",
            "replicas_lost", "failovers", "rebalances",
            "routed"} <= set(fl)
    assert fl["size"] == 2 and len(fl["routed_per_member"]) == 2
    assert sum(fl["routed_per_member"]) == fl["routed"] == 12
    for rec in fl["records"].values():
        assert {"name", "hits", "window_hits", "copies"} <= set(rec)
        for c in rec["copies"]:
            assert {"member", "rid", "primary", "state", "routed",
                    "inflight"} <= set(c)
            assert c["state"] in ("live", "pending", "dead")
    fleet.close()


# ---------------------------------------------------------------------------
# fleet-backed serving
# ---------------------------------------------------------------------------
def test_serve_engine_on_fleet_matches_single_overlay_tokens():
    cfg = smoke_config("phi3-mini-3.8b")
    params = pm.init(model_spec(cfg), jax.random.PRNGKey(0))
    prompts = [[1, 2, 3, 4], [5, 6, 7], [2, 4, 6]]

    def serve(overlay):
        eng = EventLoopEngine(params, cfg, batch=2, max_len=32,
                              overlay=overlay)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=list(p), max_new_tokens=3))
        return {r.rid: r.out for r in eng.run_until_drained()}

    single = serve(Overlay(3, 3))
    fleet = _fleet(2)
    got = serve(fleet)
    assert got == single                       # bit-identical token streams
    # the prefill chunk and decode
    assert fleet.describe()["fleet"]["placements"] >= 2
    fleet.close()

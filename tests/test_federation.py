"""Planet-scale federation (ISSUE 13).

Region taxonomy, lease/epoch fencing, cross-region store anti-entropy +
the checkpoint fallback read, geo front-door spill with typed shedding,
the new ``kill-region``/``partition`` chaos verbs, ``kt fleet status`` —
and the chaos acceptance drill: two subprocess regions running a real
Checkpointer training job and open-loop serve traffic, the primary
region SIGKILLed mid-step and mid-request, training resumed in the
survivor with zero lost committed steps (fingerprint-verified) and serve
traffic spilled with only typed shedding. ``make test-federation`` runs
this file.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest
import requests

pytestmark = [pytest.mark.level("minimal"), pytest.mark.fed]

from kubetorch_tpu import chaos, federation, telemetry
from kubetorch_tpu.constants import SESSION_HEADER
from kubetorch_tpu.data_store import commands as ds
from kubetorch_tpu.data_store import netpool, ring
from kubetorch_tpu.exceptions import (AdmissionShedError,
                                      DeadlineExceededError, StaleLeaseError,
                                      package_exception,
                                      rehydrate_exception)
from kubetorch_tpu.federation import (GeoFrontDoor, GlobalScheduler,
                                      HttpRegionTarget, LeaseTable,
                                      LocalRegionLeaf, LocalRegionTarget,
                                      RegionBook, XRegionReplicator,
                                      regions as regions_mod,
                                      replication, scheduler as fed_sched,
                                      sim_region, status as fed_status,
                                      topology)
from kubetorch_tpu.resilience import DEADLINE_HEADER
from kubetorch_tpu.train import checkpoint as ck
from tests.assets.store_fleet import SubprocessStoreFleet, ThreadedStoreFleet
from tests.assets.threaded_server import ThreadedAiohttpServer
from kubetorch_tpu.utils.procs import free_port, wait_for_port


@pytest.fixture(autouse=True)
def _fed_isolation(monkeypatch):
    """Fresh routers, no chaos/fleet/topology env leakage per test."""
    for var in ("POD_IP", "KT_STORE_NODES", "KT_CHAOS", "KT_CHAOS_RANK",
                "KT_REGION", "KT_CHAOS_REGION_HOSTS", "KT_FED_REGIONS",
                "KT_FED_STORES", "KT_FED_SELF_REGION", "KT_FED_URL",
                "KT_STORE_SUSPECT_COOLDOWN_S"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    monkeypatch.setenv("KT_STORE_FSYNC", "0")
    ring.reset_rings()
    netpool.reset_breakers()
    chaos.reset_partition_state()
    yield
    ring.reset_rings()
    netpool.reset_breakers()
    chaos.reset_partition_state()


def _tree(leaves=4, n=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"layers": {f"w{i}": rng.standard_normal(n).astype(np.float32)
                       for i in range(leaves)}}


def _spec(fleet) -> str:
    return ",".join(fleet.urls)


# ---------------------------------------------------------------------------
# Chaos verbs: parse + scoping (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def test_parse_kill_region():
    faults = chaos.parse_spec("kill-region@iowa")
    assert len(faults) == 1
    f = faults[0]
    assert (f.kind, f.region, f.op_index, f.signal_no) == \
        ("kill-region", "iowa", 0, 9)
    f2 = chaos.parse_spec("kill-region:12@iowa")[0]
    assert (f2.region, f2.op_index) == ("iowa", 12)
    # no @-suffix: any tagged process
    assert chaos.parse_spec("kill-region")[0].region is None
    with pytest.raises(chaos.ChaosError):
        chaos.parse_spec("kill-region:x@iowa")


def test_parse_partition():
    assert chaos.parse_spec("partition")[0].pct == 1.0
    assert chaos.parse_spec("partition:0.5")[0].pct == 0.5
    # values > 1 read as percentages
    assert chaos.parse_spec("partition:50")[0].pct == 0.5
    with pytest.raises(chaos.ChaosError):
        chaos.parse_spec("partition:nope")
    with pytest.raises(chaos.ChaosError):
        chaos.parse_spec("partition:-3")


def test_region_kill_plan_scoping(monkeypatch):
    monkeypatch.setenv("KT_CHAOS", "kill-region:3@iowa")
    monkeypatch.setenv("KT_REGION", "iowa")
    assert chaos.region_kill_plan() == {3: 9}
    monkeypatch.setenv("KT_REGION", "oregon")
    assert chaos.region_kill_plan() == {}
    # untagged processes are never in any region's blast radius
    monkeypatch.delenv("KT_REGION")
    assert chaos.region_kill_plan() == {}
    # an empty region matches any TAGGED process
    monkeypatch.setenv("KT_CHAOS", "kill-region")
    monkeypatch.setenv("KT_REGION", "oregon")
    assert chaos.region_kill_plan() == {0: 9}


def test_engine_region_fault_scoping(monkeypatch):
    monkeypatch.setenv("KT_REGION", "iowa")
    eng = chaos.ChaosEngine(chaos.parse_spec("kill-region:1@iowa"))
    assert len(eng.region_faults) == 1
    # op 0 passes, op 1 is the kill (engine returns the fault; the
    # middleware is what actually delivers the signal)
    assert eng.next_fault("/kv/x", "GET") is None
    fault = eng.next_fault("/kv/y", "GET")
    assert fault is not None and fault.kind == "kill-region"
    # out-of-scope region: armed nothing
    monkeypatch.setenv("KT_REGION", "oregon")
    eng2 = chaos.ChaosEngine(chaos.parse_spec("kill-region:0@iowa"))
    assert eng2.region_faults == []
    assert eng2.next_fault("/kv/x", "GET") is None


def test_partition_scoping(monkeypatch):
    monkeypatch.setenv("KT_CHAOS", "partition")
    monkeypatch.setenv("KT_CHAOS_REGION_HOSTS", "http://127.0.0.1:7001")
    chaos.reset_partition_state()
    assert not chaos.partitioned("http://127.0.0.1:7001/kv/x")
    assert chaos.partitioned("http://10.9.9.9:7001/kv/x")
    with pytest.raises(requests.exceptions.ConnectionError):
        chaos.maybe_partition("http://10.9.9.9:7001/kv/x")
    chaos.maybe_partition("http://127.0.0.1:7001/kv/x")  # local: no raise
    # pct=0 never drops; seeded pct is deterministic
    monkeypatch.setenv("KT_CHAOS", "partition:0.0")
    chaos.reset_partition_state()
    assert not chaos.partitioned("http://10.9.9.9:7001/kv/x")


def test_partition_blocks_netpool_cross_region(monkeypatch, tmp_path):
    with ThreadedStoreFleet(tmp_path, n=2, node_ttl_s=5.0) as fleet:
        monkeypatch.setenv("KT_CHAOS", "partition")
        monkeypatch.setenv("KT_CHAOS_REGION_HOSTS", fleet.urls[0])
        chaos.reset_partition_state()
        # local node keeps answering
        assert netpool.request(
            "GET", f"{fleet.urls[0]}/health", timeout=5).status_code == 200
        # cross-region node is black-holed BEFORE the retry policy: the
        # live server never sees the request, the client fails fast
        t0 = time.monotonic()
        with pytest.raises(requests.exceptions.ConnectionError,
                           match="partition"):
            netpool.request("GET", f"{fleet.urls[1]}/health", timeout=5)
        assert time.monotonic() - t0 < 1.0


# ---------------------------------------------------------------------------
# Region book taxonomy + config lifts
# ---------------------------------------------------------------------------


def test_region_book_taxonomy():
    book = RegionBook(["east", "west"], ttl_s=0.15)
    assert book.state("east") == federation.ALIVE
    book.mark_failure("east")
    assert book.state("east") == federation.UNREACHABLE
    assert book.usable("east")               # suspect, still attemptable
    assert book.usable_regions() == ["west", "east"]
    time.sleep(0.2)
    assert book.state("east") == federation.DEAD
    assert not book.usable("east")
    assert book.alive_regions() == ["west"]
    book.mark_ok("east")                     # partitions heal
    assert book.state("east") == federation.ALIVE
    st = book.status()
    assert st["east"]["state"] == "Alive"
    assert st["west"]["state"] == "Alive"


def test_config_lifts(monkeypatch):
    # suspect cooldown: auto default = min(node_ttl, 5)
    monkeypatch.setenv("KT_STORE_NODE_TTL_S", "2.0")
    assert ring.suspect_cooldown_s() == 2.0
    monkeypatch.setenv("KT_STORE_SUSPECT_COOLDOWN_S", "0.123")
    assert ring.suspect_cooldown_s() == 0.123
    assert ring.StoreRing("http://x").down_cooldown_s == 0.123
    # federation heartbeat + region TTL
    monkeypatch.setenv("KT_FED_HEARTBEAT_S", "0.5")
    assert fed_sched.heartbeat_s() == 0.5
    monkeypatch.setenv("KT_FED_REGION_TTL_S", "7.5")
    assert regions_mod.region_ttl_s() == 7.5


def test_topology_parsing(monkeypatch):
    monkeypatch.setenv("KT_FED_REGIONS",
                       "east=http://c1:8080, west=http://c2:8080")
    monkeypatch.setenv("KT_FED_STORES",
                       "east=http://s1|http://s2,west=http://s3")
    assert topology.fed_regions() == {"east": "http://c1:8080",
                                      "west": "http://c2:8080"}
    assert topology.fed_stores()["east"] == ["http://s1", "http://s2"]
    assert topology.store_spec("east") == "http://s1,http://s2"
    assert topology.store_spec("nowhere") is None
    assert topology.federated()
    # exclusion by region name and by member URL both work
    assert list(topology.fallback_store_specs("east")) == ["west"]
    assert list(topology.fallback_store_specs("http://s1,http://s2")) \
        == ["west"]
    # self-region never a fallback target
    monkeypatch.setenv("KT_FED_SELF_REGION", "west")
    assert topology.fallback_store_specs("east") == {}


# ---------------------------------------------------------------------------
# Leases: epoch fencing
# ---------------------------------------------------------------------------


def test_lease_grant_validate_and_stale():
    table = LeaseTable()
    e1 = table.grant("ns/job", "east")
    assert e1 == 1
    table.validate("ns/job", "east", 1)
    e2 = table.grant("ns/job", "west")    # migration re-grant
    assert e2 == 2
    table.validate("ns/job", "west", 2)
    with pytest.raises(StaleLeaseError) as ei:
        table.validate("ns/job", "east", 1)
    err = ei.value
    assert (err.workload, err.region, err.epoch) == ("ns/job", "east", 1)
    assert (err.current_region, err.current_epoch) == ("west", 2)
    # right region, stale epoch: still fenced
    with pytest.raises(StaleLeaseError):
        table.validate("ns/job", "west", 1)
    # unknown workload: fenced too
    with pytest.raises(StaleLeaseError):
        table.validate("ns/other", "east", 1)


def test_stale_lease_error_rehydrates():
    err = StaleLeaseError("fenced", workload="ns/job", region="east",
                          epoch=1, current_epoch=3, current_region="west")
    back = rehydrate_exception(package_exception(err))
    assert isinstance(back, StaleLeaseError)
    assert back.workload == "ns/job" and back.current_epoch == 3
    assert back.current_region == "west"


# ---------------------------------------------------------------------------
# Global scheduler: placement, death-driven migration, fencing e2e
# ---------------------------------------------------------------------------


def test_global_scheduler_places_on_best_region():
    big = LocalRegionLeaf("east", capacity={"cpu": 8})
    small = LocalRegionLeaf("west", capacity={"cpu": 1})
    sched = GlobalScheduler([big, small], ttl_s=5.0,
                            heartbeat_interval_s=999)
    sched.heartbeat_once()
    out = sched.place("ns/job", {"device_class": "cpu", "width": 2})
    assert out["region"] == "east" and out["epoch"] == 1
    assert sched.placements["ns/job"]["region"] == "east"
    assert "ns/job" in big.placed
    st = sched.status()
    assert st["regions"]["east"]["state"] == "Alive"
    assert st["placements"]["ns/job"]["epoch"] == 1
    assert st["leases"]["ns/job"]["region"] == "east"


def test_throughput_scores_break_capacity_ties():
    a = LocalRegionLeaf("east", capacity={"v5e": 4},
                        throughput={"ns/job": {"v5e": 1.0}})
    b = LocalRegionLeaf("west", capacity={"v5e": 4},
                        throughput={"ns/job": {"v5e": 9.0}})

    def hb(leaf):
        return lambda: {"capacity": {"v5e": {"free": 4}},
                        "queue_depth": 0,
                        "throughput": leaf.throughput}

    a._heartbeat_fn, b._heartbeat_fn = hb(a), hb(b)
    sched = GlobalScheduler([a, b], ttl_s=5.0, heartbeat_interval_s=999)
    sched.heartbeat_once()
    assert sched.choose_region("ns/job",
                               {"device_class": "v5e", "width": 2}) == "west"


def test_region_death_migrates_and_fences_stale_controller():
    """The lease-fencing acceptance: the partitioned region's stale
    placement attempt is rejected typed, never double-placed."""
    flaky = {"fail": False}

    def east_hb():
        if flaky["fail"]:
            raise ConnectionError("partitioned")
        return {"capacity": {"cpu": {"free": 4}}, "queue_depth": 0,
                "throughput": {}}

    drains = []
    east = LocalRegionLeaf("east", capacity={"cpu": 4},
                           heartbeat_fn=east_hb,
                           drain_fn=lambda w: drains.append(w))
    west = LocalRegionLeaf("west", capacity={"cpu": 4})
    sched = GlobalScheduler([east, west], ttl_s=0.2,
                            heartbeat_interval_s=999)
    sched.heartbeat_once()
    placed = sched.place("ns/train", {"device_class": "cpu", "width": 2})
    assert placed == {"region": "east", "epoch": 1, "placed": True}
    # the partition: east goes dark and stays dark past the TTL
    flaky["fail"] = True
    sched.heartbeat_once()
    assert sched.book.state("east") == federation.UNREACHABLE
    assert sched.placements["ns/train"]["region"] == "east"
    time.sleep(0.25)
    states = sched.heartbeat_once()          # crosses into Dead → migrates
    assert states["east"] == federation.DEAD
    entry = sched.placements["ns/train"]
    assert entry["region"] == "west" and entry["epoch"] == 2
    assert entry["migrated_from"] == "east"
    assert "ns/train" in west.placed
    # nobody can drain a dead region
    assert drains == []
    # the partition heals; east's controller still believes epoch 1 —
    # its placement attempt is fenced with a TYPED error
    flaky["fail"] = False
    sched.heartbeat_once()
    with pytest.raises(StaleLeaseError):
        sched.confirm("ns/train", "east", 1)
    # exactly ONE live placement, in the survivor
    assert [e["region"] for e in sched.placements.values()] == ["west"]
    sched.confirm("ns/train", "west", 2)     # the real holder passes


def test_operator_migration_drains_live_source():
    drains = []
    east = LocalRegionLeaf("east", capacity={"cpu": 4},
                           drain_fn=lambda w: drains.append(w) or 41)
    west = LocalRegionLeaf("west", capacity={"cpu": 4})
    sched = GlobalScheduler([east, west], ttl_s=5.0,
                            heartbeat_interval_s=999)
    sched.heartbeat_once()
    sched.place("ns/job", {"device_class": "cpu", "width": 1},
                region="east")
    out = sched.migrate("ns/job", reason="operator")
    assert drains == ["ns/job"]
    assert out["region"] == "west" and out["epoch"] == 2
    assert out["committed_step"] == 41


def test_http_region_leaf_heartbeat_parses_controller_queue():
    snap = {"policy": "fifo-priority",
            "capacity": {"limited": True,
                         "classes": {"cpu": {"capacity": 8, "used": 2,
                                             "free": 6}}},
            "queue": [{"key": "ns/x"}],
            "throughput": {"ns/x": {"cpu": 3.5}}}

    def factory():
        from aiohttp import web

        async def queue(request):
            return web.json_response(snap)

        app = web.Application()
        app.router.add_get("/controller/queue", queue)
        return app

    with ThreadedAiohttpServer(factory) as srv:
        leaf = federation.HttpRegionLeaf("east", srv.url)
        hb = leaf.heartbeat()
    assert hb["capacity"]["cpu"]["free"] == 6
    assert hb["queue_depth"] == 1
    assert hb["throughput"]["ns/x"]["cpu"] == 3.5


# ---------------------------------------------------------------------------
# Cross-region replication + checkpoint fallback read
# ---------------------------------------------------------------------------


def test_key_tier_ordering():
    assert replication._key_tier("ckpt/job/slot-0/layers/w0") == 0
    assert replication._key_tier("ckpt/job/slot-0.__kt_index__") == 1
    assert replication._key_tier("ckpt/job/__kt_commit__") == 2


def test_xregion_sweep_replicates_and_converges(tmp_path):
    with ThreadedStoreFleet(tmp_path / "east", n=2) as east, \
            ThreadedStoreFleet(tmp_path / "west", n=2) as west:
        tree = _tree(seed=3)
        ds.put("ckpt/fedjob/slot-0", tree, store_url=_spec(east))
        ds.put_json("ckpt/fedjob/__kt_commit__", {"step": 4, "slot": 0},
                    store_url=_spec(east))
        rep = XRegionReplicator(_spec(east), {"west": _spec(west)})
        report = rep.sweep()
        assert report["targets"]["west"]["pushed"] >= 5  # leaves+index+marker
        assert report["targets"]["west"]["failed"] == 0
        assert rep.lag_s["west"] == 0.0
        got = ds.get("ckpt/fedjob/slot-0", store_url=_spec(west))
        assert ck.tree_fingerprint(got) == ck.tree_fingerprint(tree)
        marker = ds.get_json("ckpt/fedjob/__kt_commit__",
                             store_url=_spec(west))
        assert marker == {"step": 4, "slot": 0}
        # converged: the second sweep moves nothing
        report2 = rep.sweep()
        assert report2["targets"]["west"]["pushed"] == 0


def test_xregion_sweep_never_rolls_back_newer_target(tmp_path):
    with ThreadedStoreFleet(tmp_path / "east", n=1) as east, \
            ThreadedStoreFleet(tmp_path / "west", n=1) as west:
        ds.put_json("ckpt/fedjob/__kt_commit__", {"step": 5, "slot": 1},
                    store_url=_spec(east))
        time.sleep(0.05)   # the target's copy is strictly newer
        ds.put_json("ckpt/fedjob/__kt_commit__", {"step": 9, "slot": 1},
                    store_url=_spec(west))
        XRegionReplicator(_spec(east), {"west": _spec(west)}).sweep()
        assert ds.get_json("ckpt/fedjob/__kt_commit__",
                           store_url=_spec(west)) == {"step": 9, "slot": 1}


def test_partition_shows_as_bounded_lag_not_crash(tmp_path, monkeypatch):
    with ThreadedStoreFleet(tmp_path / "east", n=1) as east, \
            ThreadedStoreFleet(tmp_path / "west", n=1) as west:
        ds.put_json("ckpt/j/__kt_commit__", {"step": 1, "slot": 0},
                    store_url=_spec(east))
        monkeypatch.setenv("KT_CHAOS", "partition")
        monkeypatch.setenv("KT_CHAOS_REGION_HOSTS", east.urls[0])
        chaos.reset_partition_state()
        rep = XRegionReplicator(_spec(east), {"west": _spec(west)})
        report = rep.sweep()     # degrades to recorded lag, no raise
        assert report["targets"]["west"]["failed"] == 1
        assert rep.lag_s["west"] > 0.0
        # partition heals → next sweep converges and the lag collapses
        monkeypatch.delenv("KT_CHAOS")
        chaos.reset_partition_state()
        report2 = rep.sweep()
        assert report2["targets"]["west"]["pushed"] == 1
        assert rep.lag_s["west"] == 0.0


def test_checkpoint_fallback_read_after_region_death(tmp_path, monkeypatch):
    """The satellite acceptance: marker committed in A, region A dead,
    restore in B succeeds and fingerprint-matches."""
    east = ThreadedStoreFleet(tmp_path / "east", n=2)
    with east, ThreadedStoreFleet(tmp_path / "west", n=2) as west:
        ckpt = ck.Checkpointer("ckpt/fedjob", store_url=_spec(east))
        tree = _tree(seed=11)
        ckpt.save(tree, 7)
        want_fp = ck.tree_fingerprint(tree)
        XRegionReplicator(_spec(east), {"west": _spec(west)}).sweep()
        monkeypatch.setenv(
            "KT_FED_STORES",
            f"east={'|'.join(east.urls)},west={'|'.join(west.urls)}")
        # region A dies wholesale
        for i in range(east.n):
            east.stop_node(i)
        ring.reset_rings()
        # commit_info on the DEAD configured ring falls back cross-region
        info = ck.commit_info("ckpt/fedjob", store_url=_spec(east))
        assert info == {"step": 7, "slot": 0}
        restored = ck.Checkpointer("ckpt/fedjob",
                                   store_url=_spec(east)).restore()
        assert restored is not None
        got, step = restored
        assert step == 7
        assert ck.tree_fingerprint(got) == want_fp


def test_unfederated_dead_store_still_raises(tmp_path):
    east = ThreadedStoreFleet(tmp_path / "east", n=1)
    with east:
        ds.put_json("ckpt/solo/__kt_commit__", {"step": 1, "slot": 0},
                    store_url=_spec(east))
    # fleet gone, NO federation topology: a dead store must surface as an
    # error, never as "no checkpoint — start from step 0"
    ring.reset_rings()
    with pytest.raises(Exception):
        ck.commit_info("ckpt/solo", store_url=east.urls[0])


# ---------------------------------------------------------------------------
# Geo front door: spill, re-hash, typed shedding
# ---------------------------------------------------------------------------


def _run(coro):
    return asyncio.run(coro)


def test_geo_spills_on_region_death_and_stays_typed():
    calls = {"east": 0, "west": 0}

    async def dead(payload, headers, timeout):
        calls["east"] += 1
        raise ConnectionError("connection refused")

    async def alive(payload, headers, timeout):
        calls["west"] += 1
        return {"region": "west", "ok": True}

    door = GeoFrontDoor([LocalRegionTarget("east", dead),
                         LocalRegionTarget("west", alive)],
                        local_region="east")
    out = _run(door.dispatch({"prompt_len": 8, "new_tokens": 2}))
    assert out["region"] == "west"
    assert calls == {"east": 1, "west": 1}
    assert door.book.state("east") == federation.UNREACHABLE
    # with both regions dark the client STILL gets a typed error
    async def dead2(payload, headers, timeout):
        raise ConnectionError("refused")

    door2 = GeoFrontDoor([LocalRegionTarget("east", dead2),
                          LocalRegionTarget("west", dead2)],
                         local_region="east")
    with pytest.raises(AdmissionShedError) as ei:
        _run(door2.dispatch({"prompt_len": 8, "new_tokens": 2}))
    assert ei.value.reason == "region_down"


def test_geo_spill_preserves_typed_shed_when_everyone_sheds():
    async def shedding(payload, headers, timeout):
        raise AdmissionShedError("full", reason="queue_full", tier="batch",
                                 queue_depth=9, retry_after=0.5)

    door = GeoFrontDoor([LocalRegionTarget("east", shedding),
                         LocalRegionTarget("west", shedding)],
                        local_region="east")
    with pytest.raises(AdmissionShedError) as ei:
        _run(door.dispatch({"prompt_len": 8, "new_tokens": 2}))
    assert ei.value.reason == "queue_full"     # the routers' own verdict


def test_geo_shed_spills_keyless_traffic():
    async def shedding(payload, headers, timeout):
        raise AdmissionShedError("full", reason="queue_full")

    async def alive(payload, headers, timeout):
        return {"region": "west"}

    door = GeoFrontDoor([LocalRegionTarget("east", shedding),
                         LocalRegionTarget("west", alive)],
                        local_region="east")
    assert _run(door.dispatch({"prompt_len": 8,
                               "new_tokens": 2}))["region"] == "west"


def test_geo_affinity_rehashes_to_survivor():
    served = []

    def mk(name):
        async def fn(payload, headers, timeout):
            served.append(name)
            return {"region": name}
        return fn

    book = RegionBook(["east", "west"], ttl_s=0.05)
    door = GeoFrontDoor([LocalRegionTarget("east", mk("east")),
                         LocalRegionTarget("west", mk("west"))],
                        local_region="east", book=book)
    headers = {SESSION_HEADER: "sess-42"}
    home = _run(door.dispatch({"prompt_len": 4, "new_tokens": 1},
                              headers))["region"]
    # sticky while the home region lives
    assert _run(door.dispatch({"prompt_len": 4, "new_tokens": 1},
                              headers))["region"] == home
    # home dies → the key re-hashes to the survivor, consistently
    book.mark_failure(home)
    time.sleep(0.1)
    assert book.state(home) == federation.DEAD
    other = {"east": "west", "west": "east"}[home]
    for _ in range(3):
        assert _run(door.dispatch({"prompt_len": 4, "new_tokens": 1},
                                  headers))["region"] == other


def test_geo_spill_under_partition_via_http(monkeypatch):
    """The satellite acceptance: geo-spill preserves typed shedding under
    partition — cross-region requests black-holed at netpool, the spill
    still answers, and overload still sheds typed."""
    with ThreadedAiohttpServer(
            lambda: sim_region.create_sim_region_app(
                "east", replicas=1, slots=1, queue_max=1)) as east_srv, \
        ThreadedAiohttpServer(
            lambda: sim_region.create_sim_region_app(
                "west", replicas=2, slots=4)) as west_srv:
        monkeypatch.setenv("KT_CHAOS", "partition")
        # east is cross-region from this client's vantage: only west local
        monkeypatch.setenv("KT_CHAOS_REGION_HOSTS", west_srv.url)
        chaos.reset_partition_state()
        door = GeoFrontDoor(
            [HttpRegionTarget("east", east_srv.url),
             HttpRegionTarget("west", west_srv.url)],
            local_region="east")
        out = _run(door.dispatch({"prompt_len": 4, "new_tokens": 1}))
        assert out["region"] == "west"
        assert door.book.state("east") == federation.UNREACHABLE
        # expired deadline through the spill path: typed 504, rehydrated
        with pytest.raises(DeadlineExceededError):
            _run(door.dispatch(
                {"prompt_len": 4, "new_tokens": 1},
                {DEADLINE_HEADER: f"{time.time() - 1:.6f}"}))


def test_sim_region_surface():
    with ThreadedAiohttpServer(
            lambda: sim_region.create_sim_region_app(
                "east", replicas=1, slots=2)) as srv:
        r = requests.post(f"{srv.url}/generate",
                          json={"prompt_len": 4, "new_tokens": 2},
                          timeout=10)
        assert r.status_code == 200
        body = r.json()
        assert body["region"] == "east" and body["tokens"] == 2
        assert body["ttft_s"] > 0
        # expired deadline → typed 504 body that rehydrates client-side
        r = requests.post(
            f"{srv.url}/generate",
            json={"prompt_len": 4, "new_tokens": 2},
            headers={DEADLINE_HEADER: f"{time.time() - 1:.6f}"},
            timeout=10)
        assert r.status_code == 504
        assert isinstance(rehydrate_exception(r.json()),
                          DeadlineExceededError)
        h = requests.get(f"{srv.url}/health", timeout=10).json()
        assert h["region"] == "east" and "router" in h


# ---------------------------------------------------------------------------
# kt fleet status (CLI satellite)
# ---------------------------------------------------------------------------


def test_fleet_status_coordinator_mode_and_cli():
    east = LocalRegionLeaf("east", capacity={"cpu": 4})
    west = LocalRegionLeaf("west", capacity={"cpu": 4})
    sched = GlobalScheduler([east, west], ttl_s=5.0,
                            heartbeat_interval_s=999)
    sched.heartbeat_once()
    sched.place("ns/job", {"device_class": "cpu", "width": 1})
    with ThreadedAiohttpServer(lambda: fed_status.fed_app(sched)) as srv:
        snap = federation.fleet_status(fed_url=srv.url)
        assert snap["source"] == "coordinator"
        assert set(snap["regions"]) == {"east", "west"}
        assert snap["placements"]["ns/job"]["epoch"] == 1

        from click.testing import CliRunner

        from kubetorch_tpu.cli import cli as kt_cli

        res = CliRunner().invoke(kt_cli,
                                 ["fleet", "status", "--url", srv.url])
        assert res.exit_code == 0, res.output
        assert "east" in res.output and "west" in res.output
        assert "ns/job" in res.output
        res_json = CliRunner().invoke(
            kt_cli, ["fleet", "status", "--url", srv.url, "--json"])
        assert res_json.exit_code == 0
        assert json.loads(res_json.output)["source"] == "coordinator"


def test_fleet_status_probe_mode(monkeypatch):
    snap = {"policy": "fifo-priority",
            "capacity": {"classes": {"cpu": {"capacity": 4, "used": 1,
                                             "free": 3}}},
            "queue": []}

    def factory():
        from aiohttp import web

        async def queue(request):
            return web.json_response(snap)

        app = web.Application()
        app.router.add_get("/controller/queue", queue)
        return app

    with ThreadedAiohttpServer(factory) as srv:
        monkeypatch.setenv(
            "KT_FED_REGIONS",
            f"east={srv.url},west=http://127.0.0.1:1")  # west: dead port
        out = federation.fleet_status()
    assert out["source"] == "probe"
    assert out["regions"]["east"]["state"] == "Alive"
    assert out["regions"]["east"]["queue_depth"] == 0
    # probe mode has no memory: a dark region is Unreachable, never Dead
    assert out["regions"]["west"]["state"] == "Unreachable"


def test_controller_scheduler_snapshot_exports_throughput():
    from types import SimpleNamespace

    from kubetorch_tpu.controller.scheduler import Scheduler

    state = SimpleNamespace(cluster_config={}, persister=None,
                            workloads={}, record_event=lambda *a, **k: None)
    sched = Scheduler(state, capacity={"cpu": 4})
    sched.note_throughput("ns/job", "cpu", execute_sum=2.0,
                          execute_count=10.0)
    snap = sched.snapshot()
    assert snap["throughput"]["ns/job"]["cpu"] == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# The chaos acceptance drill (slow): kill an entire region mid-everything
# ---------------------------------------------------------------------------


def _read_jsonl(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _wait_for(pred, timeout=60.0, interval=0.1, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        val = pred()
        if val:
            return val
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.slow
@pytest.mark.chaos
def test_kill_region_drill_resumes_training_and_spills_serve(
        tmp_path, monkeypatch):
    """The ISSUE 13 acceptance drill.

    Two subprocess regions (each: a 2-node store fleet + a sim-region
    serve gateway; the primary also runs a real Checkpointer training
    job). The cross-region pump replicates primary→survivor. Then the
    primary region dies — the trainer SIGKILLs itself MID-STEP via the
    ``kill-region`` plan, the gateway SIGKILLs itself MID-REQUEST via the
    armed middleware verb, the store fleet is SIGKILLed outright — and:

    - the global scheduler's heartbeats declare the region Dead and
      migrate: a new trainer starts in the survivor and resumes from the
      last committed checkpoint with ZERO lost committed steps,
      fingerprint-verified;
    - serve traffic spills to the survivor with only TYPED shedding —
      no raw connection error ever reaches the client.
    """
    KILL_STEP = 4            # trainer dies mid-step 4 → last commit is 3
    PRE_KILL_REQS = 6        # gateway dies serving request PRE_KILL_REQS
    FINAL_STEP = 6

    primary = SubprocessStoreFleet(
        tmp_path / "primary", n=2, node_ttl_s=1.0,
        extra_env={"KT_REGION": "primary"})
    survivor = SubprocessStoreFleet(
        tmp_path / "survivor", n=2, node_ttl_s=1.0,
        extra_env={"KT_REGION": "survivor"})
    gate_file = str(tmp_path / "gate")
    result_a = str(tmp_path / "trainer_primary.jsonl")
    result_b = str(tmp_path / "trainer_survivor.jsonl")
    sim_procs = {}

    def start_sim(region, port, chaos_spec=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["KT_REGION"] = region
        env.pop("KT_CHAOS", None)
        if chaos_spec:
            env["KT_CHAOS"] = chaos_spec
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.federation.sim_region",
             "--port", str(port), "--region", region, "--replicas", "2",
             "--slots", "4", "--prefill-us-per-tok", "50",
             "--decode-us-per-tok", "100"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        assert wait_for_port("127.0.0.1", port, timeout=30)
        sim_procs[region] = proc
        return f"http://127.0.0.1:{port}"

    def start_trainer(region, store_spec, result, resume=False,
                      chaos_spec=None, extra=()):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["KT_REGION"] = region
        env.pop("KT_CHAOS", None)
        env.pop("KT_STORE_NODES", None)
        if chaos_spec:
            env["KT_CHAOS"] = chaos_spec
        env["KT_FED_STORES"] = (
            f"primary={'|'.join(primary.urls)},"
            f"survivor={'|'.join(survivor.urls)}")
        args = [sys.executable, "tests/assets/fed_trainer.py",
                "--base-key", "ckpt/fedjob", "--store", store_spec,
                "--steps", str(FINAL_STEP), "--result", result,
                *extra]
        if resume:
            args.append("--resume")
        return subprocess.Popen(args, env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    with primary, survivor:
        url_a = start_sim("primary", free_port(),
                          chaos_spec=f"kill-region:{PRE_KILL_REQS}@primary")
        url_b = start_sim("survivor", free_port())
        try:
            # -- the training job in the primary, armed to die mid-step --
            trainer = start_trainer(
                "primary", _spec(primary), result_a,
                chaos_spec=f"kill-region:{KILL_STEP}@primary",
                extra=("--gate-step", str(KILL_STEP - 1),
                       "--gate-file", gate_file))
            committed = _wait_for(
                lambda: [r for r in _read_jsonl(result_a)
                         if r.get("committed") == KILL_STEP - 1],
                what="primary trainer to commit the pre-kill step")
            fp_by_step = {r["committed"]: r["fingerprint"]
                          for r in _read_jsonl(result_a)
                          if "committed" in r}
            assert committed

            # -- replicate primary → survivor until marker parity --------
            rep = XRegionReplicator(_spec(primary),
                                    {"survivor": _spec(survivor)},
                                    prefixes=("ckpt/",))
            _wait_for(
                lambda: rep.sweep()["targets"]["survivor"]["failed"] == 0
                and (ds.get_json("ckpt/fedjob/__kt_commit__",
                                 store_url=_spec(survivor)) or {}
                     ).get("step") == KILL_STEP - 1,
                timeout=30, what="replication parity on the marker")

            # -- open-loop serve traffic through the geo front door ------
            door = GeoFrontDoor(
                [HttpRegionTarget("primary", url_a),
                 HttpRegionTarget("survivor", url_b)],
                local_region="primary",
                book=RegionBook(["primary", "survivor"], ttl_s=1.0))
            outcomes = {"ok_primary": 0, "ok_survivor": 0, "typed": 0,
                        "raw": 0}

            async def one_request(i):
                # keyless on purpose: local-first routing makes the
                # primary gateway's op counter — and therefore the armed
                # kill-region index — deterministic
                try:
                    out = await door.dispatch(
                        {"prompt_len": 8, "new_tokens": 2})
                    outcomes["ok_" + out["region"]] += 1
                except (AdmissionShedError, DeadlineExceededError):
                    outcomes["typed"] += 1
                except Exception:  # noqa: BLE001 — the forbidden bucket
                    outcomes["raw"] += 1

            async def pre_kill_traffic():
                for i in range(PRE_KILL_REQS):
                    await one_request(i)

            asyncio.run(pre_kill_traffic())
            assert outcomes["raw"] == 0

            # -- kill the region: trainer mid-step, gateway mid-request,
            #    stores outright ----------------------------------------
            with open(gate_file, "w") as f:
                f.write("go")
            trainer.wait(timeout=60)
            assert trainer.returncode == -signal.SIGKILL
            records_a = _read_jsonl(result_a)
            assert any(r.get("dying_at_step") == KILL_STEP
                       for r in records_a)
            assert max(r["committed"] for r in records_a
                       if "committed" in r) == KILL_STEP - 1

            async def kill_window_traffic():
                # the armed gateway dies serving one of these requests —
                # mid-request, exactly like a SIGKILLed pod; the door must
                # absorb the reset and spill
                for i in range(8):
                    await one_request(100 + i)

            asyncio.run(kill_window_traffic())
            assert sim_procs["primary"].poll() is not None, \
                "armed kill-region verb should have killed the gateway"
            for i in range(primary.n):
                primary.kill_node(i)
            ring.reset_rings()

            # -- the global scheduler notices and migrates ----------------
            resumed = {}

            def place_in_survivor(workload, spec, epoch):
                resumed["proc"] = start_trainer(
                    "survivor", _spec(survivor), result_b, resume=True)
                return {"placed": True}

            def probe(urls):
                def hb():
                    r = requests.get(f"{urls[0]}/ring", timeout=3)
                    r.raise_for_status()
                    return {"capacity": {"cpu": {"free": 4}},
                            "queue_depth": 0, "throughput": {}}
                return hb

            sched = GlobalScheduler(
                [LocalRegionLeaf("primary",
                                 heartbeat_fn=probe(primary.urls)),
                 LocalRegionLeaf("survivor",
                                 heartbeat_fn=probe(survivor.urls),
                                 place_fn=place_in_survivor)],
                ttl_s=1.0, heartbeat_interval_s=999)
            sched.heartbeat_once()
            sched.leases.grant("ns/fedjob", "primary")
            sched.placements["ns/fedjob"] = {
                "region": "primary", "epoch": 1,
                "spec": {"device_class": "cpu", "width": 1},
                "migrations": 0}

            def dead_and_migrated():
                sched.heartbeat_once()
                return sched.book.state("primary") == federation.DEAD \
                    and "proc" in resumed
            _wait_for(dead_and_migrated, timeout=20,
                      what="region death detection + migration")
            assert sched.placements["ns/fedjob"]["region"] == "survivor"
            assert sched.placements["ns/fedjob"]["epoch"] == 2
            # the dead region's stale epoch is fenced, typed
            with pytest.raises(StaleLeaseError):
                sched.confirm("ns/fedjob", "primary", 1)

            # -- zero lost committed steps, fingerprint-verified ----------
            _wait_for(lambda: any(r.get("done")
                                  for r in _read_jsonl(result_b)),
                      timeout=90, what="survivor trainer to finish")
            records_b = _read_jsonl(result_b)
            restored = next(r for r in records_b if "restored" in r)
            assert restored["restored"] == KILL_STEP - 1
            assert restored["fingerprint"] == fp_by_step[KILL_STEP - 1]
            assert max(r["committed"] for r in records_b
                       if "committed" in r) == FINAL_STEP

            # -- post-kill serve traffic: spilled, typed only -------------
            async def post_kill_traffic():
                for i in range(6):
                    await one_request(200 + i)

            asyncio.run(post_kill_traffic())
            assert outcomes["raw"] == 0, outcomes
            assert outcomes["ok_survivor"] > 0, outcomes
            assert resumed["proc"].wait(timeout=30) == 0
        finally:
            for proc in sim_procs.values():
                if proc.poll() is None:
                    proc.kill()
            for proc in (locals().get("trainer"),
                         (locals().get("resumed") or {}).get("proc")):
                if proc is not None and proc.poll() is None:
                    proc.kill()

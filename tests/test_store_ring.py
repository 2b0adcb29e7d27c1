"""Replicated, ring-sharded data store (ISSUE 7).

Placement determinism, R-way replica forwarding at write-quorum, proxy
reads, ring-epoch safety under membership change, TTL-driven
re-replication — and the chaos acceptance: SIGKILL a store node mid
multi-leaf put and mid pull_tree with ZERO client-visible failures.
``make test-ring`` runs this file.
"""

import hashlib
import json
import os
import time
from urllib.parse import quote, unquote

import numpy as np
import pytest
import requests

pytestmark = [pytest.mark.level("minimal"), pytest.mark.chaos]

from kubetorch_tpu.data_store import commands as ds
from kubetorch_tpu.data_store import netpool, ring
from kubetorch_tpu.data_store.store_server import RingState
from kubetorch_tpu.exceptions import (RingEpochMismatch, package_exception,
                                      rehydrate_exception)
from kubetorch_tpu.train import checkpoint as ck
from tests.assets.store_fleet import (SubprocessStoreFleet,
                                      ThreadedStoreFleet)
from tests.assets.threaded_server import ThreadedAiohttpServer


@pytest.fixture(autouse=True)
def _ring_isolation(monkeypatch):
    """Every test starts with a fresh router cache, no fleet env leakage,
    and the peer fan-out off (POD_IP drives it; these tests cover the
    store ring, not P2P)."""
    monkeypatch.delenv("POD_IP", raising=False)
    monkeypatch.delenv("KT_STORE_NODES", raising=False)
    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    monkeypatch.setenv("KT_STORE_FSYNC", "0")
    ring.reset_rings()
    netpool.reset_breakers()
    yield
    ring.reset_rings()
    netpool.reset_breakers()


def _use_fleet(monkeypatch, fleet) -> None:
    for k, v in fleet.client_env().items():
        monkeypatch.setenv(k, v)
    ring.reset_rings()


def _kv_copies(fleet, key: str):
    """Which LIVE nodes hold ``key`` locally (strictly-local HEADs)."""
    holders = []
    for i, url in enumerate(fleet.urls):
        if getattr(fleet, "servers", None) is not None \
                and fleet.servers[i] is None:
            continue
        try:
            r = requests.head(f"{url}/kv/{quote(key, safe='/')}",
                              headers={ring.REPLICATED_HEADER: "1"},
                              timeout=10)
        except requests.RequestException:
            continue
        if r.status_code == 200:
            holders.append(url)
    return holders


def _tree(leaves=8, n=64, seed=0):
    rng = np.random.default_rng(seed)
    return {"layers": {f"w{i:02d}": rng.standard_normal(n).astype(np.float32)
                       for i in range(leaves)}}


# ---------------------------------------------------------------------------
# Placement units: deterministic, order-independent, quote/escape-stable
# ---------------------------------------------------------------------------


def test_ring_placement_order_independent():
    nodes = [f"http://10.0.0.{i}:8873" for i in range(5)]
    a = ring.HashRing(nodes)
    b = ring.HashRing(list(reversed(nodes)))
    c = ring.HashRing(nodes[2:] + nodes[:2])
    for key in ("ckpt/slot-0/layers/wq", "weights/step-0001/w", "x"):
        assert a.walk(key) == b.walk(key) == c.walk(key)
        assert a.replicas(key, 2) == a.walk(key)[:2]
        assert len(set(a.replicas(key, 3))) == 3


def test_ring_placement_spreads_keys():
    nodes = [f"http://10.0.0.{i}:8873" for i in range(3)]
    r = ring.HashRing(nodes)
    primaries = {r.walk(f"bench/leaf/{i}")[0] for i in range(64)}
    assert primaries == set(nodes), "64 keys must hit every primary"


def test_urlkey_quoted_keys_hash_identically():
    """The cross-node hash-stability contract: the wire form
    (``netpool.urlkey``) and disk form (``escape_key``) of a key must
    place EXACTLY like the raw key on every node, or two nodes would
    route one key to two replica sets."""
    from kubetorch_tpu.data_store import durability

    nodes = [f"http://10.0.0.{i}:8873" for i in range(4)]
    r = ring.HashRing(nodes)
    for key in ("plain/key", "sp ace/key", "pc%2Fnt/key", "uni/cöde",
                "tra%25il/%", "a/b/c.__kt_index__"):
        wire = unquote(netpool.urlkey(key))
        disk = durability.unescape_key(durability.escape_key(key))
        assert wire == disk == key
        assert r.walk(wire) == r.walk(key) == r.walk(disk)


def test_client_and_server_placement_agree():
    nodes = [f"http://10.1.0.{i}:8873" for i in range(3)]
    client = ring.StoreRing(nodes[0], nodes=nodes, epoch=1)
    server = RingState(nodes[1], nodes, epoch=1, replication=2, quorum=2)
    for key in ("ckpt/a", "ckpt/b/leaf", "tree/blob0123"):
        assert client.nodes_for(key)[:2] == server.walk(key)[:2]
        assert server.live_replicas(key) == server.walk(key)[:2]


def test_ring_epoch_mismatch_rehydrates_typed():
    exc = RingEpochMismatch("stale", expected=4, actual=2)
    back = rehydrate_exception(json.loads(json.dumps(package_exception(exc))))
    assert isinstance(back, RingEpochMismatch)
    assert back.expected == 4 and back.actual == 2


def test_single_origin_ring_sends_no_epoch_header(tmp_path):
    """KT_STORE_NODES unset → the degenerate ring: no discovery request,
    no epoch header — wire behavior identical to the pre-ring client."""
    from kubetorch_tpu.data_store.store_server import create_store_app

    with ThreadedAiohttpServer(
            lambda: create_store_app(str(tmp_path / "s"))) as srv:
        rg = ring.ring_for(srv.url)
        assert rg.size == 1 and rg.epoch is None
        stats = ds.put("solo/t", {"w": np.ones(4, np.float32)},
                       store_url=srv.url)
        assert stats["leaves"] == 1
        out = ds.get("solo/t", store_url=srv.url)
        np.testing.assert_array_equal(out["w"], np.ones(4, np.float32))


# ---------------------------------------------------------------------------
# Replication + failover (in-process fleet)
# ---------------------------------------------------------------------------


def test_put_replicates_every_key_to_quorum(tmp_path, monkeypatch):
    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        tree = _tree(leaves=6)
        stats = ds.put("repl/ckpt", tree, store_url=fleet.urls[0])
        assert stats["leaves"] == 6 and stats["skipped"] == 0
        for i in range(6):
            key = f"repl/ckpt/layers/w{i:02d}"
            assert len(_kv_copies(fleet, key)) >= 2, \
                f"{key} must exist on >=2 nodes (W=2)"
        assert len(_kv_copies(fleet, "repl/ckpt.__kt_index__")) >= 2
        # any seed node serves the whole tree
        for url in fleet.urls:
            out = ds.get("repl/ckpt", store_url=url)
            np.testing.assert_array_equal(out["layers"]["w03"],
                                          tree["layers"]["w03"])


def test_node_loss_fails_over_and_delta_still_skips(tmp_path, monkeypatch):
    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        tree = _tree(leaves=6, seed=1)
        ds.put("loss/ckpt", tree, store_url=fleet.urls[0])
        fleet.stop_node(1)
        out = ds.get("loss/ckpt", store_url=fleet.urls[1])  # dead seed, even
        np.testing.assert_array_equal(out["layers"]["w00"],
                                      tree["layers"]["w00"])
        # an identical re-put against the degraded ring still moves ~0
        # bytes: /kv/diff answers ring-wide from surviving replicas
        stats = ds.put("loss/ckpt", tree, store_url=fleet.urls[0])
        assert stats["skipped"] == 6
        # deterministic failover proof: pick a key whose PRIMARY is the
        # dead node (placement is deterministic, so search for one) and
        # clear the router's down-marking so it really tries it first
        rg = ring.ring_for(fleet.urls[0])
        dead = fleet.urls[1]
        probe = next(f"loss/probe/{i}" for i in range(256)
                     if ring.HashRing(rg.nodes).walk(
                         f"loss/probe/{i}")[0] == dead)
        rg.record_success(dead)
        before = ring._FAILOVERS.value(kind="connect")
        assert ds.get_json(probe, store_url=fleet.urls[0]) is None
        assert ring._FAILOVERS.value(kind="connect") > before


def test_any_node_proxies_keys_it_does_not_hold(tmp_path, monkeypatch):
    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        val = np.arange(32, dtype=np.float32)
        ds.put("proxy/one", {"w": val}, store_url=fleet.urls[0])
        key = "proxy/one/w"
        holders = _kv_copies(fleet, key)
        others = [u for u in fleet.urls if u not in holders]
        assert others, "R=2 of 3 nodes must leave a non-holder"
        # a DIRECT client GET (no ring header) against the non-holder
        r = requests.get(f"{others[0]}/kv/{quote(key, safe='/')}",
                         timeout=30)
        assert r.status_code == 200
        assert r.content == val.tobytes()
        prom = requests.get(f"{others[0]}/metrics", timeout=10).text
        assert "kt_store_proxy_fetches_total" in prom


def test_tripped_breaker_on_one_replica_does_not_gate_siblings(
        tmp_path, monkeypatch):
    """Satellite: per-netloc breakers + ring failover. A dead replica
    trips ITS breaker; requests keep flowing to the sibling, and the
    open breaker is just another failover signal."""
    with ThreadedStoreFleet(tmp_path, n=2) as fleet:
        _use_fleet(monkeypatch, fleet)
        monkeypatch.setenv("KT_STORE_BREAKER_THRESHOLD", "1")
        monkeypatch.setenv("KT_STORE_RETRIES", "1")
        val = np.ones(16, np.float32)
        rg = ring.ring_for(fleet.urls[0])
        # placement depends on the fleet's EPHEMERAL ports: pick a base key
        # whose leaf provably places node0 FIRST, so killing node0 puts a
        # refused connection (→ tripped breaker) on the request path every
        # run instead of only when the port hash happens to land that way
        base = next(f"brk/ckpt{i}" for i in range(64)
                    if rg.nodes_for(f"brk/ckpt{i}/w")[0] == fleet.urls[0])
        ds.put(base, {"w": val}, store_url=fleet.urls[0])
        fleet.stop_node(0)
        before = ring._FAILOVERS.value(kind="breaker")
        # repeated ops: first trips node0's breaker (refused), later ones
        # hit the open breaker and must STILL succeed via node1. Clearing
        # the router's own down-marking between ops forces each retry back
        # onto node0 first, so the OPEN BREAKER (not the liveness
        # ordering) is what the failover absorbs.
        for _ in range(3):
            rg.record_success(fleet.urls[0])
            out = ds.get(base, store_url=fleet.urls[0])
            np.testing.assert_array_equal(out["w"], val)
        from urllib.parse import urlsplit
        dead = urlsplit(fleet.urls[0]).netloc
        live = urlsplit(fleet.urls[1]).netloc
        assert netpool._BREAKERS[dead].state == "open"
        assert netpool._BREAKERS.get(live) is None or \
            netpool._BREAKERS[live].state == "closed"
        assert ring._FAILOVERS.value(kind="breaker") > before


# ---------------------------------------------------------------------------
# Membership change: epoch safety under concurrent writes (satellite)
# ---------------------------------------------------------------------------


def test_stale_epoch_rejected_typed_before_touching_disk(
        tmp_path, monkeypatch):
    with ThreadedStoreFleet(tmp_path, n=2, epoch=5) as fleet:
        _use_fleet(monkeypatch, fleet)
        r = requests.put(f"{fleet.urls[0]}/kv/stale/k", data=b"x",
                         headers={ring.RING_EPOCH_HEADER: "3"}, timeout=30)
        assert r.status_code == 409
        body = r.json()
        assert body["error_type"] == "RingEpochMismatch"
        exc = rehydrate_exception(body)
        assert exc.expected == 5 and exc.actual == 3
        # nothing landed
        assert requests.get(f"{fleet.urls[0]}/kv/stale/k",
                            timeout=10).status_code == 404


def test_membership_change_mid_put_lands_at_quorum_never_partial(
        tmp_path, monkeypatch):
    """THE satellite scenario: a node joins (epoch bump) while a
    multi-leaf put is in flight. In-flight leaves hit 409 +
    RingEpochMismatch, the router refreshes and re-routes transparently
    (the RetryPolicy-shaped absorption), and the put lands at quorum on
    the NEW ring — never a silent partial tree."""
    from kubetorch_tpu.data_store.store_server import create_store_app

    with ThreadedStoreFleet(tmp_path, n=3, epoch=1) as fleet:
        _use_fleet(monkeypatch, fleet)
        monkeypatch.setenv("KT_STORE_CONCURRENCY", "1")  # deterministic order
        joiner_port = __import__(
            "kubetorch_tpu.utils.procs", fromlist=["free_port"]).free_port()
        joiner_url = f"http://127.0.0.1:{joiner_port}"
        new_nodes = fleet.urls + [joiner_url]
        joiner_ring = RingState(joiner_url, new_nodes, epoch=2,
                                replication=2, quorum=2,
                                ttl_s=fleet.node_ttl_s)
        joiner = ThreadedAiohttpServer(
            lambda: create_store_app(str(tmp_path / "joiner"),
                                     ring=joiner_ring),
            port=joiner_port)
        joiner.__enter__()
        try:
            rg = ring.ring_for(fleet.urls[0])
            assert rg.epoch == 1
            state = {"puts": 0}
            orig = ds._kv_put

            def join_mid_put(url, key, data, meta, sess=None):
                state["puts"] += 1
                if state["puts"] == 3:
                    # the membership change lands between leaf uploads
                    fleet.post_ring(new_nodes, epoch=2)
                return orig(url, key, data, meta, sess)

            monkeypatch.setattr(ds, "_kv_put", join_mid_put)
            before = ring._FAILOVERS.value(kind="epoch")
            tree = _tree(leaves=8, seed=3)
            stats = ds.put("join/ckpt", tree, store_url=fleet.urls[0])
            monkeypatch.setattr(ds, "_kv_put", orig)
            assert stats["leaves"] == 8
            # the router noticed, refreshed, and re-routed at least once
            assert ring._FAILOVERS.value(kind="epoch") > before
            assert rg.epoch == 2 and joiner_url in rg.nodes
            # never a partial tree: every leaf readable and bit-exact,
            # from the old members AND the joiner
            for url in (fleet.urls[0], joiner_url):
                out = ds.get("join/ckpt", store_url=url)
                for name, arr in tree["layers"].items():
                    np.testing.assert_array_equal(out["layers"][name], arr)
        finally:
            joiner.__exit__()


# ---------------------------------------------------------------------------
# TTL re-replication + deletes + trees
# ---------------------------------------------------------------------------


def test_dead_node_past_ttl_rereplicated_by_scrub(tmp_path, monkeypatch):
    with ThreadedStoreFleet(tmp_path, n=3, node_ttl_s=0.4) as fleet:
        _use_fleet(monkeypatch, fleet)
        tree = _tree(leaves=6, seed=2)
        ds.put("heal/ckpt", tree, store_url=fleet.urls[0])
        fleet.stop_node(2)
        # first sweep starts every survivor's death clock for node2
        for url in fleet.urls[:2]:
            requests.post(f"{url}/scrub/run", timeout=60)
        time.sleep(0.5)                      # past the TTL
        for url in fleet.urls[:2]:
            rep = requests.post(f"{url}/scrub/run", timeout=60).json()
            assert rep.get("still_under_replicated", 0) == 0
        for url in fleet.urls[:2]:
            s = requests.get(f"{url}/scrub/status", timeout=10).json()
            assert s["under_replicated"] == 0
            assert s["ring"]["down"], "dead node must be in the ring view"
        # every key is back at R=2 on the SURVIVORS
        for i in range(6):
            holders = _kv_copies(fleet, f"heal/ckpt/layers/w{i:02d}")
            assert len(holders) == 2 and fleet.urls[2] not in holders


def test_rm_deletes_from_every_replica(tmp_path, monkeypatch):
    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        ds.put("gone/ckpt", {"w": np.ones(8, np.float32)},
               store_url=fleet.urls[0])
        assert ds.rm("gone/ckpt", store_url=fleet.urls[0])
        for url in fleet.urls:
            r = requests.get(f"{url}/kv/gone/ckpt/w",
                             headers={ring.REPLICATED_HEADER: "1"},
                             timeout=10)
            assert r.status_code == 404
        assert ds.ls("gone/", store_url=fleet.urls[0]) == []


def test_push_pull_tree_survive_node_stop(tmp_path, monkeypatch):
    from kubetorch_tpu.data_store.sync import pull_tree, push_tree

    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        proj = tmp_path / "proj"
        proj.mkdir()
        for i in range(6):
            (proj / f"mod{i}.py").write_text(f"x = {i}\n" * 50)
        stats = push_tree(fleet.urls[0], "code/app", str(proj))
        assert stats["uploaded"] == 6
        fleet.stop_node(0)                   # kill a replica (and the seed)
        dest = tmp_path / "dest"
        out = pull_tree(fleet.urls[0], "code/app", str(dest))
        assert out["fetched"] == 6
        for i in range(6):
            assert (dest / f"mod{i}.py").read_text() == f"x = {i}\n" * 50


# ---------------------------------------------------------------------------
# Checkpoint markers: quorum reads across the ring
# ---------------------------------------------------------------------------


def test_checkpoint_marker_quorum_and_restore_with_dead_replica(
        tmp_path, monkeypatch):
    """Elastic-resume integration (light): a committed checkpoint on the
    ring restores bit-exact — fingerprint-matched — when one replica
    holding checkpoint state (the MARKER's primary, the worst case) is
    dead at restore time."""
    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        c = ck.Checkpointer("job/ring", store_url=fleet.urls[0])
        tree = {"w": np.arange(16.0), "b": np.ones(4)}
        c.save(tree, 1)
        tree["w"] = tree["w"] + 1
        c.save(tree, 2)
        marker_key = "job/ring/__kt_commit__"
        primary = ring.ring_for(fleet.urls[0]).nodes_for(marker_key)[0]
        fleet.stop_node(fleet.urls.index(primary))
        ring.reset_rings()
        c2 = ck.Checkpointer("job/ring", store_url=fleet.urls[0])
        assert c2.last_committed_step == 2
        restored, step = c2.restore()
        assert step == 2
        assert ck.tree_fingerprint(restored) == ck.tree_fingerprint(tree)


def test_marker_quorum_read_prefers_newest_copy(tmp_path, monkeypatch):
    """A replica that missed the last marker write (down, now back) must
    never win the quorum read: newest stored_at wins."""
    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        key = "stale/marker/__kt_commit__"
        ds.put_json(key, {"step": 1, "slot": 0}, store_url=fleet.urls[0])
        time.sleep(0.02)
        # overwrite on ONE replica only (simulates the survivor that took
        # the newer write while its sibling was down)
        holders = _kv_copies(fleet, key)
        assert len(holders) >= 2
        data = json.dumps({"step": 7, "slot": 1}).encode()
        meta = {"kind": "json",
                "blake2b": hashlib.blake2b(data, digest_size=20).hexdigest()}
        r = requests.put(f"{holders[0]}/kv/{quote(key, safe='/')}",
                         data=data,
                         headers={"X-KT-Meta": json.dumps(meta),
                                  ring.REPLICATED_HEADER: "1"}, timeout=30)
        assert r.status_code == 200
        got = ds.get_json(key, store_url=fleet.urls[0], quorum=True)
        assert got == {"step": 7, "slot": 1}


# ---------------------------------------------------------------------------
# Chaos acceptance: SIGKILL mid-push / mid-pull, zero client-visible failures
# ---------------------------------------------------------------------------


def _wait_scrub_heals(fleet, live_idx, deadline_s=60.0):
    """Drive /scrub/run on the survivors until under_replicated hits 0."""
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        ok = True
        for i in live_idx:
            rep = requests.post(f"{fleet.urls[i]}/scrub/run",
                                timeout=120).json()
            if rep.get("still_under_replicated", 0):
                ok = False
        if ok:
            statuses = [requests.get(f"{fleet.urls[i]}/scrub/status",
                                     timeout=10).json() for i in live_idx]
            if all(s["under_replicated"] == 0 for s in statuses):
                return statuses
        time.sleep(0.2)
    raise AssertionError("re-replication did not converge")


@pytest.mark.slow
def test_sigkill_store_node_mid_put_and_mid_pull_zero_failures(
        tmp_path, monkeypatch):
    """THE acceptance scenario. 3-node subprocess ring (R=2, W=2):

    1. node 1 is armed to SIGKILL itself on its 2nd client request — it
       dies MID multi-leaf put; the put completes with zero errors.
    2. every leaf reads back hash-verified (through ring failover).
    3. a tree push/pull with node 2 killed mid-pull also completes.
    4. once the dead node is past its TTL, /scrub/run re-replicates its
       keys: /scrub/status shows under_replicated == 0 and every key is
       on 2 live nodes again.
    5. kt_store_failovers_total incremented client-side throughout.
    """
    from kubetorch_tpu.data_store.sync import pull_tree, push_tree

    with SubprocessStoreFleet(
            tmp_path, n=3, node_ttl_s=0.5,
            chaos={1: "kill-store-node:9@1"}) as fleet:
        _use_fleet(monkeypatch, fleet)
        monkeypatch.setenv("KT_STORE_CONCURRENCY", "1")
        fail_before = sum(ring._FAILOVERS.value(kind=k)
                          for k in ("connect", "status", "breaker"))
        tree = _tree(leaves=24, seed=7)
        stats = ds.put("chaos/ckpt", tree, store_url=fleet.urls[0])
        assert stats["leaves"] == 24, "put must succeed despite the kill"
        assert fleet.wait_node_dead(1), \
            "node1 should have SIGKILLed itself mid-put"
        # hash-verified read-back of every leaf (fetch() verifies against
        # the index's blake2b; a corrupt or torn leaf would raise typed)
        out = ds.get("chaos/ckpt", store_url=fleet.urls[0])
        for name, arr in tree["layers"].items():
            np.testing.assert_array_equal(out["layers"][name], arr)
        fails_after = sum(ring._FAILOVERS.value(kind=k)
                          for k in ("connect", "status", "breaker"))
        assert fails_after > fail_before, \
            "the absorbed node loss must be visible in kt_store_failovers"

        # mid-pull loss: push a tree, then node 2 dies while we pull it
        proj = tmp_path / "proj"
        proj.mkdir()
        for i in range(8):
            (proj / f"f{i}.bin").write_bytes(os.urandom(4096) * 8)
        push_tree(fleet.urls[0], "chaos/code", str(proj))
        fleet.kill_node(2)
        dest = tmp_path / "dest"
        res = pull_tree(fleet.urls[0], "chaos/code", str(dest))
        assert res["files"] == 8
        for i in range(8):
            assert (dest / f"f{i}.bin").read_bytes() == \
                (proj / f"f{i}.bin").read_bytes()

        # restart node 2 (its disk survived; node 1 stays dead past TTL).
        # Depending on WHEN the kill landed, write-time ownership handoff
        # may already have placed every put key on the survivors — so also
        # plant a single-copy key (internal PUT to one node only): the
        # sweep MUST find it under-replicated and push its second copy.
        fleet.chaos.pop(1, None)
        fleet.start_node(2)
        lone_key = "chaos/lonely"
        lone = b"only one copy of me exists"
        meta = {"blake2b": hashlib.blake2b(lone, digest_size=20).hexdigest()}
        r = requests.put(f"{fleet.urls[0]}/kv/{quote(lone_key, safe='/')}",
                         data=lone,
                         headers={"X-KT-Meta": json.dumps(meta),
                                  ring.REPLICATED_HEADER: "1"}, timeout=30)
        assert r.status_code == 200
        assert _kv_copies(fleet, lone_key) == [fleet.urls[0]]
        time.sleep(0.6)                      # let node1 age past its TTL
        statuses = _wait_scrub_heals(fleet, live_idx=(0, 2))
        assert all(s["under_replicated"] == 0 for s in statuses)
        assert any(s["re_replicated"] > 0 for s in statuses), \
            "the under-replicated key must have been re-replicated"
        assert len(_kv_copies(fleet, lone_key)) == 2
        for i in range(24):
            holders = _kv_copies(fleet, f"chaos/ckpt/layers/w{i:02d}")
            assert len(holders) >= 2 and fleet.urls[1] not in holders, \
                f"leaf w{i:02d} must be back at R=2 on live nodes"


@pytest.mark.slow
@pytest.mark.elastic
def test_elastic_resume_with_checkpoint_on_ring_and_dead_replica(
        tmp_path, monkeypatch):
    """Acceptance: PR 6's kill-rank → N-1 resume scenario, unchanged —
    except the checkpoint lives on a 3-node ring and one replica holding
    checkpoint blobs is DEAD at restore time. The survivor must resume
    from the committed checkpoint through ring failover."""
    import asyncio

    from kubetorch_tpu.parallel.mesh import DistributedConfig
    from kubetorch_tpu.resources.pointers import Pointers
    from kubetorch_tpu.serving.spmd_supervisor import SPMDSupervisor

    assets = os.path.join(os.path.dirname(__file__), "assets")
    with ThreadedStoreFleet(tmp_path, n=3) as fleet:
        _use_fleet(monkeypatch, fleet)
        key = "elastic/ring-kill"
        monkeypatch.setenv("KT_CHAOS", "kill-rank:9@2")
        monkeypatch.setenv("KT_CHAOS_RANK", "1")
        monkeypatch.setenv("KT_WATCHDOG_INTERVAL_S", "0.25")
        monkeypatch.setenv("KT_RESTART_BUDGET", "3")
        monkeypatch.setenv("KT_RESTART_WINDOW_S", "300")
        monkeypatch.setenv("KT_RESTART_BACKOFF_BASE_S", "0.01")
        monkeypatch.setenv("KT_RESTART_BACKOFF_MAX_S", "0.01")
        monkeypatch.setenv("LOCAL_IPS", "127.0.0.1")
        monkeypatch.setenv("POD_IP", "127.0.0.1")
        cfg = DistributedConfig(
            distribution_type="spmd", workers=1, procs_per_worker=2,
            elastic={"max_resumes": 2})
        sup = SPMDSupervisor(
            Pointers(project_root=assets, module_name="payloads",
                     file_path="payloads.py",
                     cls_or_fn_name="ElasticTrainer"),
            {"args": [fleet.urls[0], key]}, cfg,
            service_name="t-ring-elastic", namespace="default")
        sup.setup()
        try:
            async def go():
                r1 = await sup.call("step", [], {}, timeout=120)
                assert len(r1) == 2
                r2 = await sup.call("step", [], {}, timeout=120)
                assert len(r2) == 2
                # the checkpoint for step 2 is committed on the ring —
                # NOW kill the replica holding its commit marker, then
                # let the chaos kill-rank fire mid-step-3: the elastic
                # resume must restore through ring failover
                marker = f"{key}/__kt_commit__"
                primary = ring.ring_for(
                    fleet.urls[0]).nodes_for(marker)[0]
                fleet.stop_node(fleet.urls.index(primary))
                return await sup.call("step", [], {}, timeout=None)

            r3 = asyncio.run(go())
            assert len(r3) == 1, "fan-out should have shrunk to 1 rank"
            out = r3[0]
            assert out["resumed_from"] is not None, \
                "survivor should have resumed from the ring checkpoint"
            assert out["step"] == out["resumed_from"] + 1
            assert sup.elastic.resumes == 1
            # the resumed state hash-matches a clean ring reload
            ring.reset_rings()
            reloaded, step = ck.Checkpointer(
                key, store_url=fleet.urls[0]).restore()
            assert step == out["step"]
            assert ck.tree_fingerprint(reloaded) == out["fingerprint"]
        finally:
            sup.cleanup()


# ---------------------------------------------------------------------------
# ISSUE 10: streamed proxy relay (O(chunk) RSS) + /kv/diff compression
# ---------------------------------------------------------------------------


def _vmrss_kb(pid: int) -> int:
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    raise RuntimeError("no VmRSS")


@pytest.mark.slow
def test_proxied_get_streams_with_o_chunk_rss(tmp_path):
    """A ring-wide proxy GET must RELAY, not buffer (ISSUE 10): node B
    serving a blob that lives only on node A holds O(chunk) RSS while the
    whole blob moves — the PR 1 streaming-PUT discipline, now symmetric.
    Before the StreamResponse relay, this held the full body in RAM
    (``await r.read()``), so the assertion below failed by ~blob size."""
    size = 64 << 20
    blob = os.urandom(1 << 20) * 64          # 64 MB, two nodes, R=1
    h = hashlib.blake2b(blob, digest_size=20).hexdigest()
    with SubprocessStoreFleet(tmp_path, n=2, replication=1,
                              write_quorum=1) as fleet:
        # land the blob on node 0 ONLY (internal header: no replication)
        r = requests.put(f"{fleet.urls[0]}/blob/{h}", data=blob,
                         headers={"X-KT-Replicated": "1"}, timeout=120)
        assert r.status_code == 200
        proxy_pid = fleet.procs[1].pid
        base_kb = _vmrss_kb(proxy_pid)
        # GET via node 1 → local miss → streamed relay from node 0
        peak_kb, got = base_kb, hashlib.blake2b(digest_size=20)
        read = 0
        with requests.get(f"{fleet.urls[1]}/blob/{h}", stream=True,
                          timeout=120) as resp:
            assert resp.status_code == 200
            for chunk in resp.iter_content(1 << 20):
                got.update(chunk)
                read += len(chunk)
                peak_kb = max(peak_kb, _vmrss_kb(proxy_pid))
        assert read == size and got.hexdigest() == h   # bit-exact relay
        delta_mb = (peak_kb - base_kb) / 1024.0
        assert delta_mb < size / (1 << 20) / 2, \
            f"proxy node RSS grew {delta_mb:.0f} MB during a " \
            f"{size >> 20} MB proxied GET — the relay is buffering"


def test_kv_diff_body_compression_negotiated(tmp_path):
    """/kv/diff speaks zlib (zstd when available) both ways, negotiated
    per request; clients that send no codec headers get the exact legacy
    wire shape."""
    import zlib

    from kubetorch_tpu.data_store.store_server import create_store_app

    with ThreadedAiohttpServer(
            lambda: create_store_app(str(tmp_path / "s"))) as srv:
        url = srv.url
        body = b"\x01\x02\x03"
        h = hashlib.blake2b(body, digest_size=20).hexdigest()
        assert requests.put(f"{url}/kv/comp/a", data=body,
                            timeout=30).status_code == 200
        # big key table: compresses on the way in, reply compresses too
        keys = {f"comp/missing-{i:04d}": "f" * 40 for i in range(200)}
        keys["comp/a"] = h
        payload = json.dumps({"keys": keys}).encode()
        comp = zlib.compress(payload, 3)
        assert len(comp) < len(payload) // 2
        r = requests.post(
            f"{url}/kv/diff", data=comp,
            headers={"Content-Type": "application/json",
                     "Content-Encoding": "zlib",
                     "Accept-Encoding": "zlib"}, timeout=30)
        assert r.status_code == 200
        assert r.headers.get("Content-Encoding") == "zlib"
        missing = json.loads(zlib.decompress(r.content))["missing"]
        assert "comp/a" not in missing and len(missing) == 200
        # legacy client: no codec headers → plain JSON both ways
        r = requests.post(f"{url}/kv/diff",
                          json={"keys": {"comp/a": h, "comp/nope": h}},
                          headers={"Accept-Encoding": "identity"},
                          timeout=30)
        assert r.status_code == 200
        assert "Content-Encoding" not in r.headers
        assert r.json()["missing"] == ["comp/nope"]
        # garbage compressed body → clean 400, not a 500
        r = requests.post(f"{url}/kv/diff", data=b"not zlib",
                          headers={"Content-Encoding": "zlib"}, timeout=30)
        assert r.status_code == 400


def test_kv_diff_client_helper_round_trips_compressed(tmp_path):
    """The put/delta client path itself negotiates compression: a warm
    re-put over a >COMPRESS_MIN_BYTES key table still skips every leaf."""
    from kubetorch_tpu.data_store.store_server import create_store_app

    with ThreadedAiohttpServer(
            lambda: create_store_app(str(tmp_path / "s2"))) as srv:
        rng = np.random.default_rng(7)
        tree = {"layer": {f"w{i:03d}": rng.standard_normal(16).astype(
            np.float32) for i in range(40)}}    # 40 keys → >1 KB table
        cold = ds.put("comptree/w", tree, store_url=srv.url)
        assert cold["skipped"] == 0
        warm = ds.put("comptree/w", tree, store_url=srv.url)
        assert warm["skipped"] == warm["leaves"] == 40
        assert warm["bytes"] == 0
        out = ds.get("comptree/w", store_url=srv.url)
        np.testing.assert_array_equal(out["layer"]["w000"],
                                      tree["layer"]["w000"])


def test_netpool_body_codecs_round_trip():
    data = json.dumps({"keys": {str(i): "a" * 40
                                for i in range(100)}}).encode()
    for coding in ("zlib",) + ((netpool.ZSTD,) if netpool._zstd() else ()):
        comp = netpool.compress_body(data, coding)
        assert len(comp) < len(data)
        assert netpool.decompress_body(comp, coding) == data
    assert netpool.decompress_body(data, None) == data
    assert netpool.best_coding("zlib, gzip") == "zlib"
    assert netpool.best_coding("identity") is None
    assert netpool.best_coding(None) is None

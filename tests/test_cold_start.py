"""Fleet cold-start burn-down suite (ISSUE 16).

Four layers, each pinned at its sharpest contract:

- ``serve/aot_cache.py`` — a stale/corrupt/mismatched cache entry is a
  TYPED, counted fallback to a fresh compile, never a wrong executable.
- ``serving/shm_ring.py`` weight segments — fork-attach is one verified
  memcpy; a corrupt segment raises ``DataCorruptionError(source="shm")``;
  crash cleanup by name leaks nothing.
- ``serving/warm_template.py`` — the pre-warmed fork server converges to
  N replicas under kill-template/kill-joiner chaos with zero /dev/shm
  residue (the acceptance drill, marked slow).
- the router readiness fence + autoscaler growth cap — a warming replica
  is ordered last and probed fresh before its first request; the ≤2×
  growth cap relaxes only on a MEASURED fast cold start.

Fast tests use a trivially small jit (`x + 1`) so the cache semantics
run in milliseconds; the engine-equivalence and fork drills carry
``pytest.mark.slow`` like the rest of the subprocess suites.
"""

import asyncio
import dataclasses
import glob
import json
import os
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu import telemetry
from kubetorch_tpu.chaos import (ChaosEngine, joiner_kill_plan, parse_spec,
                                 template_kill_plan)
from kubetorch_tpu.exceptions import (AOTCacheCorruptError, AOTCacheMissError,
                                      DataCorruptionError, WorkerCallError)
from kubetorch_tpu.serve.aot_cache import AOTCompileCache, AOTKey
from kubetorch_tpu.serving import shm_ring
from kubetorch_tpu.serving.router import Router
from kubetorch_tpu.soak import schedule as soak_schedule

IPS = ["10.1.0.1", "10.1.0.2", "10.1.0.3"]
MY_IP = "9.9.9.9"


def _fence(result):
    return telemetry.cold_start_metrics()["fence"].value(result=result)


# ---------------------------------------------------------------------------
# AOT compile cache: typed misses, corruption fallback, never-wrong loads
# ---------------------------------------------------------------------------


def _key(**over):
    base = dict(model={"kind": "probe"}, mesh_shape=None, buckets=(8,),
                slots=2, max_len=64, quantize_kv=False, decode_block=1,
                jax_version=jax.__version__)
    base.update(over)
    return AOTKey(**base)


def _build():
    return jax.jit(lambda x: x + 1.0).lower(
        jnp.zeros((4,), jnp.float32)).compile()


class TestAOTCache:
    def test_absent_is_a_typed_miss(self, tmp_path):
        cache = AOTCompileCache(tmp_path)
        with pytest.raises(AOTCacheMissError) as e:
            cache.load(_key(), "probe")
        assert e.value.reason == "absent"

    def test_miss_compiles_publishes_then_hits(self, tmp_path):
        cache = AOTCompileCache(tmp_path)
        exe, tag = cache.get_or_compile(_key(), "probe", _build)
        assert tag == "miss"
        # second boot (fresh cache object, same dir): a pure hit, and the
        # deserialized executable computes the same thing
        cache2 = AOTCompileCache(tmp_path)
        exe2, tag2 = cache2.get_or_compile(_key(), "probe", _build)
        assert tag2 == "hit"
        np.testing.assert_allclose(
            np.asarray(exe2(jnp.ones((4,), jnp.float32))),
            np.full((4,), 2.0, np.float32))
        assert cache.counts == {"miss": 1, "publish": 1}
        assert cache2.counts == {"hit": 1}

    def test_key_mismatch_is_incompatible_not_absent(self, tmp_path):
        cache = AOTCompileCache(tmp_path)
        cache.get_or_compile(_key(), "probe", _build)
        # same executable NAME under a drifted key (bucket change): the
        # miss must be distinguishable from a cold cache
        with pytest.raises(AOTCacheMissError) as e:
            cache.load(_key(buckets=(8, 16)), "probe")
        assert e.value.reason == "incompatible"
        _, tag = cache.get_or_compile(_key(buckets=(8, 16)), "probe", _build)
        assert tag == "incompatible"

    def test_corrupt_payload_recompiles_with_typed_count(self, tmp_path):
        cache = AOTCompileCache(tmp_path)
        key = _key()
        cache.get_or_compile(key, "probe", _build)
        bin_path = cache.entry_dir(key) / "probe.bin"
        bin_path.write_bytes(b"garbage that is definitely not a pickle")
        with pytest.raises(AOTCacheCorruptError):
            cache.load(key, "probe")
        exe, tag = cache.get_or_compile(key, "probe", _build)
        assert tag == "corrupt"
        np.testing.assert_allclose(
            np.asarray(exe(jnp.zeros((4,), jnp.float32))),
            np.ones((4,), np.float32))
        # the recompile re-published a good entry: next load is a hit
        assert cache.get_or_compile(key, "probe", _build)[1] == "hit"

    def test_unreadable_sidecar_is_corrupt(self, tmp_path):
        cache = AOTCompileCache(tmp_path)
        key = _key()
        cache.get_or_compile(key, "probe", _build)
        (cache.entry_dir(key) / "probe.json").write_text("{not json")
        with pytest.raises(AOTCacheCorruptError):
            cache.load(key, "probe")

    def test_crash_between_bin_and_meta_reads_absent(self, tmp_path):
        # _write_entry commits bin first, meta last; a crash in the
        # window must read as ABSENT (recompile), not corrupt
        cache = AOTCompileCache(tmp_path)
        key = _key()
        cache.get_or_compile(key, "probe", _build)
        (cache.entry_dir(key) / "probe.json").unlink()
        with pytest.raises(AOTCacheMissError) as e:
            cache.load(key, "probe")
        assert e.value.reason == "absent"

    def test_digest_is_stable_and_key_sensitive(self):
        assert _key().digest() == _key().digest()
        assert _key().digest() != _key(buckets=(8, 16)).digest()
        assert _key().digest() != _key(jax_version="99.0").digest()
        # top_k is baked into every warmed executable as a static: two
        # engines differing only in top_k must not share a cache line
        assert _key().digest() != _key(top_k=40).digest()
        assert _key(top_k=5).digest() != _key(top_k=40).digest()

    def test_key_carries_the_grid_layout(self, tmp_path):
        """An executable persisted for another slot-grid layout (before the
        grid was head-major the key had no such field) is never found: the
        lookup is a typed miss, not a load that fails on shapes at call
        time."""
        from kubetorch_tpu.serve.engine import GRID_LAYOUT

        class _Eng:
            cfg = {"kind": "probe"}
            _mesh = None
            _buckets = [8]
            slots, max_len = 2, 64
            quantize_kv, decode_block = False, 1
            top_k = None
        key = AOTKey.for_engine(_Eng())
        assert key.grid_layout == GRID_LAYOUT == \
            "layer,slot,kv_head,row,head_dim"
        old = dataclasses.replace(key, grid_layout="")
        assert key.digest() != old.digest()
        assert "grid_layout" in key.describe()
        cache = AOTCompileCache(tmp_path)
        cache.put(old, "decode_1", _build())
        with pytest.raises(AOTCacheMissError) as e:
            cache.load(key, "decode_1")
        assert e.value.reason == "incompatible"    # a key mismatch, typed
        cache.load(old, "decode_1")            # the old line itself is intact

    def test_engine_key_carries_top_k(self):
        class _Eng:
            cfg = {"kind": "probe"}
            _mesh = None
            _buckets = [8]
            slots, max_len = 2, 64
            quantize_kv, decode_block = False, 1
            top_k = 7
        assert AOTKey.for_engine(_Eng()).top_k == 7


class _FakeStore:
    """In-memory stand-in for data_store.commands put/get (path-based)."""

    def __init__(self):
        self.blobs = {}

    def put(self, key, src, store_url=None, **kw):
        self.blobs[key] = Path(src).read_bytes()

    def get(self, key, dest=None, store_url=None, **kw):
        if key not in self.blobs:
            raise KeyError(key)
        Path(dest).write_bytes(self.blobs[key])


class TestAOTStoreLayer:
    def _fake(self, monkeypatch):
        store = _FakeStore()
        from kubetorch_tpu.data_store import commands as ds
        monkeypatch.setattr(ds, "put", store.put)
        monkeypatch.setattr(ds, "get", store.get)
        return store

    def test_publish_is_content_addressed_and_second_node_hits(
            self, tmp_path, monkeypatch):
        store = self._fake(monkeypatch)
        c1 = AOTCompileCache(tmp_path / "node1", store=True)
        c1.get_or_compile(_key(), "probe", _build)
        ptr_key = [k for k in store.blobs if k.endswith(".ptr")]
        assert len(ptr_key) == 1
        want = store.blobs[ptr_key[0]].decode()
        # the payload's own key names its blake2b — self-verifying fetch
        payload_keys = [k for k in store.blobs if not k.endswith(".ptr")]
        assert payload_keys == [ptr_key[0][:-len(".ptr")] + "/" + want]
        c2 = AOTCompileCache(tmp_path / "node2", store=True)
        exe, tag = c2.get_or_compile(_key(), "probe", _build)
        assert tag == "hit"
        assert c2.counts.get("store_hit") == 1
        np.testing.assert_allclose(
            np.asarray(exe(jnp.ones((4,), jnp.float32))),
            np.full((4,), 2.0, np.float32))

    def test_tampered_store_payload_never_reaches_pickle(
            self, tmp_path, monkeypatch):
        store = self._fake(monkeypatch)
        c1 = AOTCompileCache(tmp_path / "node1", store=True)
        c1.get_or_compile(_key(), "probe", _build)
        for k in store.blobs:
            if not k.endswith(".ptr"):
                store.blobs[k] = b"swapped blob, arbitrary pickle inside"
        c2 = AOTCompileCache(tmp_path / "node2", store=True)
        _, tag = c2.get_or_compile(_key(), "probe", _build)
        assert tag == "miss"                # typed, counted fallback
        assert c2.counts.get("store_corrupt") == 1
        assert "store_hit" not in c2.counts

    def test_tampered_pointer_is_rejected(self, tmp_path, monkeypatch):
        store = self._fake(monkeypatch)
        c1 = AOTCompileCache(tmp_path / "node1", store=True)
        c1.get_or_compile(_key(), "probe", _build)
        for k in list(store.blobs):
            if k.endswith(".ptr"):
                store.blobs[k] = b"../../etc/not-a-hash"
        c2 = AOTCompileCache(tmp_path / "node2", store=True)
        _, tag = c2.get_or_compile(_key(), "probe", _build)
        assert tag == "miss"
        assert c2.counts.get("store_corrupt") == 1


# ---------------------------------------------------------------------------
# shm weight segments: one verified memcpy, typed corruption, no leaks
# ---------------------------------------------------------------------------


class TestWeightSegment:
    def _params(self):
        return {"wte": np.arange(12, dtype=np.float32).reshape(3, 4),
                "blocks": [{"w": np.ones((2, 2), np.float64)},
                           {"w": np.full((2, 2), 7, np.int32)}],
                "head": (np.zeros(5, np.float32),)}

    def test_roundtrip_preserves_structure_and_values(self):
        params = self._params()
        seg = shm_ring.create_weight_segment(params, tag="t")
        try:
            out = seg.manifest
            assert out["total_bytes"] > 0
            tree = shm_ring.attach_weight_segment(seg.manifest)
        finally:
            seg.close()
        assert isinstance(tree["blocks"], list)
        assert isinstance(tree["head"], tuple)
        np.testing.assert_array_equal(tree["wte"], params["wte"])
        np.testing.assert_array_equal(tree["blocks"][1]["w"],
                                      params["blocks"][1]["w"])
        assert tree["blocks"][0]["w"].dtype == np.float64
        # the attached tree OWNS its memory: the unlink above must not
        # invalidate it
        assert float(tree["head"][0].sum()) == 0.0

    def test_owner_close_unlinks_segment(self):
        seg = shm_ring.create_weight_segment(self._params(), tag="t")
        manifest = seg.manifest
        seg.close()
        with pytest.raises(FileNotFoundError):
            shm_ring.attach_weight_segment(manifest)

    def test_corrupt_segment_raises_typed_never_wrong_weights(self):
        seg = shm_ring.create_weight_segment(self._params(), tag="t")
        try:
            bad = dict(seg.manifest, blake2b="00" * 16)
            with pytest.raises(DataCorruptionError) as e:
                shm_ring.attach_weight_segment(bad)
            assert e.value.source == "shm"
            # explicit opt-out still works (bench A/B uses verify=True;
            # the flag exists for profiling the hash cost)
            tree = shm_ring.attach_weight_segment(bad, verify=False)
            np.testing.assert_array_equal(tree["wte"],
                                          self._params()["wte"])
        finally:
            seg.close()

    def test_unlink_by_name_is_idempotent(self):
        seg = shm_ring.create_weight_segment(self._params(), tag="t")
        name = seg.manifest["name"]
        seg.close(unlink=False)           # simulate a SIGKILLed owner
        assert shm_ring.unlink_weight_segment(name) is True
        assert shm_ring.unlink_weight_segment(name) is False


# ---------------------------------------------------------------------------
# chaos verbs: parse, plans, middleware scoping
# ---------------------------------------------------------------------------


class TestTemplateChaosVerbs:
    def test_kill_plans_parse_signal_and_op_index(self):
        assert template_kill_plan("kill-template@0") == {0: 9}
        assert template_kill_plan("kill-template:15@2,kill-joiner@1") \
            == {2: 15}
        assert joiner_kill_plan("kill-joiner:TERM@1,kill-template@0") \
            == {1: 15}
        assert template_kill_plan("") == {}
        assert joiner_kill_plan("") == {}

    def test_default_op_index_is_zero(self):
        assert template_kill_plan("kill-template") == {0: 9}

    def test_http_middleware_never_sees_template_verbs(self):
        # the fork server consumes these by op index; the request-path
        # engine must not double-fire them on HTTP traffic
        eng = ChaosEngine(parse_spec("kill-template@0,kill-joiner:9@1"))
        assert eng.schedule == []
        assert eng.persistent == []
        assert eng.node_faults == [] and eng.peer_faults == []


# ---------------------------------------------------------------------------
# router readiness fence
# ---------------------------------------------------------------------------


class _FencePool:
    def __init__(self):
        self.health = {}
        self.health_calls = []
        self.calls = []

    async def check_health(self, ip, timeout=2.0):
        self.health_calls.append(ip)
        return self.health.get(ip, True)

    async def call_worker(self, ip, fn_name, method, body, headers,
                          timeout=None, subtree=None, sel_ips=None):
        self.calls.append(ip)
        if ip in self.health and not self.health[ip]:
            raise WorkerCallError(f"worker {ip} down", worker=ip)
        return {"served_by": ip}


async def _local_call(method, args, kwargs, timeout):
    return {"served_by": "local"}


def _dispatch(router, pool, ips=None):
    return router.dispatch(pool=pool, ips=ips or IPS, my_ip=MY_IP,
                           method=None, args=[], kwargs={}, headers=None,
                           timeout=None, local_call=_local_call)


class TestReadinessFence:
    def test_warming_replica_probed_fresh_then_admitted(self):
        async def body():
            router = Router(slots_per_replica=4, health_ttl_s=60)
            pool = _FencePool()
            router.mark_warming(IPS[2])
            before = _fence("admitted")
            out = await _dispatch(router, pool, ips=[IPS[2]])
            return router, pool, out, _fence("admitted") - before
        router, pool, out, admitted = asyncio.run(body())
        assert out == {"served_by": IPS[2]}
        assert pool.health_calls == [IPS[2]], \
            "the warming replica's FIRST request must be probe-gated"
        assert admitted == 1
        assert not router._is_warming(IPS[2])

    def test_warming_replica_ordered_last(self):
        async def body():
            router = Router(slots_per_replica=4, health_ttl_s=60)
            pool = _FencePool()
            router.mark_warming(IPS[0])
            for _ in range(4):
                await _dispatch(router, pool)
            return pool.calls
        calls = asyncio.run(body())
        # an idle fleet with healthy peers never sends the first requests
        # to the still-warming replica
        assert calls[0] in (IPS[1], IPS[2])
        assert calls[1] in (IPS[1], IPS[2])

    def test_dead_boot_stays_fenced_and_counts_blocked(self):
        async def body():
            router = Router(slots_per_replica=4, health_ttl_s=60)
            pool = _FencePool()
            pool.health[IPS[2]] = False
            router.mark_warming(IPS[2])
            before = _fence("blocked")
            out = await _dispatch(router, pool, ips=[IPS[2]])
            return router, pool, out, _fence("blocked") - before
        router, pool, out, blocked = asyncio.run(body())
        assert out == {"served_by": "local"}      # nothing admissible
        assert pool.calls == []                   # request never reached it
        assert blocked == 1
        assert router._is_warming(IPS[2]), \
            "a failed probe must keep the fence up, not admit the replica"

    def test_fence_expiry_counts_and_releases(self):
        router = Router(slots_per_replica=4, health_ttl_s=60)
        router.warming_ttl_s = 0.01
        router.mark_warming(IPS[0])
        before = _fence("expired")
        time.sleep(0.03)
        assert router._is_warming(IPS[0]) is False
        assert _fence("expired") - before == 1
        assert IPS[0] not in router._warming

    def test_membership_growth_fences_and_prober_admits_without_traffic(
            self):
        # the production wiring: a new ip in the membership is fenced,
        # and the BACKGROUND prober clears the fence — no request (and no
        # failover of the settled fleet) is needed for the new capacity
        # to become admissible
        async def body():
            router = Router(slots_per_replica=4, health_ttl_s=60)
            router.warming_probe_s = 0.01
            pool = _FencePool()
            router.observe_membership(IPS[:2], pool)      # baseline fleet
            assert not router._warming
            router.observe_membership(IPS, pool)          # scale-out
            assert router._is_warming(IPS[2])
            for _ in range(100):
                if not router._warming:
                    break
                await asyncio.sleep(0.01)
            return router, pool
        router, pool = asyncio.run(body())
        assert not router._warming, \
            "the background prober never admitted the warming replica"
        assert IPS[2] in pool.health_calls
        assert pool.calls == [], \
            "clearing the fence must not require routing a request"

    def test_prober_keeps_dead_boot_fenced(self):
        async def body():
            router = Router(slots_per_replica=4, health_ttl_s=60)
            router.warming_probe_s = 0.01
            pool = _FencePool()
            pool.health[IPS[2]] = False
            router.observe_membership(IPS[:2], pool)
            router.observe_membership(IPS, pool)
            await asyncio.sleep(0.05)
            return router
        router = asyncio.run(body())
        assert router._is_warming(IPS[2]), \
            "a failing probe must keep the fence up"

    def test_departed_warming_ip_drops_fence(self):
        router = Router(slots_per_replica=4, health_ttl_s=60)
        router.observe_membership(IPS[:2])
        router.observe_membership(IPS)
        assert router._is_warming(IPS[2])
        before = _fence("departed")
        router.observe_membership(IPS[:2])      # scaled back down
        assert not router._warming
        assert _fence("departed") - before == 1


# ---------------------------------------------------------------------------
# autoscaler growth cap
# ---------------------------------------------------------------------------


class TestGrowthCap:
    def test_gate_off_keeps_2x_status_quo(self):
        from kubetorch_tpu.controller.app import _growth_cap
        assert _growth_cap(4, 1.5, fast_s=0.0, factor=8) == 8

    def test_measured_fast_cold_start_relaxes_cap(self):
        from kubetorch_tpu.controller.app import _growth_cap
        assert _growth_cap(4, 3.0, fast_s=5.0, factor=8) == 32
        assert _growth_cap(1, 5.0, fast_s=5.0, factor=16) == 16

    def test_slow_or_unmeasured_cold_start_never_relaxes(self):
        from kubetorch_tpu.controller.app import _growth_cap
        assert _growth_cap(4, 9.0, fast_s=5.0, factor=8) == 8
        # gauge 0/absent = no evidence: configuration optimism loses
        assert _growth_cap(4, 0.0, fast_s=5.0, factor=8) == 8

    def test_factor_floor_is_2x(self):
        from kubetorch_tpu.controller.app import _growth_cap
        assert _growth_cap(4, 1.0, fast_s=5.0, factor=1) == 8


class TestFreshestColdStart:
    """The gate's fleet aggregate: recency beats optimism — one historic
    fast boot (warm cache, live template) must not keep the relaxed cap
    after current boots turn slow again."""

    def _f(self, pairs):
        from kubetorch_tpu.controller.app import _freshest_cold_start
        return _freshest_cold_start(pairs)

    def test_newest_boot_wins_over_historic_fast_one(self):
        assert self._f([(100.0, 1.5), (200.0, 45.0)]) == 45.0
        assert self._f([(200.0, 1.5), (100.0, 45.0)]) == 1.5

    def test_untimestamped_fleet_aggregates_pessimistically(self):
        assert self._f([(0.0, 3.0), (0.0, 9.0), (0.0, 4.0)]) == 9.0

    def test_timestamped_measurement_beats_untimestamped(self):
        assert self._f([(0.0, 1.0), (50.0, 7.0)]) == 7.0

    def test_empty_means_unmeasured(self):
        assert self._f([]) == 0.0


# ---------------------------------------------------------------------------
# soak schedule: the scale-to-zero → cold-burst episode (draw 7)
# ---------------------------------------------------------------------------


class TestColdBurstEpisode:
    ACTIONS = ("scale-to-zero", "cold-burst")

    def test_episode_present_deterministic_and_well_formed(self):
        hits = 0
        for seed in range(20):
            s1 = soak_schedule.generate(seed, "serve", 24)
            s2 = soak_schedule.generate(seed, "serve", 24)
            assert s1.events == s2.events, f"seed {seed} not deterministic"
            stz = [e for e in s1.events if e.action == "scale-to-zero"]
            burst = [e for e in s1.events if e.action == "cold-burst"]
            assert len(stz) == len(burst)     # always drawn as a pair
            if not stz:
                continue
            hits += 1
            assert len(stz) == 1
            assert stz[0].at_op < burst[0].at_op, \
                "the fleet must hit zero BEFORE the burst back"
            assert stz[0].target == burst[0].target == "gateway:0"
            assert stz[0].verb == "kill-template"
            assert burst[0].verb == "kill-joiner"
        assert hits >= 1, "no serve seed in 0..19 drew the episode"

    def test_store_profile_never_draws_the_episode(self):
        for seed in range(20):
            s = soak_schedule.generate(seed, "store", 24)
            assert not any(e.action in self.ACTIONS for e in s.events)


# ---------------------------------------------------------------------------
# supervisor spawn deadline: a silent template must time out, not hang
# ---------------------------------------------------------------------------


class TestSupervisorSpawnDeadline:
    def _patch_template_cmd(self, monkeypatch, code):
        """Make TemplateSupervisor._spawn launch ``python -c code`` in
        place of the real template module."""
        import subprocess as sp
        import sys
        import types

        from kubetorch_tpu.serving import warm_template as wt
        procs = []

        def fake_popen(cmd, **kw):
            p = sp.Popen([sys.executable, "-c", code], stdout=sp.PIPE,
                         stderr=sp.DEVNULL, text=True)
            procs.append(p)
            return p

        monkeypatch.setattr(
            wt, "subprocess",
            types.SimpleNamespace(Popen=fake_popen, PIPE=sp.PIPE,
                                  DEVNULL=sp.DEVNULL))
        return wt, procs

    def test_silent_wedged_template_times_out_and_is_killed(
            self, tmp_path, monkeypatch):
        # alive but never prints READY (e.g. wedged before the announce,
        # stderr-only failure): the deadline must fire while the reader
        # is blocked, and the child must not outlive the TimeoutError
        wt, procs = self._patch_template_cmd(
            monkeypatch, "import time; time.sleep(60)")
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            wt.TemplateSupervisor({"weights": str(tmp_path / "w.npy"),
                                   "result_dir": str(tmp_path)},
                                  timeout=1.0)
        assert time.monotonic() - t0 < 10
        procs[0].wait(timeout=10)
        assert procs[0].poll() is not None, "wedged template leaked"

    def test_dead_template_raises_promptly(self, tmp_path, monkeypatch):
        wt, procs = self._patch_template_cmd(monkeypatch, "pass")
        with pytest.raises(RuntimeError, match="died before READY"):
            wt.TemplateSupervisor({"weights": str(tmp_path / "w.npy"),
                                   "result_dir": str(tmp_path)},
                                  timeout=30.0)


# ---------------------------------------------------------------------------
# slow tier: engine AOT equivalence + the template fork chaos drill
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dense():
    from kubetorch_tpu.models.llama import LlamaConfig, llama_init
    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.mark.slow
class TestEngineAOT:
    def test_aot_tokens_match_jit_and_second_boot_hits(self, dense,
                                                       tmp_path):
        from kubetorch_tpu.serve import GenerationEngine

        params, cfg = dense
        prompt = [5, 17, 42, 99]

        def run(cache):
            eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                                   prefill_buckets=(8,), aot_cache=cache)
            h = eng.submit(prompt, max_new_tokens=8)
            while eng.step():
                pass
            stats = eng.aot_stats()
            eng.stop()
            return h.result(timeout=0), stats

        want, _ = run(None)                          # plain jit baseline
        got_cold, cold = run(AOTCompileCache(tmp_path))
        got_warm, warm = run(AOTCompileCache(tmp_path))
        assert got_cold == want
        assert got_warm == want, \
            "a deserialized executable produced different tokens"
        assert cold.get("miss", 0) >= 1 and cold.get("publish", 0) >= 1
        assert warm.get("hit", 0) >= 2               # prefill + decode
        assert warm.get("miss", 0) == 0


@pytest.mark.slow
class TestTemplateForkDrill:
    def _spec(self, tmp_path, dense, chaos):
        from kubetorch_tpu.serving.warm_template import save_weights
        params, _ = dense
        wpath = tmp_path / "weights.npy"
        save_weights(wpath, params)
        return {"weights": str(wpath),
                "model": {"kind": "llama-tiny"},
                "engine": {"slots": 2, "max_len": 64,
                           "prefill_buckets": [8]},
                "probe_prompt": [1, 2, 3], "probe_tokens": 2,
                "result_dir": str(tmp_path / "out"),
                "aot_root": str(tmp_path / "aot"),
                "chaos": chaos}

    @staticmethod
    def _wait_results(out_dir, names, timeout=240.0):
        deadline = time.monotonic() + timeout
        results = {}
        while time.monotonic() < deadline:
            for n in list(names):
                p = Path(out_dir) / f"{n}.json"
                if n not in results and p.exists():
                    results[n] = json.loads(p.read_text())
            if len(results) == len(names):
                return results
            time.sleep(0.25)
        raise TimeoutError(f"missing results: {set(names) - set(results)}")

    def test_sigkill_template_and_joiner_converge_with_no_shm_leak(
            self, dense, tmp_path):
        from kubetorch_tpu.serving.warm_template import TemplateSupervisor

        before = set(glob.glob("/dev/shm/kt-shm-*"))
        # joiner 0 dies mid-boot (weights attached, engine never up);
        # the RE-fork of 0 is fork-op 2, where the template itself is
        # SIGKILLed — the supervisor must respawn it with the schedule
        # consumed and still land all N replicas
        spec = self._spec(tmp_path, dense,
                          "kill-joiner@0,kill-template:9@2")
        with TemplateSupervisor(spec, timeout=240.0) as sup:
            sup.fork(0)
            sup.fork(1)
            got = self._wait_results(spec["result_dir"], ["replica_1"])
            assert got["replica_1"]["ok"] is True
            assert not (Path(spec["result_dir"]) / "replica_0.json").exists()

            out = sup.fork(0)                 # kill-template fires here
            assert out.get("ok") is True
            assert sup.respawns == 1, \
                "SIGKILLed template was not respawned exactly once"
            got = self._wait_results(spec["result_dir"], ["replica_0"])
            assert got["replica_0"]["ok"] is True
            assert got["replica_0"]["phases"]["import"] == 0.0, \
                "forked replica re-paid the import bill"
        after = set(glob.glob("/dev/shm/kt-shm-*"))
        assert after - before == set(), \
            f"leaked /dev/shm segments: {sorted(after - before)}"

    def test_clean_burst_all_replicas_land(self, dense, tmp_path):
        from kubetorch_tpu.serving.warm_template import TemplateSupervisor

        before = set(glob.glob("/dev/shm/kt-shm-*"))
        spec = self._spec(tmp_path, dense, "")
        with TemplateSupervisor(spec, timeout=240.0) as sup:
            for i in range(2):
                assert sup.fork(i).get("ok") is True
            got = self._wait_results(spec["result_dir"],
                                     ["replica_0", "replica_1"])
            assert all(r["ok"] for r in got.values())
            assert sup.respawns == 0
        after = set(glob.glob("/dev/shm/kt-shm-*"))
        assert after - before == set()

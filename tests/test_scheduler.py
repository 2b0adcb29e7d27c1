"""Preemptive, priority-tiered scheduling (ISSUE 8): the admission queue +
capacity book in front of every controller placement, preemption via the
PR 6 drain path (SIGTERM → ``kt.drain_requested()`` → ``Checkpointer``
commit inside the grace window), and transparent checkpoint-resume when
capacity frees — ``make test-sched``.

The acceptance scenario rides REAL processes: a numpy training loop in a
subprocess is preempted through the shared SIGTERM+grace+SIGKILL contract
(``chaos.deliver_term_with_grace`` — the same delivery the ``term-rank``
chaos verb uses), commits inside the window, and resumes with a
``tree_fingerprint`` matching a clean reload and zero lost committed steps.
"""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

pytestmark = [pytest.mark.level("minimal"), pytest.mark.sched]

from kubetorch_tpu.controller.app import ControllerState
from kubetorch_tpu.controller.scheduler import (
    _PREEMPTIONS, CapacityBook, CostPolicy, MaxMinFairnessPolicy, Scheduler,
    SchedulingPolicy, _class_from_manifest, _parse_capacity,
    _shrunk_mesh_env, parse_priority, tier_of)
from kubetorch_tpu.train import checkpoint as ck
from tests.assets.threaded_server import ThreadedAiohttpServer

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


def _store_app(root):
    from kubetorch_tpu.data_store.store_server import create_store_app
    return lambda: create_store_app(str(root))


class FakeBackend:
    """Just enough backend for the scheduler: applies are bookkeeping,
    ``signal_pods`` drains instantly when cooperative (the pods 'commit and
    exit') and never when not (the forced-eviction case)."""

    server_port = 32300

    def __init__(self, cooperative=True):
        self.pods = {}
        self.applies = []
        self.signals = []
        self.cooperative = cooperative

    def apply(self, ns, name, manifest, env):
        key = f"{ns}/{name}"
        replicas = int((manifest.get("spec") or {}).get("replicas", 1))
        self.applies.append((key, replicas, dict(env)))
        self.pods[key] = replicas
        return {"pod_ips": [f"10.0.0.{i}" for i in range(replicas)],
                "service_url": (f"http://10.0.0.0:{self.server_port}"
                                if replicas else None)}

    def pod_ips(self, ns, name):
        return [f"10.0.0.{i}"
                for i in range(self.pods.get(f"{ns}/{name}", 0))]

    def signal_pods(self, ns, name, sig, grace_s=0.0):
        key = f"{ns}/{name}"
        self.signals.append((key, sig, grace_s))
        if self.cooperative:
            self.pods[key] = 0        # drained: committed and exited
        return 1

    def delete(self, ns, name, kind=None):
        return self.pods.pop(f"{ns}/{name}", None) is not None

    def shutdown(self):
        pass


def _state(backend, capacity, policy=None, state_dir=None):
    state = ControllerState(backend=backend, state_dir=state_dir)
    state.scheduler = Scheduler(state, capacity=capacity, policy=policy)
    return state


def _rec(state, name, width, priority=None, device_class="cpu",
         metadata=None, drain_grace_s=None, ns="default"):
    sched = {"device_class": device_class, "width": width}
    if priority is not None:
        sched["priority"] = priority
    if drain_grace_s is not None:
        sched["drain_grace_s"] = drain_grace_s
    record = {"namespace": ns, "name": name,
              "manifest": {"kind": "Deployment",
                           "spec": {"replicas": width}},
              "metadata": metadata or {}, "launch_id": name,
              "created_at": time.time(), "updated_at": time.time(),
              "scheduling": sched}
    state.workloads[f"{ns}/{name}"] = record
    return record


async def _submit(state, record):
    return await state.sched().submit(
        record, record["manifest"], {})


# ---------------------------------------------------------------------------
# Tiers, capacity book, demand inference
# ---------------------------------------------------------------------------


def test_parse_priority_and_tier_bands():
    assert parse_priority("high") == 80 and tier_of(80) == "high"
    assert parse_priority("batch") == 20 and tier_of(20) == "batch"
    assert parse_priority(None) == 50 and tier_of(50) == "normal"
    assert parse_priority("junk") == 50       # unparseable → default
    assert parse_priority(999) == 100 and parse_priority(-3) == 0
    assert tier_of(69) == "normal" and tier_of(70) == "high"
    assert tier_of(39) == "batch" and tier_of(40) == "normal"


def test_capacity_env_parsing_skips_malformed_tokens():
    assert _parse_capacity("cpu=8,v5e=16") == {"cpu": 8, "v5e": 16}
    assert _parse_capacity(" cpu = 4 ,junk,v5p=oops,v5e=-2") == \
        {"cpu": 4, "v5e": 0}
    assert _parse_capacity(None) == {} and _parse_capacity("") == {}


def test_capacity_book_accounting():
    book = CapacityBook({"cpu": 4, "v5e": 8})
    assert book.limited and book.free("cpu") == 4
    book.allocate("d/a", "cpu", 3, 20)
    assert book.free("cpu") == 1 and book.fits("cpu", 1)
    assert not book.fits("cpu", 2)
    assert book.free("v5p") == 0            # unlisted class doesn't exist
    book.resize("d/a", 2)
    assert book.free("cpu") == 2
    assert book.release("d/a")["width"] == 2
    assert book.free("cpu") == 4 and book.release("d/a") is None
    # unlimited book: everything fits, free is None
    assert not CapacityBook().limited
    assert CapacityBook().fits("v5p", 10 ** 6)


def test_demand_inferred_from_gke_selector():
    manifest = {"spec": {"replicas": 4, "template": {"spec": {
        "nodeSelector": {
            "cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
            "cloud.google.com/gke-tpu-topology": "2x4"}}}}}
    assert _class_from_manifest(manifest) == "v5e"
    assert _class_from_manifest({"spec": {}}) == "cpu"
    cls, width = Scheduler.demand_for(
        {"scheduling": None, "manifest": manifest})
    assert (cls, width) == ("v5e", 4)
    # explicit scheduling block wins over inference
    cls, width = Scheduler.demand_for(
        {"scheduling": {"device_class": "v5p", "width": 2},
         "manifest": manifest})
    assert (cls, width) == ("v5p", 2)


# ---------------------------------------------------------------------------
# Admission: pass-through, queueing, preemption
# ---------------------------------------------------------------------------


def test_unlimited_book_is_pass_through():
    fb = FakeBackend()
    state = _state(fb, capacity={})

    async def go():
        a = _rec(state, "a", 3)
        out = await _submit(state, a)
        assert "queued" not in out and len(out["pod_ips"]) == 3
        assert not state.sched().queue
        assert state.sched().book.allocations["default/a"]["width"] == 3

    asyncio.run(go())


def test_full_book_queues_same_tier():
    fb = FakeBackend()
    state = _state(fb, capacity={"cpu": 2})

    async def go():
        await _submit(state, _rec(state, "a", 2, priority="batch"))
        out = await _submit(state, _rec(state, "b", 1, priority="batch"))
        assert out["queued"] and out["tier"] == "batch"
        assert out["position"] == 0
        assert state.workloads["default/b"]["status"] == "queued"
        assert not fb.signals, "same tier must never preempt"
        # b placed automatically once a releases its slots
        state.workloads.pop("default/a")
        await state.sched().release("default", "a")
        await state.sched().kick()
        assert not state.sched().queue
        assert state.sched().book.allocations["default/b"]["width"] == 1
        assert "status" not in state.workloads["default/b"]

    asyncio.run(go())


def test_higher_tier_preempts_batch_via_drain_path():
    fb = FakeBackend()
    state = _state(fb, capacity={"cpu": 2})

    async def go():
        import signal
        await _submit(state, _rec(state, "batchjob", 2, priority="batch",
                                  drain_grace_s=5.0))
        before = _PREEMPTIONS.value(tier="batch", outcome="drained")
        out = await _submit(state, _rec(state, "serve", 2, priority="high"))
        # the high-tier deploy PLACED (not queued) by evicting the batch job
        assert "queued" not in out and len(out["pod_ips"]) == 2
        assert fb.signals == [("default/batchjob", signal.SIGTERM, 5.0)]
        assert _PREEMPTIONS.value(tier="batch",
                                  outcome="drained") == before + 1
        # victim: evicted (scaled to 0), re-queued at its own priority
        assert fb.pods["default/batchjob"] == 0
        assert state.workloads["default/batchjob"]["status"] == "preempted"
        [entry] = state.sched().queue
        assert entry["key"] == "default/batchjob" and entry["preempted"]
        assert entry["priority"] == 20 and entry["width"] == 2
        led = state.sched().ledger[-1]
        assert led["phase"] == "evicted" and led["drained"] is True
        assert led["preemptor"] == "default/serve"

        # transparent resume: delete the preemptor → victim re-places
        state.workloads.pop("default/serve")
        await state.sched().release("default", "serve")
        await state.sched().kick()
        assert not state.sched().queue
        assert fb.pods["default/batchjob"] == 2
        assert state.sched().ledger[-1]["phase"] == "resumed"
        assert "status" not in state.workloads["default/batchjob"]

    asyncio.run(go())


def test_same_tier_and_lower_tier_never_preempt():
    fb = FakeBackend()
    state = _state(fb, capacity={"cpu": 1})

    async def go():
        await _submit(state, _rec(state, "a", 1, priority="normal"))
        # higher priority NUMBER, same tier → queue, don't evict
        out = await _submit(state, _rec(state, "b", 1, priority=65))
        assert out["queued"] and not fb.signals
        # lower tier → queue
        out = await _submit(state, _rec(state, "c", 1, priority="batch"))
        assert out["queued"] and not fb.signals

    asyncio.run(go())


def test_forced_eviction_when_pods_ignore_sigterm():
    fb = FakeBackend(cooperative=False)       # pods squat past the grace
    state = _state(fb, capacity={"cpu": 1})

    async def go():
        await _submit(state, _rec(state, "stubborn", 1, priority="batch",
                                  drain_grace_s=0.3))
        before = _PREEMPTIONS.value(tier="batch", outcome="forced")
        t0 = time.monotonic()
        out = await _submit(state, _rec(state, "vip", 1, priority="high"))
        assert "queued" not in out
        assert time.monotonic() - t0 >= 0.3   # the grace window was granted
        assert _PREEMPTIONS.value(tier="batch",
                                  outcome="forced") == before + 1
        led = state.sched().ledger[-1]
        assert led["drained"] is False and led["phase"] == "evicted"
        # the eviction (apply replicas=0) is the backstop for squatters
        assert ("default/stubborn", 0) in [(k, r)
                                           for k, r, _ in fb.applies]

    asyncio.run(go())


def test_reduced_width_resume_shrinks_mesh():
    fb = FakeBackend()
    state = _state(fb, capacity={"cpu": 4})

    async def go():
        meta = {"KT_DISTRIBUTED_CONFIG": {
            "distribution_type": "spmd", "workers": 4,
            "mesh": {"data": 4}}}
        await _submit(state, _rec(state, "widejob", 4, priority="batch",
                                  metadata=meta))
        await _submit(state, _rec(state, "vip", 2, priority="high"))
        # widejob evicted and queued at width 4; only 2 slots remain free
        assert state.sched().queue[0]["width"] == 4
        assert state.sched().book.free("cpu") == 2
        await state.sched().kick()
        # resumed at reduced width with the mesh re-solved (data 4 → 2)
        assert not state.sched().queue
        alloc = state.sched().book.allocations["default/widejob"]
        assert alloc["width"] == 2
        key, replicas, env = fb.applies[-1]
        assert key == "default/widejob" and replicas == 2
        assert json.loads(env["KT_MESH"]) == {"data": 2}

    asyncio.run(go())


def test_mesh_that_cannot_shrink_stays_queued():
    # tensor=4 needs all 4 devices: no reduced-width placement exists
    record = {"metadata": {"KT_DISTRIBUTED_CONFIG": {"mesh": {"tensor": 4}}}}
    assert _shrunk_mesh_env(record, 4, 2) is None
    # no declared mesh: plain replicas shrink freely (empty override)
    assert _shrunk_mesh_env({"metadata": {}}, 4, 2) == {}

    fb = FakeBackend()
    state = _state(fb, capacity={"cpu": 4})

    async def go():
        meta = {"KT_DISTRIBUTED_CONFIG": {"mesh": {"tensor": 4}}}
        await _submit(state, _rec(state, "tp", 4, priority="batch",
                                  metadata=meta))
        await _submit(state, _rec(state, "vip", 2, priority="high"))
        await state.sched().kick()
        # still queued: 2 free slots can't hold a tensor=4 program
        assert state.sched().queue[0]["key"] == "default/tp"
        # preemptor done → full width frees → tp resumes at 4
        state.workloads.pop("default/vip")
        await state.sched().release("default", "vip")
        await state.sched().kick()
        assert not state.sched().queue
        assert state.sched().book.allocations["default/tp"]["width"] == 4

    asyncio.run(go())


def test_initial_scale_zero_charges_no_slots():
    """An autoscaling deploy with initial_scale=0 places ZERO pods — the
    book must not charge a phantom slot for it (the slot materializes at
    cold start, through the scale path)."""
    fb = FakeBackend()
    state = _state(fb, capacity={"cpu": 2})

    async def go():
        rec = _rec(state, "lazy", 1, priority="batch")
        rec["autoscaling"] = {"min_scale": 0, "initial_scale": 0}
        rec["manifest"]["spec"]["replicas"] = 0
        rec["expected_pods"] = 0
        out = await _submit(state, rec)
        assert "queued" not in out
        assert state.sched().book.used("cpu") == 0
        await state.sched().scale(rec, 1, "cold start")
        assert state.sched().book.used("cpu") == 1

    asyncio.run(go())


def test_autoscale_scale_up_clamps_to_capacity():
    fb = FakeBackend()
    state = _state(fb, capacity={"cpu": 3})

    async def go():
        rec = _rec(state, "svc", 1, priority="normal")
        await _submit(state, rec)
        await state.sched().scale(rec, 5, "inflight burst")
        # clamped to the book: 1 running + 2 free
        assert state.sched().book.allocations["default/svc"]["width"] == 3
        assert fb.pods["default/svc"] == 3
        assert any("clamped" in e["message"] for e in state.events)
        # scale to zero frees everything
        await state.sched().scale(rec, 0, "idle")
        assert "default/svc" not in state.sched().book.allocations
        assert rec["scaled_to_zero"]

    asyncio.run(go())


# ---------------------------------------------------------------------------
# Policies & heterogeneity-aware scoring
# ---------------------------------------------------------------------------


def test_throughput_ewma_and_static_fallback():
    state = _state(FakeBackend(), capacity={})
    s = state.sched()
    s.note_throughput("d/j", "v5e", execute_sum=10.0, execute_count=100)
    assert s.throughput_score("d/j", "v5e") == pytest.approx(10.0)
    s.note_throughput("d/j", "v5e", execute_sum=5.0, execute_count=100)
    assert s.throughput_score("d/j", "v5e") == pytest.approx(13.0)  # EWMA
    # unmeasured class: scaled by the static speed ratio off the anchor
    v5p = s.throughput_score("d/j", "v5p")
    assert v5p == pytest.approx(13.0 * 459 / 197)
    # a workload with no measurements at all falls back to the prior
    assert s.throughput_score("d/x", "cpu") == 1.0
    assert s.throughput_score("d/x", "v5e") == pytest.approx(197.0)


def test_fifo_priority_order_resume_before_new():
    state = _state(FakeBackend(), capacity={})
    pol = SchedulingPolicy()
    q = [{"key": "a", "priority": 50, "seq": 1},
         {"key": "b", "priority": 80, "seq": 2},
         {"key": "c", "priority": 50, "seq": 3, "preempted": True},
         {"key": "d", "priority": 50, "seq": 4}]
    assert [e["key"] for e in pol.order(q, state.sched())] == \
        ["b", "c", "a", "d"]


def test_max_min_fairness_orders_by_accumulated_service():
    state = _state(FakeBackend(), capacity={}, policy="max-min-fairness")
    s = state.sched()
    assert isinstance(s.policy, MaxMinFairnessPolicy)
    s._service = {"d/greedy": 500.0, "d/starved": 1.0}
    q = [{"key": "d/greedy", "priority": 20, "seq": 1},
         {"key": "d/starved", "priority": 20, "seq": 2},
         {"key": "d/vip", "priority": 80, "seq": 3}]
    # tier still dominates; within the batch tier the starved job wins
    assert [e["key"] for e in s.policy.order(q, s)] == \
        ["d/vip", "d/starved", "d/greedy"]


def test_cost_policy_picks_cheapest_adequate_class(monkeypatch):
    monkeypatch.setenv("KT_SCHED_COST", "v5e=1.2,v5p=4.2")
    state = _state(FakeBackend(), capacity={"v5e": 8, "v5p": 8})
    s = state.sched()
    s.note_throughput("d/j", "v5e", execute_sum=10.0, execute_count=100)
    entry = {"key": "d/j", "priority": 20, "seq": 1, "device_class": "v5e",
             "width": 2}
    candidates = {"v5e": 8, "v5p": 8}
    # throughput-only (default policy): v5p wins on the speed ratio
    assert SchedulingPolicy().choose_class(entry, candidates, s) == "v5p"
    # per-dollar: 10/1.2 ops/$ on v5e beats (10·459/197)/4.2 on v5p
    assert CostPolicy().choose_class(entry, candidates, s) == "v5e"


# ---------------------------------------------------------------------------
# End-to-end (in-process): preempt → drain-commit → evict → resume, with a
# REAL Checkpointer against a real store server
# ---------------------------------------------------------------------------


class ThreadTrainerBackend(FakeBackend):
    """'Pods' for the batch job are a thread running a genuine numpy
    training loop on the commit-marker protocol; ``signal_pods`` delivers
    the drain (the thread commits and exits, exactly what a SIGTERM'd rank
    does — the real signal plumbing is proven by the subprocess acceptance
    test below and test_elastic's term-rank e2e)."""

    def __init__(self, store_url, ckpt_key, trainee="batchjob"):
        super().__init__()
        self.store_url, self.ckpt_key, self.trainee = \
            store_url, ckpt_key, trainee
        self.threads = {}
        self.drain_events = {}
        self.observed = {}      # the trainer's self-reported state

    def apply(self, ns, name, manifest, env):
        key = f"{ns}/{name}"
        replicas = int((manifest.get("spec") or {}).get("replicas", 1))
        self.applies.append((key, replicas, dict(env)))
        if name != self.trainee:
            self.pods[key] = replicas
            return {"pod_ips": [f"10.1.0.{i}" for i in range(replicas)]}
        if replicas == 0:
            ev = self.drain_events.get(key)
            if ev is not None:
                ev.set()
            t = self.threads.get(key)
            if t is not None:
                t.join(timeout=10)
            self.pods[key] = 0
            return {"pod_ips": []}
        ev = threading.Event()
        self.drain_events[key] = ev
        t = threading.Thread(target=self._train, args=(key, ev),
                             daemon=True)
        self.threads[key] = t
        t.start()
        self.pods[key] = replicas
        return {"pod_ips": [f"10.1.0.{i}" for i in range(replicas)]}

    def pod_ips(self, ns, name):
        key = f"{ns}/{name}"
        if name == self.trainee:
            t = self.threads.get(key)
            return ["10.1.0.0"] if t is not None and t.is_alive() else []
        return super().pod_ips(ns, name)

    def signal_pods(self, ns, name, sig, grace_s=0.0):
        key = f"{ns}/{name}"
        self.signals.append((key, sig, grace_s))
        ev = self.drain_events.get(key)
        if ev is not None:
            ev.set()
            return 1
        return super().signal_pods(ns, name, sig, grace_s)

    def _train(self, key, drain_ev):
        ckpt = ck.Checkpointer(self.ckpt_key, store_url=self.store_url,
                               every=10 ** 9)   # periodic commits OFF
        restored = ckpt.restore()
        if restored is not None:
            tree, step = restored
            params, resumed_from = tree["w"], step
        else:
            params, step, resumed_from = np.zeros(8, np.float64), 0, None
        while not drain_ev.is_set():
            params = params + 1.0
            step += 1
            self.observed[key] = {
                "step": step, "resumed_from": resumed_from,
                "fingerprint": ck.tree_fingerprint({"w": params})}
            time.sleep(0.02)
        # the grace window: flush + commit, then vacate
        ckpt.flush()
        ckpt.save({"w": params}, step)
        self.observed[key] = {
            "step": step, "resumed_from": resumed_from, "drained": True,
            "fingerprint": ck.tree_fingerprint({"w": params})}


def _wait(predicate, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def test_preempt_drain_commit_resume_end_to_end(tmp_path):
    """The full scheduler loop in-process: a batch trainer (real
    ``Checkpointer``, real store server, periodic commits OFF) is preempted
    by a high-tier deploy; its ONLY commit is the drain-path one, landing
    inside the grace window; after the high-tier workload finishes it
    resumes automatically from exactly that step with a fingerprint
    matching a clean reload — zero committed steps lost."""
    with ThreadedAiohttpServer(_store_app(tmp_path / "store")) as srv:
        key = "sched/e2e"
        fb = ThreadTrainerBackend(srv.url, key)
        state = _state(fb, capacity={"cpu": 2})
        bkey = "default/batchjob"

        async def phase1():
            await _submit(state, _rec(state, "batchjob", 2,
                                      priority="batch", drain_grace_s=15.0))
            assert await asyncio.to_thread(
                _wait, lambda: fb.observed.get(bkey, {}).get("step", 0) >= 3)
            assert ck.commit_info(key, store_url=srv.url) is None, \
                "no commit may exist before the drain"
            # the preemptor: placement blocks until the victim drained
            out = await _submit(state, _rec(state, "serve", 2,
                                            priority="high"))
            assert "queued" not in out

        asyncio.run(phase1())
        drained = fb.observed[bkey]
        assert drained.get("drained"), "victim never took the drain path"
        info = ck.commit_info(key, store_url=srv.url)
        assert info is not None and info["step"] == drained["step"], \
            "the drain-path commit must capture the LAST completed step"
        assert state.sched().ledger[-1]["drained"] is True

        async def phase2():
            # preemptor finishes → the batch job resumes, no manual steps
            state.workloads.pop("default/serve")
            await state.sched().release("default", "serve")
            await state.sched().kick()
            assert await asyncio.to_thread(
                _wait, lambda: fb.observed.get(bkey, {}).get(
                    "resumed_from") == drained["step"])

        asyncio.run(phase2())
        # zero lost steps + bit-identical state: a clean reload of the
        # committed checkpoint fingerprints the drained params exactly
        reloaded, step = ck.Checkpointer(key, store_url=srv.url).restore()
        assert step == drained["step"]
        assert ck.tree_fingerprint(reloaded) == drained["fingerprint"]
        assert _wait(lambda: fb.observed[bkey].get("step", 0)
                     > drained["step"])
        # teardown the resumed trainer thread
        asyncio.run(state.sched().scale(
            state.workloads[bkey], 0, "test teardown"))


# ---------------------------------------------------------------------------
# The chaos acceptance: a REAL subprocess preempted through the REAL signal
# path (install_sigterm_drain + deliver_term_with_grace — the term-rank
# contract), then resumed by the scheduler
# ---------------------------------------------------------------------------


class SubprocessTrainerBackend(FakeBackend):
    """The batch job's pod is a real OS process running
    ``tests/assets/preemptible_trainer.py``; preemption delivers the
    SIGTERM + grace-window SIGKILL pair via the shared chaos contract."""

    def __init__(self, store_url, ckpt_key, trainee="batchjob"):
        super().__init__()
        self.store_url, self.ckpt_key, self.trainee = \
            store_url, ckpt_key, trainee
        self.procs = {}

    def _env(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("KT_CHAOS", None)
        # the package parent, so the subprocess imports THIS checkout
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(ck.__file__))))
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        return env

    def apply(self, ns, name, manifest, env):
        key = f"{ns}/{name}"
        replicas = int((manifest.get("spec") or {}).get("replicas", 1))
        self.applies.append((key, replicas, dict(env)))
        if name != self.trainee:
            self.pods[key] = replicas
            return {"pod_ips": [f"10.2.0.{i}" for i in range(replicas)]}
        proc = self.procs.get(key)
        if replicas == 0:
            if proc is not None and proc.poll() is None:
                proc.kill()
            self.pods[key] = 0
            return {"pod_ips": []}
        if proc is None or proc.poll() is not None:
            self.procs[key] = subprocess.Popen(
                [sys.executable,
                 os.path.join(ASSETS, "preemptible_trainer.py"),
                 self.store_url, self.ckpt_key, "0.05"],
                env=self._env(), stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
        self.pods[key] = replicas
        return {"pod_ips": ["10.2.0.0"]}

    def pod_ips(self, ns, name):
        key = f"{ns}/{name}"
        if name == self.trainee:
            proc = self.procs.get(key)
            return ["10.2.0.0"] if proc is not None and \
                proc.poll() is None else []
        return super().pod_ips(ns, name)

    def signal_pods(self, ns, name, sig, grace_s=0.0):
        key = f"{ns}/{name}"
        self.signals.append((key, sig, grace_s))
        proc = self.procs.get(key)
        if proc is not None and proc.poll() is None:
            from kubetorch_tpu.chaos import deliver_term_with_grace
            deliver_term_with_grace(proc.pid, grace_s or 10.0,
                                    label=f"scheduler preemption of {key}")
            return 1
        return super().signal_pods(ns, name, sig, grace_s)

    def cleanup(self):
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.kill()


@pytest.mark.chaos
def test_scheduler_preemption_acceptance_subprocess(tmp_path):
    """THE acceptance scenario, with a full capacity book and real
    processes: deploying a higher-tier workload preempts the running batch
    job through the drain path (SIGTERM + grace-window SIGKILL — the
    term-rank contract), the batch job's checkpoint commits inside the
    grace window, and after the high-tier workload finishes the batch job
    resumes automatically with ``tree_fingerprint`` matching a clean
    reload and zero lost committed steps."""
    from kubetorch_tpu.data_store import commands as ds

    with ThreadedAiohttpServer(_store_app(tmp_path / "store")) as srv:
        key = "sched/acceptance"
        fb = SubprocessTrainerBackend(srv.url, key)
        state = _state(fb, capacity={"cpu": 2})
        try:
            async def phase1():
                await _submit(state, _rec(state, "batchjob", 2,
                                          priority="batch",
                                          drain_grace_s=20.0))
                # real subprocess: wait for real steps to land on the store
                assert await asyncio.to_thread(_wait, lambda: (
                    ds.get_json(f"{key}/__status__", store_url=srv.url)
                    or {}).get("step", 0) >= 3, 60.0)
                assert ck.commit_info(key, store_url=srv.url) is None
                out = await _submit(state, _rec(state, "serve", 2,
                                                priority="high"))
                assert "queued" not in out

            asyncio.run(phase1())
            # the grace window worked: the subprocess committed + vacated
            drained = ds.get_json(f"{key}/__drained__", store_url=srv.url)
            assert drained is not None and drained["reason"] == "SIGTERM"
            info = ck.commit_info(key, store_url=srv.url)
            assert info is not None and info["step"] == drained["step"]
            assert state.sched().ledger[-1]["drained"] is True
            last_status = ds.get_json(f"{key}/__status__",
                                      store_url=srv.url)
            assert last_status["step"] == drained["step"], \
                "zero completed steps may be lost"

            async def phase2():
                state.workloads.pop("default/serve")
                await state.sched().release("default", "serve")
                await state.sched().kick()
                assert await asyncio.to_thread(_wait, lambda: (
                    ds.get_json(f"{key}/__status__", store_url=srv.url)
                    or {}).get("resumed_from") == drained["step"], 60.0)

            asyncio.run(phase2())
            # the resumed process restored the EXACT committed bytes: its
            # first post-resume fingerprint is the committed params + 1.0,
            # and a clean reload matches the pre-preemption fingerprint
            reloaded, step = ck.Checkpointer(key,
                                             store_url=srv.url).restore()
            assert step == drained["step"]
            assert ck.tree_fingerprint(reloaded) == \
                last_status["fingerprint"]
            status = ds.get_json(f"{key}/__status__", store_url=srv.url)
            assert status["step"] > drained["step"]
        finally:
            fb.cleanup()

"""Controller durability (VERDICT r1 #5; reference: KubetorchWorkload CRD
status + Loki-backed log history — a controller restart loses nothing).

Unit tier: DiskPersister round-trips + ControllerState.restore semantics.
Minimal tier: the real thing — deploy through a local controller daemon,
kill -9 it, start a fresh one on the same state dir, and ``kt list`` /
``kt logs`` still answer; the next call revives the pods.
"""

import json
import os
import signal
import time

import pytest

from kubetorch_tpu.controller.app import ControllerState
from kubetorch_tpu.controller.backends import LocalBackend
from kubetorch_tpu.controller.persistence import DiskPersister


@pytest.mark.level("unit")
def test_disk_persister_workload_round_trip(tmp_path):
    p = DiskPersister(str(tmp_path))
    record = {"namespace": "ns", "name": "svc", "launch_id": "abc",
              "manifest": {"kind": "Deployment", "spec": {"replicas": 2}},
              "_coldstart_pin_until": time.time(),   # runtime-only: stripped
              "created_at": 1.0}
    p.save_workload(record)
    loaded = p.load_workloads()
    assert len(loaded) == 1
    assert loaded[0]["name"] == "svc"
    assert "_coldstart_pin_until" not in loaded[0]

    p.delete_workload("ns", "svc")
    assert p.load_workloads() == []


@pytest.mark.level("unit")
def test_disk_persister_logs_rotate_and_reload(tmp_path, monkeypatch):
    import kubetorch_tpu.controller.persistence as pers

    monkeypatch.setattr(pers, "LOG_SPILL_MAX_BYTES", 2000)
    p = DiskPersister(str(tmp_path))
    for i in range(100):
        p.append_logs("ns/svc", [{"line": f"entry-{i:04d}", "namespace": "ns",
                                  "service": "svc"}])
    p.flush()   # appends ride the writer thread; settle before asserting
    # rotation happened (file capped), and reload spans the generations
    spill = tmp_path / "logs" / "ns__svc.jsonl"
    assert spill.with_suffix(".jsonl.1").exists()
    loaded = dict(p.load_logs())
    lines = [e["line"] for e in loaded["ns/svc"]]
    assert lines[-1] == "entry-0099"
    assert len(lines) > 20   # older generation contributes too
    assert lines == sorted(lines)


@pytest.mark.level("unit")
def test_restore_drops_stale_local_addresses(tmp_path):
    p = DiskPersister(str(tmp_path))
    p.save_workload({"namespace": "ns", "name": "svc", "launch_id": "x",
                     "manifest": {"kind": "Deployment",
                                  "spec": {"replicas": 1}},
                     "service_url": "http://127.77.1.1:32300",
                     "pod_ips": ["127.77.1.1"]})
    p.append_logs("ns/svc", [{"line": "hello", "seq": 17}])
    p.append_event({"ts": 1.0, "service": "ns/svc", "message": "deployed"})
    # the appends are queued behind the persister's writer thread; restore()
    # reads the files and does not wait for it
    p.flush()

    state = ControllerState(backend=LocalBackend(controller_url="http://x"),
                            state_dir=str(tmp_path))
    state.restore()
    record = state.workloads["ns/svc"]
    assert record["status"] == "restored"
    assert "pod_ips" not in record and "service_url" not in record
    entries = list(state.logs["ns/svc"])
    assert entries[0]["line"] == "hello"
    assert entries[0]["seq"] == 1     # renumbered onto the fresh cursor
    assert state.log_seq == 1
    assert state.events[-1]["message"] == "deployed"


# ---------------------------------------------------------------------------
# Scheduler-state durability (ISSUE 8): queue, priorities, and half-finished
# preemptions survive a controller SIGKILL
# ---------------------------------------------------------------------------


@pytest.mark.level("unit")
@pytest.mark.sched
def test_scheduler_queue_and_priorities_survive_restart(tmp_path):
    import asyncio

    from kubetorch_tpu.controller.scheduler import Scheduler
    from tests.test_scheduler import FakeBackend, _rec, _state, _submit

    state = _state(FakeBackend(), capacity={"cpu": 1},
                   state_dir=str(tmp_path))

    async def fill():
        await _submit(state, _rec(state, "running", 1, priority="batch"))
        # same tier as the running job: they queue (never preempt)
        assert (await _submit(state, _rec(state, "waiting-hi", 1,
                                          priority=30)))["queued"]
        assert (await _submit(state, _rec(state, "waiting-lo", 1,
                                          priority=25)))["queued"]
        for rec in state.workloads.values():
            await state.persist_workload(rec)

    asyncio.run(fill())
    state.persister.flush()

    # "restart": fresh state + scheduler over the same state dir
    state2 = ControllerState(backend=FakeBackend(),
                             state_dir=str(tmp_path))
    state2.restore()
    sched2 = Scheduler(state2, capacity={"cpu": 1})
    sched2.restore(state2.persister.load_scheduler_state())
    state2.scheduler = sched2
    assert [(e["key"], e["priority"]) for e in
            sched2.policy.order(sched2.queue, sched2)] == \
        [("default/waiting-hi", 30), ("default/waiting-lo", 25)]
    assert sched2.book.allocations["default/running"]["width"] == 1
    assert state2.workloads["default/waiting-hi"]["status"] == "queued"


@pytest.mark.level("unit")
@pytest.mark.sched
def test_sigkill_mid_preemption_recovers_and_resumes(tmp_path):
    """THE durability scenario: the controller dies (nothing after the
    persisted 'draining' ledger entry ever runs) between signaling the
    victim and evicting it. The restarted controller must finish the
    eviction, re-queue the victim at its priority, and place it once
    capacity frees — from ``persistence.py`` state alone."""
    import asyncio

    from kubetorch_tpu.controller.scheduler import Scheduler
    from tests.test_scheduler import FakeBackend, _rec, _state, _submit

    fb = FakeBackend(cooperative=False)      # victim pods never exit
    state = _state(fb, capacity={"cpu": 1}, state_dir=str(tmp_path))

    async def crash_mid_preemption():
        victim = _rec(state, "victim", 1, priority="batch",
                      drain_grace_s=30.0)
        await _submit(state, victim)
        await state.persist_workload(victim)
        vip = _rec(state, "vip", 1, priority="high")
        await state.persist_workload(vip)
        task = asyncio.get_running_loop().create_task(
            _submit(state, vip))
        # let the preemption reach the drain wait (ledger: "draining")
        for _ in range(200):
            await asyncio.sleep(0.01)
            if state.sched().ledger and \
                    state.sched().ledger[-1]["phase"] == "draining":
                break
        assert state.sched().ledger[-1]["phase"] == "draining"
        task.cancel()                        # the SIGKILL: nothing after
        try:
            await task
        except asyncio.CancelledError:
            pass

    asyncio.run(crash_mid_preemption())
    state.persister.flush()

    # restart: fresh process over the same state dir
    fb2 = FakeBackend()
    state2 = ControllerState(backend=fb2, state_dir=str(tmp_path))
    state2.restore()
    sched2 = Scheduler(state2, capacity={"cpu": 1})
    sched2.restore(state2.persister.load_scheduler_state())
    state2.scheduler = sched2
    led = sched2.ledger[-1]
    assert led["victim"] == "default/victim" and led["phase"] == "draining"

    async def recover_and_drain():
        await sched2.recover()
        # half-finished preemption completed: victim evicted + re-queued
        assert sched2.ledger[-1]["phase"] == "evicted"
        [entry] = [e for e in sched2.queue
                   if e["key"] == "default/victim"]
        assert entry["preempted"] and entry["priority"] == 20
        assert "default/victim" not in sched2.book.allocations
        # capacity is free (the vip deploy died with the old controller):
        # the victim resumes automatically on the next queue drain
        await sched2.kick()
        assert sched2.book.allocations["default/victim"]["width"] == 1
        assert not [e for e in sched2.queue
                    if e["key"] == "default/victim"]
        assert ("default/victim", 1) in [(k, r)
                                         for k, r, _ in fb2.applies]

    asyncio.run(recover_and_drain())


@pytest.mark.level("minimal")
@pytest.mark.slow
def test_kill_dash_nine_controller_restart_keeps_workloads_and_logs():
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "assets"))
    import payloads

    import kubetorch_tpu as kt
    from kubetorch_tpu.client import (_read_running_local, controller_client,
                                      shutdown_local_controller)

    f = kt.fn(payloads.summer, name="t-persist")
    f.to(kt.Compute(cpus=1))
    try:
        assert f(3, 4) == 7
        cc = controller_client()
        ns = f.compute.namespace
        # ensure a log line reached the controller sink
        cc._request("POST", "/controller/logs", json={"entries": [
            {"namespace": ns, "service": f.name, "line": "pre-crash marker"}]})

        state = _read_running_local()
        assert state is not None
        os.kill(state["pid"], signal.SIGKILL)    # no cleanup runs
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                os.kill(state["pid"], 0)
                time.sleep(0.1)
            except OSError:
                break

        # next client access detects the dead daemon and boots a fresh one,
        # which restores state from disk (reset_config = what a fresh CLI
        # process does; the in-process singleton caches the dead api_url)
        from kubetorch_tpu.config import reset_config
        reset_config()
        cc2 = controller_client()
        names = [w["name"] for w in cc2.list_workloads()]
        assert f.name in names, names

        logs = cc2._request("GET", "/controller/logs",
                            params={"service": f.name, "namespace": ns})
        assert any("pre-crash marker" in json.dumps(e)
                   for e in logs.get("entries", []))

        # the old pods died with the old controller (PDEATHSIG); a call
        # through a re-attached handle revives them via the proxy
        g = type(f).from_name(f.name, namespace=ns)
        assert g(5, 6) == 11
    finally:
        try:
            f.teardown()
        except Exception:
            pass

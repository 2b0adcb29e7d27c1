"""Failure detection and elastic recovery (SURVEY §5.3): pod death surfaces
as typed exceptions; the client-driven resize-and-redeploy recipe restores
service — the reference's fault_tolerance/dynamic_world_size pattern."""

import json
import os
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.level("minimal")
import requests

from kubetorch_tpu.utils.procs import free_port, kill_process_tree, wait_for_port

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


def spawn_pod(ip, port, ips, fn_name="sleeper", procs=1):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "LOCAL_IPS": ",".join(ips),
        "POD_IP": ip,
        "POD_NAME": f"pod-{ip.split('.')[-1]}",
        "KT_PROJECT_ROOT": ASSETS,
        "KT_MODULE_NAME": "payloads",
        "KT_FILE_PATH": "payloads.py",
        "KT_CLS_OR_FN_NAME": fn_name,
        "KT_LAUNCH_ID": "l1",
        "KT_SERVICE_NAME": "t-fault",
        "KT_DISTRIBUTED_CONFIG": json.dumps(
            {"distribution_type": "spmd", "workers": len(ips),
             "procs_per_worker": procs}),
        "KT_SERVER_PORT": str(port),
    })
    return subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.serving.http_server",
         "--host", ip, "--port", str(port)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


@pytest.mark.slow
def test_peer_death_is_typed_error():
    """Mid-fan-out peer death → typed WorkerCallError/PodTerminatedError at
    the coordinator, not a hang or a bare 500."""
    port = free_port()
    ips = ["127.0.0.11", "127.0.0.12"]
    pods = [spawn_pod(ip, port, ips, fn_name="sleeper") for ip in ips]
    try:
        for ip in ips:
            assert wait_for_port(ip, port, timeout=30)
        # warm up the supervisors
        r = requests.post(f"http://{ips[0]}:{port}/sleeper",
                          json={"args": [0.1], "kwargs": {}}, timeout=60)
        assert r.status_code == 200

        # hard-kill the peer, then fan out again
        kill_process_tree(pods[1].pid)
        time.sleep(0.5)
        r = requests.post(f"http://{ips[0]}:{port}/sleeper",
                          json={"args": [0.1], "kwargs": {}}, timeout=60)
        assert r.status_code != 200
        err = r.json()
        assert err["error_type"] in ("WorkerCallError", "PodTerminatedError",
                                     "WorkerMembershipChanged"), err["error_type"]

        # elastic recipe: the client resizes to the survivors and retries
        r = requests.post(f"http://{ips[0]}:{port}/sleeper",
                          json={"args": [0.1], "kwargs": {},
                                "_kt_workers": "ready"}, timeout=60)
        assert r.status_code == 200, r.text
        assert len(r.json()) == 1   # only the surviving pod ran
    finally:
        for p in pods:
            if p.poll() is None:
                kill_process_tree(p.pid)


@pytest.mark.slow
def test_membership_monitor_detects_change():
    """The DNS/LOCAL_IPS monitor diffs worker sets and queues a critical
    WorkerMembershipChanged for removals (reference distributed_supervisor
    :236-339). LOCAL_IPS is process-wide env, so we drive the supervisor
    in-process with a mutable discover()."""
    from kubetorch_tpu.exceptions import WorkerMembershipChanged
    from kubetorch_tpu.parallel.mesh import DistributedConfig
    from kubetorch_tpu.serving import execution_supervisor as es
    from kubetorch_tpu.resources.pointers import Pointers

    sup = es.DistributedSupervisor(
        Pointers(project_root=ASSETS, module_name="payloads",
                 file_path="payloads.py", cls_or_fn_name="summer"),
        None, DistributedConfig(distribution_type="spmd", workers=2),
        service_name="t-mon", namespace="default")
    ips = ["10.0.0.1", "10.0.0.2"]
    sup.discover = lambda: list(ips)
    # skip real pool setup; drive the monitor directly
    sup._known_ips = list(ips)
    monkey_interval = es.MEMBERSHIP_POLL_S
    es.MEMBERSHIP_POLL_S = 0.1
    try:
        sup._start_monitor()
        ips.remove("10.0.0.2")
        deadline = time.monotonic() + 5
        event = None
        while time.monotonic() < deadline and event is None:
            event = sup.pop_membership_event()
            time.sleep(0.05)
        assert event is not None, "monitor never flagged the removal"
        assert event.removed == ["10.0.0.2"] and event.is_critical
        # additions are non-critical
        ips.extend(["10.0.0.2", "10.0.0.3"])
        deadline = time.monotonic() + 5
        event = None
        while time.monotonic() < deadline and event is None:
            event = sup.pop_membership_event()
            time.sleep(0.05)
        assert event is not None and not event.is_critical
        assert "10.0.0.3" in event.added
        with pytest.raises(WorkerMembershipChanged):
            sup._membership_events.append(WorkerMembershipChanged(
                removed=["x"], previous=["x"], current=[]))
            sup.check_membership()
    finally:
        es.MEMBERSHIP_POLL_S = monkey_interval
        sup._stop_monitor.set()


# ---------------------------------------------------------------------------
# Chaos harness (ISSUE 2): deterministic fault injection through KT_CHAOS
# proves the resilience layer end-to-end — real pod server, real sync client,
# faults injected by the seeded schedule, backoff asserted exactly.
# ---------------------------------------------------------------------------

import numpy as np

from kubetorch_tpu.resilience import RetryPolicy
from kubetorch_tpu.serving.http_client import CustomResponse, HTTPClient
from tests.assets.threaded_server import ThreadedAiohttpServer


@pytest.fixture
def pod_metadata(monkeypatch):
    """Point the pod server at the summer() test payload."""
    monkeypatch.setenv("KT_PROJECT_ROOT", ASSETS)
    monkeypatch.setenv("KT_MODULE_NAME", "payloads")
    monkeypatch.setenv("KT_FILE_PATH", "payloads.py")
    monkeypatch.setenv("KT_CLS_OR_FN_NAME", "summer")
    monkeypatch.setenv("KT_LAUNCH_ID", "chaos-1")
    monkeypatch.delenv("KT_DISTRIBUTED_CONFIG", raising=False)
    monkeypatch.delenv("POD_IP", raising=False)


def _pod_app():
    from kubetorch_tpu.serving.http_server import create_app
    return create_app()


@pytest.mark.chaos
def test_chaos_resets_then_503_idempotent_call_succeeds(pod_metadata,
                                                        monkeypatch):
    """The acceptance scenario: 2 injected connection resets + 1 injected
    503 on a seeded schedule → the idempotent call still succeeds, the
    server-side handler executed exactly once, and the recorded backoff
    delays are exactly the (seeded) policy's."""
    monkeypatch.setenv("KT_CHAOS", "reset,reset,503")
    monkeypatch.setenv("KT_CHAOS_SEED", "1234")
    with ThreadedAiohttpServer(_pod_app) as srv:
        client = HTTPClient(srv.url, stream_logs=False)
        policy = RetryPolicy(max_attempts=5, base_delay=0.05, max_delay=0.3,
                             seed=4242)
        out = client.call_method("summer", args=(2, 3),
                                 idempotency_key="chaos-call-1",
                                 retry=policy, timeout=60)
        assert out == 5
        engine = srv.app["chaos"]
        state = srv.app["state"]
        assert engine.injected == 3
        # chaos fires BEFORE routing, so the three faulted attempts provably
        # never dispatched: exactly one server-side execution
        assert state.request_count == 1
        assert len(state.idempotency) == 1
        assert client.last_retry_delays == policy.preview_delays(3)

        # same key again → replayed from the dedupe cache, still one exec
        again = client.call_method("summer", args=(2, 3),
                                   idempotency_key="chaos-call-1",
                                   timeout=60)
        assert again == 5
        assert state.request_count == 1


@pytest.mark.chaos
def test_post_without_key_not_retried_surfaces_typed_error(pod_metadata,
                                                           monkeypatch):
    """A non-idempotent POST (no key) whose connection was established must
    NOT be retried: one injected fault → one attempt, the typed remote
    error surfaces, and the dedupe cache never saw an execution."""
    monkeypatch.setenv("KT_CHAOS", "oom")
    with ThreadedAiohttpServer(_pod_app) as srv:
        client = HTTPClient(srv.url, stream_logs=False)
        from kubetorch_tpu.exceptions import HbmOomError
        with pytest.raises(HbmOomError) as ei:
            client.call_method("summer", args=(1, 1), timeout=60)
        assert ei.value.requested_bytes == 8 << 30
        assert ei.value.status_code == 503          # transport facts attached
        assert getattr(ei.value, "request_id", None)
        engine, state = srv.app["chaos"], srv.app["state"]
        assert engine.requests_seen == 1            # exactly one attempt
        assert state.request_count == 0             # never dispatched
        assert len(state.idempotency) == 0          # no double exec possible


@pytest.mark.chaos
def test_post_without_key_reset_not_retried(pod_metadata, monkeypatch):
    monkeypatch.setenv("KT_CHAOS", "reset,reset")
    with ThreadedAiohttpServer(_pod_app) as srv:
        client = HTTPClient(srv.url, stream_logs=False)
        with pytest.raises(requests.exceptions.ConnectionError):
            client.call_method("summer", args=(1, 1), timeout=60)
        assert srv.app["chaos"].requests_seen == 1  # no second attempt
        assert srv.app["state"].request_count == 0


@pytest.mark.chaos
def test_deadline_rejected_before_dispatch(pod_metadata):
    """X-KT-Deadline in the past → rehydratable DeadlineExceededError, user
    function never invoked."""
    from kubetorch_tpu.exceptions import DeadlineExceededError
    with ThreadedAiohttpServer(_pod_app) as srv:
        r = requests.post(f"{srv.url}/summer",
                          json={"args": [1, 2], "kwargs": {}},
                          headers={"X-KT-Deadline": str(time.time() - 5)},
                          timeout=30)
        assert r.status_code == 504
        with pytest.raises(DeadlineExceededError) as ei:
            CustomResponse(r.status_code, r.content,
                           dict(r.headers)).result()
        assert ei.value.deadline is not None
        assert srv.app["state"].request_count == 0


@pytest.mark.chaos
def test_deadline_cancels_mid_dispatch(monkeypatch):
    """A deadline that expires DURING dispatch cancels the handler and
    returns the typed 504 — the slot is reclaimed, not burned."""
    monkeypatch.setenv("KT_PROJECT_ROOT", ASSETS)
    monkeypatch.setenv("KT_MODULE_NAME", "payloads")
    monkeypatch.setenv("KT_FILE_PATH", "payloads.py")
    monkeypatch.setenv("KT_CLS_OR_FN_NAME", "sleeper")
    monkeypatch.setenv("KT_LAUNCH_ID", "chaos-2")
    monkeypatch.delenv("KT_DISTRIBUTED_CONFIG", raising=False)
    monkeypatch.delenv("POD_IP", raising=False)
    with ThreadedAiohttpServer(_pod_app) as srv:
        # warm the supervisor so the deadline races ONLY the user sleep
        r = requests.post(f"{srv.url}/sleeper",
                          json={"args": [0.01], "kwargs": {}}, timeout=60)
        assert r.status_code == 200, r.text
        t0 = time.monotonic()
        r = requests.post(
            f"{srv.url}/sleeper", json={"args": [20], "kwargs": {}},
            headers={"X-KT-Deadline": str(time.time() + 1.0)}, timeout=30)
        assert r.status_code == 504, r.text
        assert time.monotonic() - t0 < 10
        assert b"DeadlineExceededError" in r.content


@pytest.mark.chaos
def test_async_client_parity_retries_with_key(pod_metadata, monkeypatch):
    """call_method_async shares a session, applies the same retry gating,
    and succeeds through an injected reset when the key is present."""
    import asyncio

    monkeypatch.setenv("KT_CHAOS", "reset")
    with ThreadedAiohttpServer(_pod_app) as srv:
        client = HTTPClient(srv.url, stream_logs=False)

        async def go():
            policy = RetryPolicy(max_attempts=4, base_delay=0.02,
                                 max_delay=0.1, seed=7)
            out = await client.call_method_async(
                "summer", args=(4, 5), idempotency_key="async-1",
                retry=policy, timeout=60)
            first_sess = client._aio_session
            out2 = await client.call_method_async("summer", args=(4, 5),
                                                  timeout=60)
            assert client._aio_session is first_sess    # session reused
            await client.aclose()
            return out, out2

        out, out2 = asyncio.run(go())
        assert out == 9 and out2 == 9
        assert srv.app["state"].request_count >= 1


@pytest.mark.chaos
def test_store_put_get_through_chaos(tmp_path, monkeypatch):
    """Data-plane proof: store ops are retry-by-default, so a put/get
    round-trip survives an injected reset + 503 without the caller doing
    anything."""
    from kubetorch_tpu.data_store import commands
    from kubetorch_tpu.data_store.store_server import create_store_app

    monkeypatch.setenv("KT_CHAOS", "reset,503")
    monkeypatch.setenv("KT_CHAOS_SEED", "1234")
    monkeypatch.delenv("POD_IP", raising=False)
    with ThreadedAiohttpServer(
            lambda: create_store_app(str(tmp_path))) as srv:
        arr = np.arange(32, dtype=np.float32).reshape(4, 8)
        stats = commands.put("chaos/w", {"w": arr}, store_url=srv.url)
        assert stats["leaves"] == 1
        out = commands.get("chaos/w", store_url=srv.url)
        np.testing.assert_array_equal(out["w"], arr)
        assert srv.app["chaos"].injected == 2

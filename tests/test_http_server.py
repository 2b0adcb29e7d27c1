"""In-process pod-runtime tests (model: reference tests/test_http_server.py —
runs the server app with a test client, loading callables from tests/assets,
no cluster)."""

import asyncio
import json
import os
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from kubetorch_tpu import serialization as ser
from kubetorch_tpu.serving.env_contract import (
    KT_CLS_OR_FN_NAME, KT_FILE_PATH, KT_INIT_ARGS, KT_LAUNCH_ID,
    KT_MODULE_NAME, KT_PROJECT_ROOT, METADATA_KEYS,
)
from kubetorch_tpu.serving.http_server import ServerState, create_app

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


@pytest.fixture(autouse=True)
def clean_env():
    saved = {k: os.environ.get(k) for k in METADATA_KEYS}
    for k in METADATA_KEYS:
        os.environ.pop(k, None)
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def set_fn_metadata(fn_name: str, init_args=None):
    os.environ[KT_PROJECT_ROOT] = ASSETS
    os.environ[KT_MODULE_NAME] = "payloads"
    os.environ[KT_FILE_PATH] = "payloads.py"
    os.environ[KT_CLS_OR_FN_NAME] = fn_name
    os.environ[KT_LAUNCH_ID] = "launch-1"
    if init_args:
        os.environ[KT_INIT_ARGS] = json.dumps(init_args)


async def poll_ready(client, launch_id: str, until, timeout: float = 60.0,
                     allowed=(200, 503)):
    """Poll /ready until ``until(status, body)`` is true; only ``allowed``
    interim statuses may appear. Returns the satisfying (status, body)."""
    import time as _t

    deadline = _t.time() + timeout
    while _t.time() < deadline:
        r = await client.get("/ready", params={"launch_id": launch_id})
        body = await r.json()
        if until(r.status, body):
            return r.status, body
        assert r.status in allowed, (r.status, body)
        await asyncio.sleep(0.2)
    raise AssertionError(f"/ready never satisfied condition for {launch_id}")


async def wait_ready(client, launch_id: str, timeout: float = 60.0):
    """Poll /ready until 200 (503 = still in the load+warmup window)."""
    return await poll_ready(client, launch_id,
                            lambda s, b: s == 200, timeout)


def run_server_test(coro_fn):
    async def runner():
        state = ServerState()
        app = create_app(state)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await coro_fn(client, state)
        finally:
            await client.close()
    asyncio.run(runner())


def test_health_and_ready():
    async def body(client, state):
        r = await client.get("/health")
        assert r.status == 200
        data = await r.json()
        assert data["status"] == "ok" and data["launch_id"] is None

        set_fn_metadata("summer")
        state.launch_id = "launch-1"
        r = await client.get("/ready", params={"launch_id": "launch-1"})
        assert r.status == 200
        r = await client.get("/ready", params={"launch_id": "other"})
        assert r.status == 409

        # rank workers still inside their load+warmup window → not ready
        class _WarmingSup:
            warming = True
        state.supervisor = _WarmingSup()
        r = await client.get("/ready", params={"launch_id": "launch-1"})
        assert r.status == 503 and (await r.json())["warming"] is True
        state.supervisor = None
    run_server_test(body)


def test_call_function():
    async def body(client, state):
        set_fn_metadata("summer")
        r = await client.post("/summer", json={"args": [2, 3], "kwargs": {}})
        assert r.status == 200, await r.text()
        assert json.loads(await r.read()) == 5
    run_server_test(body)


def test_call_wrong_name_404():
    async def body(client, state):
        set_fn_metadata("summer")
        r = await client.post("/not_summer", json={"args": [], "kwargs": {}})
        assert r.status == 404
    run_server_test(body)


def test_exception_propagation():
    async def body(client, state):
        set_fn_metadata("boomer")
        r = await client.post("/boomer", json={"args": [], "kwargs": {"msg": "zap"}})
        assert r.status == 500
        err = await r.json()
        assert err["error_type"] == "ValueError"
        assert "zap" in err["message"]
        assert "traceback" in err
    run_server_test(body)


def test_class_instance_methods():
    async def body(client, state):
        set_fn_metadata("Counter", init_args={"kwargs": {"start": 10}})
        r = await client.post("/Counter/increment", json={"args": [5], "kwargs": {}})
        assert r.status == 200, await r.text()
        assert json.loads(await r.read()) == 15
        # state persists in the worker process
        r = await client.post("/Counter/get", json={"args": [], "kwargs": {}})
        assert json.loads(await r.read()) == 15
    run_server_test(body)


def test_warmup_hook_runs_at_load():
    """__kt_warmup__ runs in the rank subprocess at eager load — the first
    real request already sees the warmed state (inference warm pools)."""
    async def body(client, state):
        set_fn_metadata("Warmable")
        r = await client.post("/Warmable/was_warmed",
                              json={"args": [], "kwargs": {}})
        assert r.status == 200, await r.text()
        assert json.loads(await r.read()) is True
    run_server_test(body)


def test_reload_prewarms_before_ready():
    """reload() opens the load+warmup window immediately: /ready flips to 200
    only after the rank worker finished __kt_warmup__, so the first request
    after readiness is already warm."""
    async def body(client, state):
        set_fn_metadata("Warmable")
        await state.reload({}, launch_id="warm-1")
        await wait_ready(client, "warm-1")
        # the supervisor already exists (prewarmed) and the worker is warm
        assert state.supervisor is not None
        r = await client.post("/Warmable/was_warmed",
                              json={"args": [], "kwargs": {}})
        assert json.loads(await r.read()) is True
    run_server_test(body)


def test_ready_carries_the_boot_timeline():
    """A ready /ready says how the launch's boot went (ISSUE 26): every
    phase in seconds, and ``ready_for_s``, which grows from poll to poll —
    how long the service had been ready when the client noticed."""
    from kubetorch_tpu.serving.http_server import BOOT_PHASES

    async def body(client, state):
        set_fn_metadata("Warmable")
        await state.reload({}, launch_id="boot-1")
        r = await client.get("/ready", params={"launch_id": "boot-1"})
        if r.status == 503:
            assert "boot" not in await r.json()     # not before it is ready
        _, first = await wait_ready(client, "boot-1")
        boot = first["boot"]
        assert set(boot) == set(BOOT_PHASES) | {"ready_for_s"}
        assert all(v >= 0.0 for v in boot.values()), boot
        # this launch came to a pod that was up (no pod_boot of its own);
        # its pool was spawned, and the rank took time to start and import
        assert boot["pod_boot_s"] == 0.0
        for phase in ("pool_spawn_s", "rank_spawn_s", "rank_import_s"):
            assert boot[phase] > 0.0, (phase, boot)
        await asyncio.sleep(0.3)
        _, second = await wait_ready(client, "boot-1")
        assert second["boot"]["ready_for_s"] \
            >= boot["ready_for_s"] + 0.25
        assert {k: v for k, v in second["boot"].items()
                if k != "ready_for_s"} == \
            {k: v for k, v in boot.items() if k != "ready_for_s"}
        # a new launch on the same pod starts its own record
        await state.reload({}, launch_id="boot-2")
        _, again = await wait_ready(client, "boot-2")
        assert again["boot"]["ready_for_s"] < second["boot"]["ready_for_s"]
    run_server_test(body)


# -- /ready?wait=: the pod holds the request until the launch is warm -------

class _StubPool:
    """What ``ServerState.boot_body`` reads: the moment the ranks were
    ready."""

    def __init__(self):
        self.ready_mono = None

    def boot_record(self):
        return {} if self.ready_mono is None \
            else {"ready_mono": self.ready_mono}


class _StubSup:
    """A supervisor as ``/ready`` sees one: three flags and a pool."""

    def __init__(self, **flags):
        self.warming = self.recovering = False
        self.healthy = True
        self.pool = _StubPool()
        for k, v in flags.items():
            setattr(self, k, v)

    def turn_ready(self):
        self.warming = self.recovering = False
        self.healthy = True
        self.pool.ready_mono = time.monotonic()

    def cleanup(self):
        pass


def _not_ready(state, why):
    """Put ``state`` (launch ``launch-1``) into one kind of "not yet";
    returns the body /ready gives for it, and what ends it."""
    state.launch_id = "launch-1"
    if why == "prewarm":
        gate = asyncio.Event()
        sup = _StubSup()

        async def build():
            await gate.wait()
            state.supervisor = sup
            sup.turn_ready()
        state._prewarm_task = asyncio.ensure_future(build())
        return ({"ready": False, "launch_id": "launch-1", "warming": True},
                gate.set)
    if why == "other_launch":
        state.launch_id = "launch-0"
        state.supervisor = sup = _StubSup()

        def flip():
            state.launch_id = "launch-1"
            sup.turn_ready()
        return ({"ready": False, "launch_id": "launch-0",
                 "expected": "launch-1"}, flip)
    state.supervisor = sup = _StubSup(**{why: True})
    return ({"ready": False, "launch_id": "launch-1",
             "warming": why == "warming", "recovering": why == "recovering",
             "healthy": True}, sup.turn_ready)


NOT_YET = {"prewarm": 503, "warming": 503, "recovering": 503,
           "other_launch": 409}


async def _timed_get(client, path, **params):
    t0 = time.monotonic()
    r = await client.get(path, params=params)
    body = await r.json() if path == "/ready" else await r.read()
    return r.status, body, time.monotonic() - t0


@pytest.mark.parametrize("why", [*NOT_YET, "real_rank"])
def test_ready_with_wait_is_answered_when_the_launch_turns_ready(why):
    """``/ready?wait=`` sent inside the load+warmup window (or before the
    pod flipped to the asked launch) stays open, and is answered 200 with
    the ``boot`` body as soon as the launch is ready: ``ready_for_s`` says
    within 50 ms, and never before the window closed."""
    async def body(client, state):
        if why == "real_rank":
            set_fn_metadata("Warmable")
            await state.reload({}, launch_id="launch-1")
            status, got, took = await _timed_get(
                client, "/ready", launch_id="launch-1", wait=10)
            assert state.supervisor is not None \
                and not state.supervisor.warming
            # the rank was spawned and imported inside the hold
            assert took >= got["boot"]["rank_spawn_s"] > 0.0
        else:
            _, end = _not_ready(state, why)
            asyncio.get_running_loop().call_later(0.4, end)
            status, got, took = await _timed_get(
                client, "/ready", launch_id="launch-1", wait=5)
            assert 0.4 <= took < 0.6, took
        assert status == 200 and got["ready"] is True
        assert got["launch_id"] == "launch-1"
        assert 0.0 <= got["boot"]["ready_for_s"] <= 0.05, got["boot"]
    run_server_test(body)


@pytest.mark.parametrize("why,ask,cap", [
    *[(w, 0.3, None) for w in NOT_YET],
    ("warming", 60, 0.3),       # a wait above the pod's cap is cut to it
    ("other_launch", "inf", 0.3),
])
def test_ready_wait_runs_out_with_the_answer_it_would_have_had(
        why, ask, cap, monkeypatch):
    """A ``wait`` shorter than the window, or the pod's cap where ``wait``
    is above it, gives the "not yet" status and body, after about that
    long."""
    from kubetorch_tpu.serving import http_server

    async def body(client, state):
        if cap is not None:
            monkeypatch.setattr(http_server, "READY_WAIT_CAP_S", cap)
        not_yet, _ = _not_ready(state, why)
        status, got, took = await _timed_get(
            client, "/ready", launch_id="launch-1", wait=ask)
        assert (status, got) == (NOT_YET[why], not_yet)
        assert 0.3 <= took < 0.5, took
        if state._prewarm_task is not None:
            state._prewarm_task.cancel()
    run_server_test(body)


@pytest.mark.parametrize("wait", [None, "0", "-1", "nan", "soon", ""])
@pytest.mark.parametrize("why", [*NOT_YET, "ready"])
def test_ready_without_wait_answers_at_once(why, wait):
    """No ``wait`` (a kubelet probe, ``HTTPClient.is_ready``, every poller
    there was), or one that is no positive number: today's status and body,
    at once."""
    async def body(client, state):
        if why == "ready":
            state.launch_id = "launch-1"
            state.supervisor = sup = _StubSup()
            sup.turn_ready()
            want_status = 200
        else:
            want_body, _ = _not_ready(state, why)
            want_status = NOT_YET[why]
        params = {"launch_id": "launch-1"}
        if wait is not None:
            params["wait"] = wait
        status, got, took = await _timed_get(client, "/ready", **params)
        assert status == want_status and took < 0.1, (status, took)
        if why == "ready":
            boot = got.pop("boot")
            assert got == {"ready": True, "launch_id": "launch-1"}
            assert boot["ready_for_s"] >= 0.0
        else:
            assert got == want_body
        if state._prewarm_task is not None:
            state._prewarm_task.cancel()
    run_server_test(body)


@pytest.mark.parametrize("why", ["prewarm_error", "unhealthy", "dead_rank"])
def test_ready_never_holds_a_launch_that_cannot_become_ready(why):
    """A supervisor that could not be built, a pool past its restart
    budget, a rank that died in its warm-up: a broken launch says so at
    once, whatever ``wait`` the deploying client sent."""
    async def body(client, state):
        state.launch_id = "launch-1"
        if why == "prewarm_error":
            state._prewarm_error = "ImportError: no module named model"
            want = {"ready": False, "launch_id": "launch-1",
                    "error": "ImportError: no module named model"}
        elif why == "unhealthy":
            state.supervisor = _StubSup(healthy=False)
            want = {"ready": False, "launch_id": "launch-1",
                    "warming": False, "recovering": False, "healthy": False}
        else:
            set_fn_metadata("WarmupCrasher")
            await state.reload({}, launch_id="launch-1")
            want = {"ready": False, "launch_id": "launch-1",
                    "warming": False, "recovering": False, "healthy": False}
            # held while the rank warms up, let go when it dies
            status, got, took = await _timed_get(
                client, "/ready", launch_id="launch-1", wait=10)
            assert (status, got) == (503, want) and took < 9.0, took
        status, got, took = await _timed_get(
            client, "/ready", launch_id="launch-1", wait=5)
        assert (status, got) == (503, want)
        assert took < 0.1, took
    run_server_test(body)


@pytest.mark.parametrize("path", ["/health", "/metrics", "/ready"])
def test_a_held_ready_keeps_nothing_else_waiting(path):
    """The hold is a coroutine asleep: while several ``/ready`` are held,
    ``/health``, ``/metrics`` and a probe's own ``/ready`` are served in
    their usual time."""
    async def body(client, state):
        _, alone, _ = await _timed_get(client, path)
        _, end = _not_ready(state, "warming")
        held = [asyncio.ensure_future(_timed_get(
            client, "/ready", launch_id="launch-1", wait=5))
            for _ in range(3)]
        await asyncio.sleep(0.1)
        assert not any(h.done() for h in held)
        status, _, took = await _timed_get(client, path)
        assert status == (503 if path == "/ready" else 200)
        assert took < 0.1, took
        end()
        for h in held:
            status, got, took = await h
            assert status == 200 and 0.1 <= took < 0.5
            assert got["boot"]["ready_for_s"] <= 0.05
    run_server_test(body)


def test_array_payload_roundtrip():
    async def body(client, state):
        set_fn_metadata("summer")
        arr = np.arange(6, dtype=np.float32).reshape(2, 3)
        payload = ser.serialize({"args": [arr, arr], "kwargs": {}}, ser.JSON)
        r = await client.post("/summer", data=payload,
                              headers={"X-Serialization": "json"})
        assert r.status == 200, await r.text()
        out = ser.deserialize(await r.read(), ser.JSON)
        np.testing.assert_array_equal(out, arr + arr)
    run_server_test(body)


def test_pickle_rejected_without_allowlist():
    async def body(client, state):
        set_fn_metadata("summer")
        payload = ser.serialize({"args": [1, 2], "kwargs": {}}, ser.PICKLE)
        r = await client.post("/summer", data=payload,
                              headers={"X-Serialization": "pickle"})
        assert r.status == 415
    run_server_test(body)


def test_termination_mid_request():
    async def body(client, state):
        set_fn_metadata("sleeper")
        task = asyncio.ensure_future(
            client.post("/sleeper", json={"args": [30], "kwargs": {}}))
        await asyncio.sleep(1.0)
        state.terminate("Preempted")
        r = await task
        assert r.status == 503
        err = await r.json()
        assert err["error_type"] == "PodTerminatedError"
        assert err["attrs"]["reason"] == "Preempted"
        # subsequent requests rejected immediately
        r2 = await client.post("/sleeper", json={"args": [0], "kwargs": {}})
        assert r2.status == 503
    run_server_test(body)


def test_request_id_propagation():
    async def body(client, state):
        set_fn_metadata("summer")
        r = await client.post("/summer", json={"args": [1, 1], "kwargs": {}},
                              headers={"X-Request-ID": "req-abc"})
        assert r.headers["X-Request-ID"] == "req-abc"
    run_server_test(body)


def test_reload_swaps_callable(tmp_path):
    async def body(client, state):
        set_fn_metadata("summer")
        r = await client.post("/summer", json={"args": [1, 2], "kwargs": {}})
        assert json.loads(await r.read()) == 3
        # hot-swap to a different callable, new launch_id
        r = await client.post("/_kt/reload", json={
            "metadata": {"KT_CLS_OR_FN_NAME": "whoami"},
            "launch_id": "launch-2",
        })
        assert r.status == 200, await r.text()
        # /ready flips to 200 once the prewarmed worker finishes its
        # load+warmup window (503 while warming)
        await wait_ready(client, "launch-2")
        r = await client.post("/whoami", json={"args": [], "kwargs": {}})
        out = json.loads(await r.read())
        assert out["world_size"] == "1"
    run_server_test(body)


def test_metrics_endpoint():
    async def body(client, state):
        r = await client.get("/metrics")
        assert r.status == 200
        text = await r.text()
        assert "kubetorch_last_activity_timestamp" in text
    run_server_test(body)


def test_restart_procs_fresh_worker_per_call():
    """.distribute(restart_procs=True): each call lands in a fresh rank
    subprocess (reference spmd_supervisor.py:265)."""
    async def body(client, state):
        set_fn_metadata("whoami")
        os.environ["KT_DISTRIBUTED_CONFIG"] = json.dumps(
            {"distribution_type": "local", "workers": 1,
             "procs_per_worker": 1, "restart_procs": True})
        r1 = await client.post("/whoami", json={"args": [], "kwargs": {}})
        assert r1.status == 200, await r1.text()
        pid1 = json.loads(await r1.read())["pid"]
        r2 = await client.post("/whoami", json={"args": [], "kwargs": {}})
        pid2 = json.loads(await r2.read())["pid"]
        assert pid1 != pid2, "restart_procs must respawn the worker"
        os.environ.pop("KT_DISTRIBUTED_CONFIG")
    run_server_test(body)


def test_dead_rank_during_warmup_never_ready():
    """A rank that dies inside __kt_warmup__ leaves the pod permanently
    not-ready (503 with healthy=false) instead of joining the endpoint
    pool as a pod that can never serve."""
    async def body(client, state):
        set_fn_metadata("WarmupCrasher")
        await state.reload({}, launch_id="crash-1")
        await poll_ready(
            client, "crash-1",
            lambda s, b: s == 503 and b.get("healthy") is False,
            timeout=30, allowed=(503,))
        # and it STAYS not-ready: no later poll may ever return 200
        for _ in range(10):
            r = await client.get("/ready", params={"launch_id": "crash-1"})
            assert r.status == 503, await r.text()
            await asyncio.sleep(0.1)
    run_server_test(body)


def test_user_metrics_hook_reaches_scrape():
    """__kt_metrics__ (the __kt_warmup__ sibling): numeric gauges from the
    user instance in the rank subprocess land on /metrics as sanitized
    kt_user_ lines — serving state reaches Prometheus with no exporter."""
    async def body(client, state):
        set_fn_metadata("Metered")
        os.environ["KT_CALLABLE_TYPE"] = "cls"
        for _ in range(2):
            r = await client.post("/Metered/ping",
                                  json={"args": [], "kwargs": {}})
            assert r.status == 200, await r.text()
        r = await client.get("/metrics")
        text = await r.text()
        assert "kt_user_calls_total 2.0" in text, text
        assert "kt_user_queue_depth_ 1.5" in text
        assert "not_a_number" not in text
    run_server_test(body)


def test_metrics_scrape_without_hook_unchanged():
    """A callable WITHOUT the hook: scrape stays clean (no kt_user_ lines,
    no errors)."""
    async def body(client, state):
        set_fn_metadata("summer")
        r = await client.post("/summer", json={"args": [2, 3], "kwargs": {}})
        assert r.status == 200
        r = await client.get("/metrics")
        text = await r.text()
        assert r.status == 200
        assert "kt_user_" not in text
    run_server_test(body)

"""The deploy's wait for readiness (ISSUE 37), client side:
``Module._wait_for_http_health`` against stub pods that hold a
``/ready?wait=`` open until the launch is warm, that ignore ``wait``, or that
are not listening yet. What the real pod does with ``wait`` is
``tests/test_http_server.py``'s; a whole deploy against a real pod and rank is
``tests/test_observability.py``'s."""

import asyncio
import threading
import time

import pytest
from aiohttp import web

from kubetorch_tpu import telemetry as tel
from kubetorch_tpu.exceptions import ServiceTimeoutError
from kubetorch_tpu.resources.module import Module
from kubetorch_tpu.resources.pointers import Pointers
from kubetorch_tpu.utils.procs import free_port
from tests.assets.threaded_server import ThreadedAiohttpServer

BOOT = {"rank_warmup_s": 1.5}


class StubPod:
    """A pod's ``/ready`` and nothing else: ready ``warm_after`` seconds
    after its first request. One that ``holds`` keeps a request with
    ``wait`` open until then (or until ``wait`` has run out, at most
    ``cap``); one that does not answers at once, as pods did before."""

    def __init__(self, warm_after, holds=True, cap=10.0):
        self.warm_after = warm_after
        self.ready_at = None
        self.holds = holds
        self.cap = cap
        self.requests = []          # (arrived, wait asked, launch_id)

    def app(self):
        app = web.Application()
        app.router.add_get("/ready", self.ready)
        return app

    async def ready(self, request):
        now = time.monotonic()
        if self.ready_at is None:
            self.ready_at = now + self.warm_after
        wait = float(request.query.get("wait", 0))
        self.requests.append((now, wait, request.query.get("launch_id")))
        if self.holds:
            await asyncio.sleep(max(0.0, min(
                self.ready_at - now, wait, self.cap)))
        slack = time.monotonic() - self.ready_at
        if slack < 0:
            return web.json_response({"ready": False, "warming": True},
                                     status=503)
        return web.json_response({"ready": True, "boot": {
            **BOOT, "ready_for_s": round(slack, 6)}})


def module_at(url):
    svc = Module(Pointers(project_root="/", module_name="m", file_path="m.py",
                          cls_or_fn_name="f"), name="stub")
    svc.service_url = url
    svc.launch_id = "launch-1"
    return svc


@pytest.fixture()
def wait_span():
    """The ``deploy.wait_ready`` span the wait under test leaves."""
    tel.RING.clear()

    def attrs():
        (span,) = [s for s in tel.RING.snapshot()
                   if s["name"] == "deploy.wait_ready"]
        return span["attrs"]
    yield attrs
    tel.RING.clear()


@pytest.mark.parametrize("warm_after,cap,held", [
    (0.7, 10.0, 1),         # one request, held until the launch is warm
    (1.0, 0.4, 3),          # a pod whose cap is short: asked again at once
    (0.0, 10.0, 0),         # ready already: answered at once, nothing held
])
def test_a_pod_that_holds_answers_when_the_launch_is_warm(
        warm_after, cap, held, wait_span, monkeypatch):
    """The deploy returns within 0.1 s of readiness with the pod's ``boot``,
    from requests the pod held; it never sleeps."""
    from kubetorch_tpu.resources import module as module_mod
    monkeypatch.setattr(module_mod, "READY_WAIT_CAP_S", cap)
    pod = StubPod(warm_after, cap=cap)
    with ThreadedAiohttpServer(pod.app) as srv:
        boot = module_at(srv.url)._wait_for_http_health(timeout=30)
    late = time.monotonic() - pod.ready_at
    assert 0.0 <= late <= (0.1 if held else 0.2), late
    assert {k: boot[k] for k in BOOT} == BOOT
    assert 0.0 <= boot["ready_for_s"] <= 0.05 or not held
    got = wait_span()
    assert got["polls"] == len(pod.requests) == max(held, 1)
    assert got["held_polls"] == held
    assert got["last_delay_s"] == 0.0                   # never slept
    assert (got["held_s"] > 0.0) == bool(held)
    assert got["held_s"] <= warm_after + 0.1
    assert all(w == pytest.approx(cap) and lid == "launch-1"
               for _, w, lid in pod.requests)


def test_a_pod_that_does_not_hold_is_polled_with_the_back_off(wait_span):
    """A pod that ignores ``wait`` and says 503 at once is asked again
    after 0.2, 0.4, 0.8, 1.6, 3.0 s ...: seven requests in five seconds at
    most, not a spin; and readiness is still noticed."""
    pod = StubPod(5.0, holds=False)
    with ThreadedAiohttpServer(pod.app) as srv:
        boot = module_at(srv.url)._wait_for_http_health(timeout=30)
    in_5s = [t for t, _, _ in pod.requests if t < pod.ready_at]
    assert 4 <= len(in_5s) <= 7, len(in_5s)
    gaps = [b - a for a, b in zip(in_5s, in_5s[1:])]
    assert all(g >= d for g, d in zip(gaps, (0.2, 0.4, 0.8, 1.6)))
    assert boot["ready_for_s"] > 0.0
    got = wait_span()
    assert got["polls"] == len(pod.requests)
    assert got["held_polls"] == 0 and got["held_s"] == 0.0
    assert got["last_delay_s"] == 3.0


def test_refused_then_held_then_ready(wait_span):
    """Nothing listens at first (refused at once: the back-off's sleeps),
    then the pod comes up and holds the next request until it is warm."""
    pod = StubPod(2.0)
    port = free_port()
    server = ThreadedAiohttpServer(pod.app, port=port)
    threading.Timer(0.5, server.__enter__).start()
    try:
        boot = module_at(f"http://127.0.0.1:{port}") \
            ._wait_for_http_health(timeout=30)
        late = time.monotonic() - pod.ready_at
    finally:
        server.__exit__(None, None, None)
    assert 0.0 <= late <= 0.1, late
    assert boot["ready_for_s"] <= 0.05
    got = wait_span()
    # refused at 0, 0.2 and perhaps 0.6 s; heard from 0.5 s on
    assert len(pod.requests) == 1 and got["held_polls"] == 1
    assert 3 <= got["polls"] <= 4
    assert got["last_delay_s"] in (0.4, 0.8)
    assert 2.0 <= got["held_s"] <= 2.1


@pytest.mark.parametrize("holds", [True, False])
def test_the_deadline_still_raises(holds, wait_span):
    pod = StubPod(60.0, holds=holds)
    with ThreadedAiohttpServer(pod.app) as srv:
        t0 = time.monotonic()
        with pytest.raises(ServiceTimeoutError, match="never became ready"):
            module_at(srv.url)._wait_for_http_health(timeout=1.0)
        took = time.monotonic() - t0
    assert 1.0 <= took < (1.3 if holds else 2.0), took
    got = wait_span()
    assert set(got) >= {"polls", "last_delay_s", "held_polls", "held_s"}
    if holds:
        # asked to be held for what was left of the deadline, and was
        assert pod.requests[0][1] == pytest.approx(1.0, abs=0.05)
        assert got["held_polls"] >= 1 and got["held_s"] >= 0.9
    else:
        assert got["held_polls"] == 0 and got["last_delay_s"] > 0.0


def test_scaled_to_zero_is_asked_between_requests(monkeypatch):
    """An autoscaled service with no pod: the request fails fast and the
    controller's record decides, as before."""
    svc = module_at(f"http://127.0.0.1:{free_port()}")
    asked = []
    monkeypatch.setattr(Module, "_scaled_to_zero",
                        lambda self: asked.append(1) or len(asked) >= 2)
    t0 = time.monotonic()
    assert svc._wait_for_http_health(timeout=30) is None
    assert len(asked) == 2 and time.monotonic() - t0 < 1.0

"""Cluster observability stack (round-2 VERDICT #44 / next #3).

Reference: ``charts/kubetorch/templates/metrics/`` (Prometheus @ 3s scrape),
data-store Loki, and client-side live metric streaming during calls
(``serving/http_client.py:758-795``). TPU-first: pods self-export HBM
gauges, so scraping kt pods IS the accelerator metrics pipeline.
"""

import asyncio
import json
import os
import stat
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "assets"))

pytestmark = pytest.mark.level("unit")

SHIM = os.path.join(os.path.dirname(__file__), "assets", "fake_kubectl.py")


@pytest.fixture()
def shim(tmp_path, monkeypatch):
    os.chmod(SHIM, os.stat(SHIM).st_mode | stat.S_IXUSR | stat.S_IXGRP)
    monkeypatch.setenv("KT_KUBECTL_SHIM_DIR", str(tmp_path))
    return tmp_path


class TestInstaller:
    def test_install_stack_applies_metrics_and_loki(self, shim):
        from kubetorch_tpu.provisioning.installer import install_stack

        applied = install_stack(kubectl=SHIM)
        kinds = {(k, n) for _, k, n in applied}
        assert ("Namespace", "kubetorch") in kinds
        assert ("ConfigMap", "kubetorch-metrics-config") in kinds
        assert ("Deployment", "kubetorch-metrics") in kinds
        assert ("Deployment", "kubetorch-loki") in kinds
        assert ("CustomResourceDefinition",
                "kubetorchworkloads.kubetorch.com") in kinds

        state = json.loads((shim / "state.json").read_text())
        prom_cfg = state["ConfigMap/kubetorch/kubetorch-metrics-config"]
        prom_yml = prom_cfg["data"]["prometheus.yml"]
        # the reference's 3s scrape cadence, targeting kt pods by label
        assert "scrape_interval: 3s" in prom_yml
        assert "kubetorch_com_service" in prom_yml
        assert ":32300" in prom_yml

    def test_install_skip_filters(self, shim):
        from kubetorch_tpu.provisioning.installer import install_stack

        applied = install_stack(kubectl=SHIM, skip=["loki", "kueue"])
        files = {f for f, _, _ in applied}
        assert "loki.yaml" not in files and "kueue-resources.yaml" not in files
        assert "metrics.yaml" in files


class TestPodMetricsEndpoint:
    def test_metrics_includes_tpu_gauges(self, monkeypatch):
        """/metrics must carry the HBM series Prometheus scrapes — not just
        the push-gateway path."""
        from aiohttp.test_utils import TestClient, TestServer

        from kubetorch_tpu.serving import http_server as hs
        from kubetorch_tpu.serving import metrics_push

        monkeypatch.setattr(
            metrics_push, "tpu_gauges",
            lambda: {'kt_tpu_hbm_bytes_in_use{device="0"}': 7 * 2**30,
                     'kt_tpu_hbm_bytes_limit{device="0"}': 16 * 2**30})

        async def body():
            app = hs.create_app()
            async with TestClient(TestServer(app)) as client:
                r = await client.get("/metrics")
                text = await r.text()
                assert 'kt_tpu_hbm_bytes_in_use{device="0"}' in text
                assert "kt_http_requests_total" in text
                return text

        asyncio.run(body())


class TestClientMetricStream:
    def test_format_metrics_compact(self):
        from kubetorch_tpu.serving.http_client import HTTPClient

        text = ('kt_tpu_hbm_bytes_in_use{device="0"} 8589934592\n'
                'kt_tpu_hbm_bytes_limit{device="0"} 17179869184\n'
                "kt_inflight_requests 2\n"
                "kt_http_requests_total 41\n")
        line = HTTPClient._format_metrics(text)
        assert "hbm=8.00/16.00GiB (50%)" in line
        assert "inflight=2" in line and "reqs=41" in line

    def test_stream_polls_and_prints(self, capsys):
        """A live /metrics stub is polled during the stream window and the
        compact line lands on the client's stdout (the 'alongside streamed
        logs' contract)."""
        from aiohttp import web

        from kubetorch_tpu.serving.http_client import HTTPClient

        hits = {"n": 0}

        async def metrics(request):
            hits["n"] += 1
            return web.Response(text=("kt_inflight_requests 1\n"
                                      "kt_http_requests_total 5\n"))

        loop = asyncio.new_event_loop()
        port = {}
        started = threading.Event()

        def serve():
            asyncio.set_event_loop(loop)
            app = web.Application()
            app.router.add_get("/metrics", metrics)
            runner = web.AppRunner(app)
            loop.run_until_complete(runner.setup())
            site = web.TCPSite(runner, "127.0.0.1", 0)
            loop.run_until_complete(site.start())
            port["p"] = site._server.sockets[0].getsockname()[1]
            started.set()
            loop.run_forever()

        t = threading.Thread(target=serve, daemon=True)
        t.start()
        assert started.wait(10)
        try:
            client = HTTPClient(f"http://127.0.0.1:{port['p']}")
            stop = client._start_metric_stream(interval=0.1)
            deadline = time.monotonic() + 10
            while hits["n"] == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            time.sleep(0.15)   # let the pump print after the poll
            stop()
            assert hits["n"] >= 1
            out = capsys.readouterr().out
            assert "[metrics]" in out and "inflight=1" in out
        finally:
            loop.call_soon_threadsafe(loop.stop)


@pytest.mark.slow
@pytest.mark.level("minimal")
class TestMetricStreamE2E:
    def test_long_call_streams_metrics(self, capsys, monkeypatch):
        """The VERDICT 'done' bar: a long call against a real deployed pod
        streams activity metrics to the client alongside logs."""
        import kubetorch_tpu as kt
        from kubetorch_tpu.config import reset_config

        import payloads  # tests/assets

        reset_config()
        try:
            f = kt.fn(payloads.sleeper)
            f.to(kt.Compute(cpus=1))
            try:
                # per-call typed config (reference MetricsConfig), no
                # global flag needed
                f(2.5, metrics=kt.MetricsConfig(interval=0.2))
            finally:
                f.teardown()
            out = capsys.readouterr().out
            assert "[metrics]" in out
            assert "reqs=" in out or "inflight=" in out
        finally:
            reset_config()


    def test_deploy_leaves_one_span_with_the_boot_timeline(self):
        """Module.to() runs under one ``client.deploy`` span: its children
        are the deploy's waits, and the pod's boot phases and
        ``poll_slack_s`` came back with the last /ready (ISSUE 26). With
        the pod torn down, the caller's ring alone renders it. Through the
        real local daemon, so slow like the rest of this class; tier-1 has
        ``TestTimelineBackToTheCaller``'s twin with a stand-in controller."""
        import kubetorch_tpu as kt
        from kubetorch_tpu.config import reset_config
        from kubetorch_tpu.serving.http_server import BOOT_PHASES

        import payloads  # tests/assets

        reset_config()
        tel.RING.clear()
        try:
            f = kt.cls(payloads.Warmable)
            t0 = time.monotonic()
            f.to(kt.Compute(cpus=1))
            t1 = time.monotonic()
            try:
                assert f.was_warmed() is True
            finally:
                f.teardown()
            spans = tel.RING.snapshot()
            deploys = [s for s in spans if s["name"] == "client.deploy"]
            assert len(deploys) == 1
            dep = deploys[0]
            assert t0 <= dep["start_mono"] <= dep["end_mono"] <= t1
            kids = {s["name"]: s for s in spans
                    if s["parent_id"] == dep["span_id"]}
            assert {"deploy.launch", "deploy.check_service_ready",
                    "deploy.wait_ready"} <= set(kids)
            assert kids["deploy.wait_ready"]["attrs"]["polls"] >= 1
            assert kids["deploy.wait_ready"]["attrs"]["held_polls"] >= 1
            assert kids["deploy.check_service_ready"]["attrs"]["polls"] >= 1
            attrs = dep["attrs"]
            for phase in BOOT_PHASES:
                assert attrs["boot." + phase] >= 0.0, phase
            # a fresh pod: its process booted, its pool spawned, its rank
            # imported and built the class and ran the warm-up hook
            for phase in ("pod_boot_s", "pool_spawn_s", "rank_spawn_s",
                          "rank_import_s"):
                assert attrs["boot." + phase] > 0.0, phase
            assert 0.0 <= attrs["poll_slack_s"] <= 0.5   # held, not polled
            took = dep["end_mono"] - dep["start_mono"]
            assert attrs["boot.rank_spawn_s"] + attrs["poll_slack_s"] < took
            text = tel.format_waterfall(tel.RING.find(dep["trace_id"]))
            assert "client.deploy" in text and "boot: " in text
            assert "rank_warmup=" in text and "poll_slack_s=" in text
        finally:
            reset_config()


class TestPromQueryPassthrough:
    def test_query_relays_to_prometheus(self, monkeypatch):
        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        from kubetorch_tpu.controller.app import (ControllerState,
                                                  create_controller_app)

        seen = {}

        async def query(request):
            seen["query"] = request.query.get("query")
            return web.json_response({"status": "success",
                                      "data": {"result": [{"value": [0, "2"]}]}})

        async def body():
            prom = web.Application()
            prom.router.add_get("/api/v1/query", query)
            async with TestClient(TestServer(prom)) as prom_client:
                monkeypatch.setenv(
                    "KT_PROMETHEUS_URL",
                    str(prom_client.make_url("")).rstrip("/"))
                state = ControllerState()
                async with TestClient(
                        TestServer(create_controller_app(state))) as ctl:
                    r = await ctl.get("/controller/metrics/query",
                                      params={"query": "up"})
                    assert r.status == 200
                    assert (await r.json())["status"] == "success"
            assert seen["query"] == "up"

        asyncio.run(body())

    def test_query_without_stack_is_503(self, monkeypatch):
        from aiohttp.test_utils import TestClient, TestServer

        from kubetorch_tpu.controller.app import (ControllerState,
                                                  create_controller_app)

        monkeypatch.delenv("KT_PROMETHEUS_URL", raising=False)

        async def body():
            state = ControllerState()
            async with TestClient(
                    TestServer(create_controller_app(state))) as ctl:
                r = await ctl.get("/controller/metrics/query",
                                  params={"query": "up"})
                assert r.status == 503

        asyncio.run(body())


class TestLokiForwarding:
    def test_controller_forwards_log_batches(self, monkeypatch):
        """POST /controller/logs fans out to Loki's push API when
        KT_LOKI_URL is set (durability beyond the ring buffer)."""
        from aiohttp import web
        from aiohttp.test_utils import TestClient, TestServer

        from kubetorch_tpu.controller.app import (ControllerState,
                                                  create_controller_app)

        received = []

        async def loki_push(request):
            received.append(await request.json())
            return web.json_response({})

        async def body():
            loki = web.Application()
            loki.router.add_post("/loki/api/v1/push", loki_push)
            async with TestClient(TestServer(loki)) as loki_client:
                loki_url = str(loki_client.make_url("")).rstrip("/")
                monkeypatch.setenv("KT_LOKI_URL", loki_url)

                state = ControllerState()
                async with TestClient(
                        TestServer(create_controller_app(state))) as ctl:
                    r = await ctl.post("/controller/logs", json={
                        "entries": [{"namespace": "ns1", "service": "svc",
                                     "line": "hello loki", "ts": time.time()}]})
                    assert r.status == 200
                    deadline = time.monotonic() + 10
                    while not received and time.monotonic() < deadline:
                        await asyncio.sleep(0.05)
            assert received, "no push reached the Loki stub"
            stream = received[0]["streams"][0]
            assert stream["stream"] == {"namespace": "ns1", "service": "svc",
                                        "source": "kubetorch"}
            assert "hello loki" in stream["values"][0][1]

        asyncio.run(body())


class TestResourceScopeLatch:
    """Only the controller's own 'no metrics stack configured' sentinel may
    permanently disable resource-scope streaming; a 503 relayed from a
    transiently-unavailable Prometheus must stay retryable (advisor
    round-3 finding)."""

    class _Resp:
        def __init__(self, status, headers=None, body=""):
            self.status_code = status
            self.headers = headers or {}
            self.text = body

        def json(self):
            import json as _json
            return _json.loads(self.text)

    def _client(self, monkeypatch, responses):
        from kubetorch_tpu.config import reset_config
        from kubetorch_tpu.serving import http_client as hc

        monkeypatch.setenv("KT_API_URL", "http://controller.test")
        reset_config()
        calls = iter(responses)
        monkeypatch.setattr(hc._requests, "get",
                            lambda *a, **k: next(calls))
        c = hc.HTTPClient("http://127.0.0.1:1", service="svc")
        return c

    def test_relayed_503_does_not_latch(self, monkeypatch):
        from kubetorch_tpu.config import reset_config
        try:
            c = self._client(monkeypatch, [
                self._Resp(503, body='{"error": "prometheus unreachable"}')])
            assert c._resource_scope_line() is None
            assert c._resource_scope_dead is False
        finally:
            reset_config()

    def test_sentinel_header_latches(self, monkeypatch):
        from kubetorch_tpu.config import reset_config
        try:
            c = self._client(monkeypatch, [
                self._Resp(503, headers={"X-KT-Unconfigured": "metrics"},
                           body='{"error": "no metrics stack configured"}')])
            assert c._resource_scope_line() is None
            assert c._resource_scope_dead is True
        finally:
            reset_config()

    def test_sentinel_body_latches_without_header(self, monkeypatch):
        """Older controllers without the header still latch via the body."""
        from kubetorch_tpu.config import reset_config
        try:
            c = self._client(monkeypatch, [
                self._Resp(503, body='{"error": "no metrics stack '
                                     'configured (deploy/metrics.yaml)"}')])
            assert c._resource_scope_line() is None
            assert c._resource_scope_dead is True
        finally:
            reset_config()


# ---------------------------------------------------------------------------
# ISSUE 5: end-to-end request tracing + the unified metrics plane
# ---------------------------------------------------------------------------

import uuid as _uuid

from kubetorch_tpu import telemetry as tel

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


@pytest.fixture()
def clean_ring():
    tel.RING.clear()
    yield
    tel.RING.clear()


@pytest.fixture()
def pod_metadata(monkeypatch):
    monkeypatch.setenv("KT_PROJECT_ROOT", ASSETS)
    monkeypatch.setenv("KT_MODULE_NAME", "payloads")
    monkeypatch.setenv("KT_FILE_PATH", "payloads.py")
    monkeypatch.setenv("KT_LAUNCH_ID", "obs-1")
    monkeypatch.delenv("KT_DISTRIBUTED_CONFIG", raising=False)
    monkeypatch.delenv("POD_IP", raising=False)
    monkeypatch.delenv("KT_CHAOS", raising=False)


class TestTelemetrySpans:
    def test_nesting_parenting_and_ring(self, clean_ring):
        with tel.span("outer", request_id="req-nest") as outer:
            with tel.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
                tel.add_event("hello", k=1)
            # inner closed: current reverts to outer
            assert tel.current_span() is outer
        spans = tel.RING.find("req-nest")
        assert {s["name"] for s in spans} == {"outer", "inner"}
        inner_d = next(s for s in spans if s["name"] == "inner")
        assert inner_d["events"][0]["name"] == "hello"
        assert inner_d["events"][0]["attrs"] == {"k": 1}
        # request_id lookup returned the WHOLE trace, not just the
        # span carrying the attribute
        assert tel.RING.find(outer.trace_id) == spans

    def test_spans_carry_monotonic_stamps(self, clean_ring):
        """One clock (ISSUE 26): beside the wall stamps a span and its
        events carry ``time.monotonic()`` ones, in ``to_dict()`` and so in
        the ring, /debug/traces and the recorder."""
        t0 = time.monotonic()
        with tel.span("timed") as sp:
            tel.add_event("tick")
            mid = sp.to_dict()               # still open: stamped "now"
            time.sleep(0.01)
        t1 = time.monotonic()
        d = tel.RING.snapshot()[-1]
        assert d == sp.to_dict()
        assert t0 <= d["start_mono"] <= d["events"][0]["mono"] \
            <= mid["end_mono"] <= d["end_mono"] <= t1
        assert d["end_mono"] - d["start_mono"] >= 0.01
        assert abs((d["end"] - d["start"])
                   - (d["end_mono"] - d["start_mono"])) < 0.005
        assert sp.seconds() == d["end_mono"] - d["start_mono"]
        with tel.span("open") as live:
            active = [s for s in tel.active_spans() if s["name"] == "open"]
            assert active[0]["end_mono"] is None
            assert active[0]["start_mono"] == live.start_mono

    def test_header_roundtrip_continues_trace(self, clean_ring):
        with tel.span("client.call") as sp:
            headers = {}
            tel.inject(headers)
            assert headers[tel.TRACE_HEADER] == f"{sp.trace_id}-{sp.span_id}"
            ctx = tel.extract(headers)
        with tel.span("server.request", parent=ctx) as remote:
            assert remote.trace_id == sp.trace_id
            assert remote.parent_id == sp.span_id

    def test_malformed_header_is_none(self):
        assert tel.parse_trace(None) is None
        assert tel.parse_trace("") is None
        assert tel.parse_trace("no-separator-missing") is not None  # 2 parts
        assert tel.parse_trace("loneid") is None

    def test_disabled_fast_path_is_shared_noop(self, monkeypatch):
        monkeypatch.setenv("KT_TRACE", "0")
        assert tel.span("x") is tel.NOOP_SPAN
        assert tel.current_header() is None
        with tel.span("x") as sp:
            assert not sp
            sp.set_attr("a", 1)
            sp.set_status("error")
            tel.add_event("e")      # no active span: silent no-op
        monkeypatch.setenv("KT_TRACE", "1")
        assert tel.span("y") is not tel.NOOP_SPAN

    def test_ring_bounded_and_dedups_by_span_id(self):
        ring = tel.TraceRing(capacity=4)
        for i in range(10):
            ring.add({"trace_id": "t", "span_id": str(i), "start": float(i)})
        assert len(ring) == 4
        # re-ingesting an existing span (worker re-ships trace prefixes)
        # upserts instead of duplicating
        ring.add({"trace_id": "t", "span_id": "9", "start": 99.0})
        assert len(ring) == 4

    def test_error_status_recorded(self, clean_ring):
        with pytest.raises(ValueError):
            with tel.span("boom", request_id="req-err"):
                raise ValueError("zap")
        (s,) = tel.RING.find("req-err")
        assert s["status"] == "error" and s["attrs"]["error"] == "ValueError"


class TestMetricsExposition:
    def test_counter_help_type_and_label_escaping(self):
        name = f"kt_t_{_uuid.uuid4().hex[:8]}_total"
        c = tel.counter(name, "helptext", labels=("kind",))
        c.inc(kind='a"b\\c\nd')
        text = tel.REGISTRY.render()
        assert f"# HELP {name} helptext" in text
        assert f"# TYPE {name} counter" in text
        assert f'{name}{{kind="a\\"b\\\\c\\nd"}} 1' in text

    def test_histogram_exposition_parses_under_prometheus_client(self):
        prom = pytest.importorskip("prometheus_client")
        from prometheus_client.parser import text_string_to_metric_families

        name = f"kt_t_{_uuid.uuid4().hex[:8]}_seconds"
        h = tel.histogram(name, "stage latency", labels=("stage",),
                          buckets=(0.1, 1.0))
        h.observe(0.05, stage="execute")
        h.observe(0.5, stage="execute")
        fams = {f.name: f for f in
                text_string_to_metric_families(tel.REGISTRY.render())}
        fam = fams[name]
        assert fam.type == "histogram"
        samples = {(s.name, s.labels.get("le")): s.value
                   for s in fam.samples if s.labels.get("stage") == "execute"}
        assert samples[(f"{name}_bucket", "0.1")] == 1
        assert samples[(f"{name}_bucket", "1")] == 2
        assert samples[(f"{name}_bucket", "+Inf")] == 2
        assert samples[(f"{name}_count", None)] == 2
        assert abs(samples[(f"{name}_sum", None)] - 0.55) < 1e-9

    def test_stage_timer_observes_histogram(self):
        before = tel.stage_histogram().count(stage="deserialize")
        with tel.stage("deserialize"):
            pass
        assert tel.stage_histogram().count(stage="deserialize") == before + 1

    def test_render_untyped_gauges_headers(self):
        text = tel.render_untyped_gauges({
            'kt_tpu_hbm_bytes_in_use{device="0"}': 7,
            'kt_tpu_hbm_bytes_in_use{device="1"}': 9,
            "kt_heartbeat_sent": 1.5,
        })
        assert text.count("# TYPE kt_tpu_hbm_bytes_in_use gauge") == 1
        assert "# TYPE kt_heartbeat_sent gauge" in text
        assert 'kt_tpu_hbm_bytes_in_use{device="1"} 9' in text


class TestMetricsPusherFixes:
    class _State:
        last_activity = 123.0
        request_count = 7

    def test_payload_has_type_headers(self):
        from kubetorch_tpu.serving.metrics_push import MetricsPusher

        p = MetricsPusher("http://gw.test", state=self._State())
        payload = p._payload()
        assert "# TYPE kubetorch_last_activity_timestamp gauge" in payload
        assert "# TYPE kt_http_requests_total gauge" in payload
        assert "kt_http_requests_total 7" in payload
        # the registry (incl. the push-failure counter) rides along
        assert "# TYPE kt_metrics_push_failures_total counter" in payload

    def test_push_failures_counted_and_logged_once_per_streak(self, capsys):
        from kubetorch_tpu.serving.metrics_push import (_PUSH_FAILURES,
                                                        MetricsPusher)

        p = MetricsPusher("http://gw.test", state=self._State())
        before = _PUSH_FAILURES.value()
        p._record_failure(ConnectionError("nope"))
        p._record_failure(ConnectionError("nope"))
        p._record_failure(ConnectionError("nope"))
        assert _PUSH_FAILURES.value() == before + 3
        out = capsys.readouterr().out
        assert out.count("metrics push") == 1       # one log per streak

    def test_device_label_escaped(self):
        # tpu_gauges needs a live TPU; the escaping primitive it now uses
        # is assertable directly
        assert tel.escape_label_value('dev"0\n') == 'dev\\"0\\n'


class TestRequestIdOnAllResponses:
    def _run(self, coro_fn, env=None):
        from aiohttp.test_utils import TestClient, TestServer

        from kubetorch_tpu.serving.http_server import ServerState, create_app

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

    def test_deadline_rejection_504_carries_request_id(self, pod_metadata,
                                                       monkeypatch):
        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "summer")

        async def body(client, state):
            r = await client.post(
                "/summer", json={"args": [1, 2], "kwargs": {}},
                headers={"X-Request-ID": "rid-504",
                         "X-KT-Deadline": f"{time.time() - 5:.6f}"})
            assert r.status == 504
            assert r.headers["X-Request-ID"] == "rid-504"
        self._run(body)

    def test_terminating_503_carries_request_id(self, pod_metadata,
                                                monkeypatch):
        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "summer")

        async def body(client, state):
            state.termination.set()
            state.termination_reason = "Evicted"
            r = await client.post("/summer",
                                  json={"args": [1, 2], "kwargs": {}},
                                  headers={"X-Request-ID": "rid-503"})
            assert r.status == 503
            assert r.headers["X-Request-ID"] == "rid-503"
        self._run(body)

    def test_idempotent_replay_carries_request_id(self, pod_metadata,
                                                  monkeypatch):
        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "summer")

        async def body(client, state):
            k = {"X-KT-Idempotency-Key": "obs-replay-1"}
            r1 = await client.post("/summer",
                                   json={"args": [4, 5], "kwargs": {}},
                                   headers={**k, "X-Request-ID": "rid-a"})
            assert r1.status == 200
            r2 = await client.post("/summer",
                                   json={"args": [4, 5], "kwargs": {}},
                                   headers={**k, "X-Request-ID": "rid-b"})
            assert r2.status == 200
            assert r2.headers["X-KT-Idempotent-Replay"] == "1"
            assert r2.headers["X-Request-ID"] == "rid-b"
        self._run(body)


class TestTracePropagationE2E:
    """The acceptance waterfall: client call → pod server → rank worker →
    store fetch is ONE trace with correctly parented spans, queryable from
    the pod's /debug/traces flight recorder."""

    def test_client_server_worker_store_single_trace(self, pod_metadata,
                                                     clean_ring,
                                                     monkeypatch, tmp_path):
        import numpy as np

        import requests as _rq

        from kubetorch_tpu.data_store import commands as ds
        from kubetorch_tpu.data_store.store_server import create_store_app
        from kubetorch_tpu.serving.http_client import HTTPClient
        from kubetorch_tpu.serving.http_server import create_app
        from tests.assets.threaded_server import ThreadedAiohttpServer

        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "store_fetcher")
        arr = np.arange(64, dtype=np.float32)

        with ThreadedAiohttpServer(
                lambda: create_store_app(str(tmp_path / "store"))) as store:
            ds.put("obs/e2e/weights", arr, store_url=store.url)
            with ThreadedAiohttpServer(create_app) as srv:
                client = HTTPClient(srv.url, stream_logs=False)
                out = client.call_method(
                    "store_fetcher", args=(store.url, "obs/e2e/weights"),
                    timeout=120)
                assert out == float(arr.sum())

                # the client span is in OUR ring; everything else must have
                # joined its trace
                client_span = next(
                    s for s in reversed(tel.RING.snapshot())
                    if s["name"] == "client.call")
                trace_id = client_span["trace_id"]

                def spans_by_name():
                    r = _rq.get(f"{srv.url}/debug/traces",
                                params={"q": trace_id}, timeout=10)
                    assert r.status == 200 if hasattr(r, "status") \
                        else r.status_code == 200
                    return {s["name"]: s for s in r.json()["spans"]}

                # worker spans arrive over the response queue a beat after
                # the HTTP response — poll briefly
                deadline = time.monotonic() + 15
                spans = spans_by_name()
                while time.monotonic() < deadline and not (
                        "worker.execute" in spans
                        and "store.fetch" in spans):
                    time.sleep(0.2)
                    spans = spans_by_name()

                assert "server.request" in spans, spans.keys()
                assert "stage.deserialize" in spans
                assert "stage.execute" in spans
                assert "worker.execute" in spans, (
                    "rank-worker spans never shipped back")
                assert "store.fetch" in spans
                assert "store.request" in spans

                # one trace, correctly parented across every boundary
                for s in spans.values():
                    assert s["trace_id"] == trace_id
                assert spans["server.request"]["parent_id"] == \
                    client_span["span_id"]
                assert spans["stage.execute"]["parent_id"] == \
                    spans["server.request"]["span_id"]
                assert spans["worker.execute"]["parent_id"] == \
                    spans["stage.execute"]["span_id"]
                assert spans["worker.execute"]["attrs"]["request_id"] == \
                    client_span["attrs"]["request_id"]
                # store fetch happened in the worker process, source-tagged
                assert spans["store.fetch"]["attrs"]["source"] == "store"
                assert spans["store.fetch"]["attrs"]["bytes"] == arr.nbytes
                # queue wait was measured and shipped
                assert "queue_wait_s" in spans["worker.execute"]["attrs"]


class TestTimelineBackToTheCaller:
    """ISSUE 26: the pod answers every call with X-KT-Timing, and the
    client writes it onto its ``client.call`` span, so the caller's ring
    alone shows where the call's time went."""

    @staticmethod
    def _last_call():
        return next(s for s in reversed(tel.RING.snapshot())
                    if s["name"] == "client.call")

    def test_server_stages_and_engine_life_on_the_callers_span(
            self, pod_metadata, clean_ring, monkeypatch):
        from kubetorch_tpu.serving.http_client import HTTPClient
        from kubetorch_tpu.serving.http_server import create_app
        from tests.assets.threaded_server import ThreadedAiohttpServer

        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "EngineService")
        with ThreadedAiohttpServer(create_app) as srv:
            client = HTTPClient(srv.url, stream_logs=False)
            toks = client.call_method("EngineService", "generate",
                                      args=([3, 5, 7], 6), timeout=180)
            assert len(toks) == 6
            first = self._last_call()
            toks = client.call_method("EngineService", "generate",
                                      args=([3, 5, 7], 6), timeout=180)
            span = self._last_call()
        assert span["span_id"] != first["span_id"]
        attrs = span["attrs"]
        took_ms = 1e3 * (span["end_mono"] - span["start_mono"])
        stages = {k: v for k, v in attrs.items()
                  if k.startswith("server.")}
        assert {"server.deserialize_ms", "server.queue_wait_ms",
                "server.execute_ms", "server.device_transfer_ms",
                "server.respond_ms"} <= set(stages)
        assert all(v >= 0.0 for v in stages.values())
        # the stages are disjoint parts of the pod's handling of the call,
        # but for execute, which holds the rank's side
        parts = sum(v for k, v in stages.items()
                    if k != "server.execute_ms")
        assert parts + attrs["rank.execute_ms"] <= attrs["pod.total_ms"] + 1
        assert attrs["server.execute_ms"] <= attrs["pod.total_ms"] <= took_ms
        assert sum(stages.values()) <= 2 * took_ms
        # the engine's account of the request, from inside
        assert attrs["engine.blocks"] >= 1
        assert 0 <= attrs["engine.blocks_ahead"] <= attrs["engine.blocks"]
        for key in ("engine.queue_ms", "engine.prefill_ms",
                    "engine.decode_ms", "engine.host_ms", "engine.wait_ms"):
            assert 0.0 <= attrs[key] <= attrs["rank.execute_ms"], key
        assert any(k.startswith("engine.host.") for k in attrs)
        # the pod is gone; the caller's ring renders the whole call
        text = tel.format_waterfall(tel.RING.find(span["trace_id"]))
        assert "server: " in text and "queue_wait=" in text
        assert "engine: " in text and "prefill=" in text \
            and "decode=" in text

    def test_deploy_leaves_one_span_with_the_boot_timeline(
            self, pod_metadata, clean_ring, monkeypatch):
        """``Module.to()`` runs under one ``client.deploy`` span whose
        children are the deploy's waits, and the pod's boot phases and
        ``poll_slack_s`` come back with the last /ready. The pod is a real
        server with a real rank; the controller is a stand-in that hands
        the launch to it, as the daemon's push would (the deploy through
        the daemon itself is ``TestMetricStreamE2E``'s, outside tier-1)."""
        import requests as _rq

        import kubetorch_tpu as kt
        from kubetorch_tpu.resources import compute as compute_mod
        from kubetorch_tpu.serving.http_server import BOOT_PHASES, create_app
        from tests.assets.threaded_server import ThreadedAiohttpServer

        import payloads  # tests/assets

        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "Warmable")

        class Controller:
            polls = 0

            def check_ready(self, namespace, name):
                self.polls += 1
                return {"ready": self.polls >= 2}

        monkeypatch.setattr(compute_mod, "controller_client", Controller)
        with ThreadedAiohttpServer(create_app) as srv:
            def launch(self, name, metadata, launch_id=None):
                r = _rq.post(f"{srv.url}/_kt/reload", timeout=60, json={
                    "metadata": {}, "launch_id": launch_id})
                assert r.status_code == 200, r.text
                return {"launch_id": launch_id, "service_url": srv.url}

            monkeypatch.setattr(kt.Compute, "_launch", launch)
            svc = kt.cls(payloads.Warmable)
            t0 = time.monotonic()
            svc.to(kt.Compute(cpus=1))
            t1 = time.monotonic()
            assert svc.was_warmed() is True
        spans = tel.RING.snapshot()
        deploys = [s for s in spans if s["name"] == "client.deploy"]
        assert len(deploys) == 1
        dep = deploys[0]
        assert t0 <= dep["start_mono"] <= dep["end_mono"] <= t1
        kids = {s["name"]: s for s in spans
                if s["parent_id"] == dep["span_id"]}
        assert {"deploy.sync_code", "deploy.launch",
                "deploy.check_service_ready", "deploy.wait_ready"} \
            <= set(kids)
        assert kids["deploy.check_service_ready"]["attrs"]["polls"] == 2
        assert kids["deploy.check_service_ready"]["attrs"][
            "last_delay_s"] == 0.25
        waited = kids["deploy.wait_ready"]["attrs"]
        # the pod held the request through the rank's boot (ISSUE 37): the
        # client never slept beside it
        assert waited["polls"] >= waited["held_polls"] >= 1
        assert waited["held_s"] > 0.0 and waited["last_delay_s"] == 0.0
        attrs = dep["attrs"]
        assert {"boot." + p for p in BOOT_PHASES} | {"poll_slack_s"} \
            <= set(attrs)
        assert all(attrs["boot." + p] >= 0.0 for p in BOOT_PHASES)
        for phase in ("pool_spawn_s", "rank_spawn_s", "rank_import_s"):
            assert attrs["boot." + phase] > 0.0, phase
        assert 0.0 <= attrs["poll_slack_s"] <= 0.5      # held, not polled
        took = dep["end_mono"] - dep["start_mono"]
        assert attrs["boot.rank_spawn_s"] + attrs["poll_slack_s"] < took
        # the pod is gone; the caller's ring alone renders the deploy
        text = tel.format_waterfall(tel.RING.find(dep["trace_id"]))
        assert "client.deploy" in text and "deploy.wait_ready" in text
        assert "boot: " in text and "rank_warmup=" in text
        assert "poll_slack_s=" in text

    def test_fanned_out_call_reports_the_slowest_rank(self):
        timing = {"server.execute": 0.5}
        tel.merge_timing(timing, {"rank.execute": 0.2, "engine.blocks": 3,
                                  "server.device_transfer": 0.01})
        tel.merge_timing(timing, {"rank.execute": 0.4,
                                  "server.queue_wait": 0.02})
        tel.merge_timing(timing, {"rank.execute": 0.3, "engine.blocks": 9})
        flat = tel.finish_call_timing(timing)
        assert flat["rank.execute"] == 0.4 and "engine.blocks" not in flat
        assert flat["server.respond"] == pytest.approx(0.5 - 0.4 - 0.02)
        assert tel.parse_timing(tel.format_timing(flat))[
            "server.queue_wait_ms"] == pytest.approx(20.0)

    def test_garbage_timing_header_is_ignored(self, clean_ring):
        from aiohttp import web

        from kubetorch_tpu.serving.http_client import HTTPClient
        from tests.assets.threaded_server import ThreadedAiohttpServer

        assert tel.parse_timing(None) == {}
        assert tel.parse_timing(12) == {}
        assert tel.parse_timing(";;;===,, ,a;dur=x,b;n=1.5,c d;dur=1") == {}
        assert tel.parse_timing("ok;dur=1.5, bad;dur=, n;n=4,;dur=2") == \
            {"ok_ms": 1.5, "n": 4}

        async def answer(request):
            return web.json_response(
                41, headers={tel.TIMING_HEADER: "%%;;=,dur;dur=dur, =;="})

        def app():
            a = web.Application()
            a.router.add_post("/f", answer)
            return a

        with ThreadedAiohttpServer(app) as srv:
            out = HTTPClient(srv.url, stream_logs=False).call_method(
                "f", timeout=30)
        assert out == 41
        attrs = self._last_call()["attrs"]
        assert attrs["status"] == 200
        assert not [k for k in attrs if k.startswith(("server.", "dur"))]

    def test_tracing_off_sends_no_header_and_allocates_no_span(
            self, pod_metadata, clean_ring, monkeypatch):
        import requests as _rq

        from kubetorch_tpu.serving.http_server import create_app
        from tests.assets.threaded_server import ThreadedAiohttpServer

        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "summer")
        with ThreadedAiohttpServer(create_app) as srv:
            body = json.dumps({"args": [1, 2], "kwargs": {}})
            on = _rq.post(f"{srv.url}/summer", data=body, timeout=120)
            assert on.json() == 3
            sent = tel.parse_timing(on.headers[tel.TIMING_HEADER])
            assert sent["server.execute_ms"] > 0
            assert "rank.execute_ms" in sent and "pod.total_ms" in sent
            assert len(on.headers[tel.TIMING_HEADER]) < 400
        # off before the pod and its rank start (a rank keeps the
        # environment it was spawned with)
        monkeypatch.setenv("KT_TRACE", "0")
        assert tel.begin_call_timing() is None
        assert tel.span("x") is tel.NOOP_SPAN
        with ThreadedAiohttpServer(create_app) as srv:
            time.sleep(0.5)      # the first pod's last rank spans are in
            tel.RING.clear()
            off = _rq.post(f"{srv.url}/summer", data=body, timeout=120)
            assert off.json() == 3
            assert tel.TIMING_HEADER not in off.headers
            time.sleep(0.5)      # rank spans would have shipped by now
            assert len(tel.RING) == 0


class TestChaosRetryThroughTraces:
    """KT_CHAOS=503*2 → the client span shows exactly 2 retry events with
    the policy's backoff delays, and the server flight recorder shows the
    faulted attempts annotated with chaos.fault events."""

    def test_5xx_retries_are_span_events(self, pod_metadata, clean_ring,
                                         monkeypatch):
        import requests as _rq

        from kubetorch_tpu.resilience import RetryPolicy
        from kubetorch_tpu.serving.http_client import HTTPClient
        from kubetorch_tpu.serving.http_server import create_app
        from tests.assets.threaded_server import ThreadedAiohttpServer

        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "summer")
        monkeypatch.setenv("KT_CHAOS", "503:0.01*2")
        monkeypatch.setenv("KT_CHAOS_SEED", "1234")

        with ThreadedAiohttpServer(create_app) as srv:
            client = HTTPClient(srv.url, stream_logs=False)
            policy = RetryPolicy(max_attempts=4, base_delay=0.02,
                                 max_delay=0.05, seed=777)
            out = client.call_method("summer", args=(2, 3),
                                     idempotency_key="obs-chaos-1",
                                     retry=policy, timeout=60)
            assert out == 5

            client_span = next(s for s in reversed(tel.RING.snapshot())
                               if s["name"] == "client.call")
            retries = [e for e in client_span["events"]
                       if e["name"] == "retry"]
            assert len(retries) == 2
            assert [e["attrs"]["delay_s"] for e in retries] == \
                [round(d, 6) for d in client.last_retry_delays]
            assert all(e["attrs"]["reason"] == "status"
                       and e["attrs"]["status"] == 503 for e in retries)

            # server side: 3 attempts in one trace, 2 annotated as faulted
            r = _rq.get(f"{srv.url}/debug/traces",
                        params={"q": client_span["trace_id"]}, timeout=10)
            server_spans = [s for s in r.json()["spans"]
                            if s["name"] == "server.request"]
            assert len(server_spans) == 3
            faulted = [s for s in server_spans
                       if any(e["name"] == "chaos.fault"
                              for e in s["events"])]
            assert len(faulted) == 2
            assert all(e["attrs"]["kind"] == "status"
                       for s in faulted for e in s["events"]
                       if e["name"] == "chaos.fault")


class TestWatchdogSpans:
    def test_death_recorded_as_span_and_counter(self, clean_ring):
        from types import SimpleNamespace

        from kubetorch_tpu.serving import watchdog as wd

        dead = SimpleNamespace(alive=False, exitcode=-9, in_warmup=False)
        pool = SimpleNamespace(
            workers=[dead], _stopping=threading.Event(),
            framework_name="spmd",
            fail_worker_futures=lambda idx, exc: None,
            cancel_pending=lambda exc: None,
            restart_all=lambda exc=None: None,
            restart_worker=lambda idx: None)
        dog = wd.Watchdog(pool, interval_s=10.0, budget=1, window_s=60.0)
        before = wd._DEATHS.value(cause="Killed")
        dog.check_now()
        assert wd._DEATHS.value(cause="Killed") == before + 1
        names = {s["name"] for s in tel.RING.snapshot()}
        assert "watchdog.death" in names
        assert "watchdog.restart" in names
        death = next(s for s in tel.RING.snapshot()
                     if s["name"] == "watchdog.death")
        assert death["attrs"]["cause"] == "Killed"
        assert death["attrs"]["rank"] == 0


class TestLogCaptureTraceJoin:
    def test_add_binds_request_and_trace_ids(self, clean_ring):
        from kubetorch_tpu.serving.http_server import request_id_var
        from kubetorch_tpu.serving.log_capture import LogCapture

        cap = LogCapture(sink_url="http://sink.test", labels={"pod": "p1"})
        token = request_id_var.set("rid-join")
        try:
            with tel.span("server.request") as sp:
                cap.add("hello from the request")
            cap.add("rank line", request_id="rid-rank", trace_id="tr-rank")
        finally:
            request_id_var.reset(token)
        a, b = cap._buffer
        assert a["request_id"] == "rid-join"
        assert a["trace_id"] == sp.trace_id
        assert b["request_id"] == "rid-rank" and b["trace_id"] == "tr-rank"


class TestWaterfallAndCLI:
    def test_format_waterfall_tree_and_events(self):
        t0 = 1000.0
        spans = [
            {"name": "client.call", "trace_id": "tr1", "span_id": "a",
             "parent_id": None, "start": t0, "end": t0 + 0.1,
             "status": "ok", "attrs": {"request_id": "r1"},
             "events": [{"ts": t0 + 0.01, "name": "retry",
                         "attrs": {"attempt": 0, "delay_s": 0.02}}]},
            {"name": "server.request", "trace_id": "tr1", "span_id": "b",
             "parent_id": "a", "start": t0 + 0.02, "end": t0 + 0.09,
             "status": "ok", "attrs": {}, "events": []},
        ]
        out = tel.format_waterfall(spans)
        assert "trace tr1" in out
        assert "client.call" in out and "server.request" in out
        assert "• retry" in out and "delay_s=0.02" in out
        # child indented under parent
        client_line = next(l for l in out.splitlines() if "client.call" in l)
        server_line = next(l for l in out.splitlines()
                           if "server.request" in l)
        assert server_line.index("server.request") > \
            client_line.index("client.call")

    def test_kt_trace_cli_waterfall(self, pod_metadata, clean_ring,
                                    monkeypatch):
        from click.testing import CliRunner

        from kubetorch_tpu.cli import cli
        from kubetorch_tpu.serving.http_client import HTTPClient
        from kubetorch_tpu.serving.http_server import create_app
        from tests.assets.threaded_server import ThreadedAiohttpServer

        monkeypatch.setenv("KT_CLS_OR_FN_NAME", "summer")
        with ThreadedAiohttpServer(create_app) as srv:
            client = HTTPClient(srv.url, stream_logs=False)
            assert client.call_method("summer", args=(1, 2),
                                      timeout=60) == 3
            client_span = next(s for s in reversed(tel.RING.snapshot())
                               if s["name"] == "client.call")
            runner = CliRunner()
            res = runner.invoke(cli, ["trace", client_span["trace_id"],
                                      "--url", srv.url])
            assert res.exit_code == 0, res.output
            assert "server.request" in res.output
            assert "trace " in res.output
            # request-id lookup works too (the waterfall join key)
            res2 = runner.invoke(
                cli, ["trace", client_span["attrs"]["request_id"],
                      "--url", srv.url])
            assert res2.exit_code == 0, res2.output
            assert "server.request" in res2.output

    def test_store_debug_traces_endpoint(self, clean_ring, tmp_path):
        import requests as _rq

        from kubetorch_tpu.data_store import netpool
        from kubetorch_tpu.data_store.store_server import create_store_app
        from tests.assets.threaded_server import ThreadedAiohttpServer

        with ThreadedAiohttpServer(
                lambda: create_store_app(str(tmp_path / "s"))) as store:
            with tel.span("client.op", request_id="rid-store") as sp:
                r = netpool.request("PUT", f"{store.url}/kv/obs%2Fk",
                                    data=b"hello", timeout=30)
                assert r.status_code == 200
            r = _rq.get(f"{store.url}/debug/traces",
                        params={"q": sp.trace_id}, timeout=10)
            names = {s["name"] for s in r.json()["spans"]}
            assert "store.server" in names
            srv_span = next(s for s in r.json()["spans"]
                            if s["name"] == "store.server")
            assert srv_span["attrs"]["bytes"] == 5
            # store /metrics speaks exposition with TYPE headers
            m = _rq.get(f"{store.url}/metrics", timeout=10)
            assert "# TYPE kt_store_requests_total counter" in m.text

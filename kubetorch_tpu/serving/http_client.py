"""Client-side HTTP caller for deployed services.

Reference (``serving/http_client.py``, 1132 LoC): request preparation with
serialization headers, sync/async call paths, WS log streaming filtered by
X-Request-ID, and exception rehydration that reconstructs the remote error
type on the caller's side.
"""

from __future__ import annotations

import asyncio
import atexit
import json
import os
import threading
import time
import uuid
from typing import Any, Dict, Optional

import requests as _requests

from .. import serialization as ser
from .. import telemetry
from ..config import config
from ..exceptions import ControllerRequestError, rehydrate_exception
from ..resilience import (DEADLINE_HEADER, ESTABLISHED_TRANSIENT_EXCS,
                          RETRYABLE_STATUSES, Deadline, RetryPolicy,
                          connection_never_established, http_policy,
                          retry_after_seconds)


class CustomResponse:
    """Wraps a response; raise_for_status rehydrates remote exceptions
    (reference http_client.py:87-194)."""

    def __init__(self, status: int, body: bytes, headers: Dict[str, str]):
        self.status = status
        self.body = body
        self.headers = headers

    def raise_for_status(self) -> None:
        if self.status < 400:
            return
        try:
            data = json.loads(self.body.decode())
        except (ValueError, UnicodeDecodeError):
            raise ControllerRequestError(
                f"HTTP {self.status}: {self.body[:500]!r}", status_code=self.status)
        if "error_type" in data:
            exc = rehydrate_exception(data)
            # keep the transport facts alongside the rehydrated type: the
            # HTTP status and the request id the server logs are labelled
            # with, so `except kt.PodTerminatedError as e` can actually
            # find the failing request in the pod logs
            if getattr(exc, "status_code", None) is None:
                exc.status_code = self.status  # type: ignore[attr-defined]
            rid = self.headers.get("X-Request-ID")
            if rid and getattr(exc, "request_id", None) is None:
                exc.request_id = rid  # type: ignore[attr-defined]
            raise exc
        raise ControllerRequestError(f"HTTP {self.status}: {data}",
                                     status_code=self.status)

    def result(self) -> Any:
        self.raise_for_status()
        fmt = self.headers.get("X-Serialization", ser.JSON)
        return ser.deserialize(self.body, fmt)


# Live log-stream pump threads: daemon threads die with the interpreter, so
# a one-shot script exiting right after its call would lose the trailing log
# lines the grace drain exists to deliver — the atexit hook joins them first.
_LIVE_PUMPS: list = []


def _drain_pumps_at_exit() -> None:
    grace = float(os.environ.get("KT_LOG_STREAM_GRACE", "3.0"))
    deadline = time.monotonic() + max(6.0, grace + 2.0)
    for t in list(_LIVE_PUMPS):
        t.join(max(0.0, deadline - time.monotonic()))


atexit.register(_drain_pumps_at_exit)


def _clamp_timeout(explicit: Optional[float],
                   policy_timeout: Optional[float]) -> Optional[float]:
    """Per-attempt I/O timeout: the caller's explicit value bounded by the
    policy's deadline-clamped attempt timeout (whichever is tighter)."""
    if explicit is None:
        return policy_timeout
    if policy_timeout is None:
        return explicit
    return min(explicit, policy_timeout)


def _retryable_exc(e: BaseException, idempotency_key: Optional[str]) -> bool:
    """The safe-retry rule for user calls: never-established is always
    retryable (the server can't have seen the request); established
    transport failures only when the server dedupes our idempotency key."""
    if connection_never_established(e):
        return True
    return bool(idempotency_key) and isinstance(e, ESTABLISHED_TRANSIENT_EXCS)


def _response_retry(status: int, body: bytes, resp: Any,
                    idempotency_key: Optional[str]):
    """Response verdict for RetryPolicy.run/arun: retry transient 5xx only
    under an idempotency key, honoring Retry-After; a DeadlineExceededError
    body is terminal — the budget is gone whatever we do."""
    if status not in RETRYABLE_STATUSES or not idempotency_key:
        return None
    if b"DeadlineExceededError" in body[:2048]:
        return None
    ra = retry_after_seconds(resp)
    return ra if ra is not None else True


class HTTPClient:
    """Caller for one deployed service."""

    def __init__(self, base_url: str, serialization: Optional[str] = None,
                 stream_logs: Optional[bool] = None,
                 proxy_url: Optional[str] = None,
                 service: Optional[str] = None,
                 retry: Optional[RetryPolicy] = None):
        self.base_url = base_url.rstrip("/")
        self.serialization = serialization or config().serialization
        self.stream_logs = (config().stream_logs if stream_logs is None
                            else stream_logs)
        # Controller-proxy fallback: a scaled-to-zero service has no pod
        # listening at base_url; the proxy cold-starts it (the Knative
        # activator role) and forwards the held request.
        self.proxy_url = proxy_url.rstrip("/") if proxy_url else None
        self.service = service       # labels resource-scope PromQL queries
        self._resource_scope_dead = False   # controller said: no stack
        self._resource_scope_fails = 0      # consecutive-failure backoff
        self._session = _requests.Session()
        self.retry = retry           # per-client default; None → http_policy()
        self.last_retry_delays: list = []   # backoff actually slept (tests)
        self._aio_session = None
        self._aio_loop = None

    # -- calls ----------------------------------------------------------------

    def call_method(self, fn_name: str, method: Optional[str] = None,
                    args: tuple = (), kwargs: Optional[dict] = None,
                    workers=None, timeout: Optional[float] = None,
                    debugger=None,
                    stream_logs: Optional[bool] = None,
                    metrics=None, logging=None,
                    idempotency_key: Optional[str] = None,
                    deadline: Optional[float] = None,
                    retry: Optional[RetryPolicy] = None) -> Any:
        """``debugger``/``metrics``/``logging`` accept the typed config
        objects (``kt.DebugConfig`` / ``kt.MetricsConfig`` /
        ``kt.LoggingConfig``, reference globals.py:40-127) or plain dicts
        with the same fields.

        Resilience (see :mod:`kubetorch_tpu.resilience`): a connection that
        was never established is always retried (the request can't have
        executed); anything after the connection was established — resets,
        timeouts, 5xx — is retried ONLY when ``idempotency_key`` is given,
        because the server dedupes that key and a retry can never run the
        function twice. ``deadline`` (seconds) rides ``X-KT-Deadline`` so
        the pod refuses work the client has already abandoned."""
        from ..config import LoggingConfig, MetricsConfig
        if isinstance(metrics, dict):
            metrics = MetricsConfig(**metrics)
        if isinstance(logging, dict):
            logging = LoggingConfig(**logging)
        if logging is not None and stream_logs is None:
            stream_logs = logging.stream_logs
        if hasattr(debugger, "to_dict"):
            debugger = debugger.to_dict()
        body: Dict[str, Any] = {"args": list(args), "kwargs": kwargs or {}}
        if workers is not None:
            body["_kt_workers"] = workers
        if debugger:
            debugger = dict(debugger)
            if "token" not in debugger:
                # one-shot session token: the pod-side breakpoint refuses
                # connections that don't present it
                debugger["token"] = uuid.uuid4().hex[:16]
                print(f"[debug] breakpoint armed — attach with: kt debug "
                      f"<service> --port {debugger.get('port', 5678)} "
                      f"--token {debugger['token']}", flush=True)
            body["debugger"] = debugger
        request_id = uuid.uuid4().hex[:16]
        url = f"{self.base_url}/{fn_name}" + (f"/{method}" if method else "")

        stop_streaming = None
        stop_metrics = None
        if (self.stream_logs if stream_logs is None else stream_logs):
            stop_streaming = self._start_log_stream(
                request_id,
                include_name=(logging.include_name if logging else True),
                grace=(logging.grace_period if logging else None))
        if metrics is not None or config().stream_metrics:
            stop_metrics = self._start_metric_stream(
                interval=(metrics.interval if metrics else None),
                scope=(metrics.scope if metrics else "pod"))
        try:
            data = ser.serialize(body, self.serialization)
            headers = {"X-Serialization": self.serialization,
                       "X-Request-ID": request_id}
            policy = retry or self.retry or http_policy()
            dl = None
            if deadline is not None:
                dl = Deadline.after(deadline)
            elif policy.deadline is not None:
                dl = Deadline.after(policy.deadline)
            if dl is not None:
                headers[DEADLINE_HEADER] = dl.header_value()
            if idempotency_key:
                headers["X-KT-Idempotency-Key"] = idempotency_key

            # the client-side root of the request's trace: the span context
            # rides X-KT-Trace so the pod server (and everything behind it)
            # parents onto it, and the retry loop's attempt/backoff events
            # land on it (resilience.py emits into the active span)
            client_span = telemetry.span(
                "client.call", fn=fn_name, method=method or "",
                request_id=request_id, url=self.base_url)

            def _attempt(info):
                t = _clamp_timeout(timeout, info.timeout)
                try:
                    return self._session.post(url, data=data,
                                              headers=headers, timeout=t)
                except _requests.exceptions.ConnectionError as e:
                    # Fall back ONLY when the connection was never
                    # established (scaled to zero / pod churn): the proxy
                    # cold-starts the service and holds the request until a
                    # pod is ready. A reset MID-request must not re-POST —
                    # the call may already be executing on the pod, and
                    # running it twice is worse than surfacing the error.
                    if (self.proxy_url is None
                            or not connection_never_established(e)):
                        raise
                    return self._session.post(
                        f"{self.proxy_url}/{fn_name}" +
                        (f"/{method}" if method else ""),
                        data=data, headers=headers, timeout=t)

            self.last_retry_delays = []
            with client_span as sp:
                telemetry.inject(headers)
                resp = policy.run(
                    _attempt,
                    retryable_exc=lambda e: _retryable_exc(e, idempotency_key),
                    response_retry_delay=lambda r: _response_retry(
                        r.status_code, r.content, r, idempotency_key),
                    deadline=dl,
                    record=self.last_retry_delays)
                sp.set_attr("status", resp.status_code)
                telemetry.apply_timing(
                    sp, resp.headers.get(telemetry.TIMING_HEADER))
        finally:
            if stop_streaming:
                stop_streaming()
            if stop_metrics:
                stop_metrics()
        return CustomResponse(resp.status_code, resp.content,
                              dict(resp.headers)).result()

    def _async_session(self):
        """One shared ``aiohttp.ClientSession`` per client per event loop
        (connection keep-alive parity with the sync path's Session). A
        session from a finished loop can't be awaited closed — it is
        abandoned and replaced."""
        import aiohttp

        loop = asyncio.get_running_loop()
        if (self._aio_session is None or self._aio_session.closed
                or self._aio_loop is not loop):
            self._aio_session = aiohttp.ClientSession()
            self._aio_loop = loop
        return self._aio_session

    async def aclose(self) -> None:
        if self._aio_session is not None and not self._aio_session.closed \
                and self._aio_loop is asyncio.get_running_loop():
            await self._aio_session.close()
        self._aio_session = None
        self._aio_loop = None

    async def call_method_async(self, fn_name: str, method: Optional[str] = None,
                                args: tuple = (), kwargs: Optional[dict] = None,
                                workers=None, timeout: Optional[float] = None,
                                idempotency_key: Optional[str] = None,
                                deadline: Optional[float] = None,
                                retry: Optional[RetryPolicy] = None) -> Any:
        """Async twin of :meth:`call_method`: same shared-session reuse,
        same scaled-to-zero proxy fallback, and the same
        never-re-POST-after-established rule (retries past an established
        connection require ``idempotency_key``)."""
        import aiohttp

        body: Dict[str, Any] = {"args": list(args), "kwargs": kwargs or {}}
        if workers is not None:
            body["_kt_workers"] = workers
        url = f"{self.base_url}/{fn_name}" + (f"/{method}" if method else "")
        data = ser.serialize(body, self.serialization)
        request_id = uuid.uuid4().hex[:16]
        headers = {"X-Serialization": self.serialization,
                   "X-Request-ID": request_id}
        policy = retry or self.retry or http_policy()
        dl = None
        if deadline is not None:
            dl = Deadline.after(deadline)
        elif policy.deadline is not None:
            dl = Deadline.after(policy.deadline)
        if dl is not None:
            headers[DEADLINE_HEADER] = dl.header_value()
        if idempotency_key:
            headers["X-KT-Idempotency-Key"] = idempotency_key
        sess = self._async_session()

        async def _read(resp) -> CustomResponse:
            return CustomResponse(resp.status, await resp.read(),
                                  dict(resp.headers))

        async def _attempt(info) -> CustomResponse:
            t = aiohttp.ClientTimeout(total=_clamp_timeout(timeout,
                                                           info.timeout))
            try:
                async with sess.post(url, data=data, headers=headers,
                                     timeout=t) as resp:
                    return await _read(resp)
            except aiohttp.ClientConnectorError:
                # connector errors = never established → the proxy fallback
                # (and retry) are safe, exactly like the sync path
                if self.proxy_url is None:
                    raise
                async with sess.post(
                        f"{self.proxy_url}/{fn_name}" +
                        (f"/{method}" if method else ""),
                        data=data, headers=headers, timeout=t) as resp:
                    return await _read(resp)

        def _aio_retryable(e: BaseException) -> bool:
            if isinstance(e, aiohttp.ClientConnectorError):
                return True          # never established
            return bool(idempotency_key) and isinstance(
                e, (aiohttp.ServerDisconnectedError,
                    aiohttp.ClientPayloadError, aiohttp.ClientOSError,
                    asyncio.TimeoutError))

        self.last_retry_delays = []
        with telemetry.span("client.call", fn=fn_name, method=method or "",
                            request_id=request_id, url=self.base_url) as sp:
            telemetry.inject(headers)
            cr = await policy.arun(
                _attempt,
                retryable_exc=_aio_retryable,
                response_retry_delay=lambda r: _response_retry(
                    r.status, r.body, r, idempotency_key),
                deadline=dl,
                record=self.last_retry_delays)
            sp.set_attr("status", cr.status)
            telemetry.apply_timing(
                sp, cr.headers.get(telemetry.TIMING_HEADER))
        return cr.result()

    # -- health ---------------------------------------------------------------

    def ready_body(self, launch_id: Optional[str] = None,
                   timeout: float = 2.0,
                   wait: float = 0.0) -> Optional[Dict[str, Any]]:
        """The pod's ``/ready`` answer once it is ready, else None. Its
        ``boot`` holds the launch's boot phases in seconds and
        ``ready_for_s``, how long the service had been ready when this
        request was answered. ``wait`` lets the pod hold the request that
        long while its answer is "not yet" (``timeout`` is then the room to
        connect, and to answer on top of ``wait``); a pod that does not
        hold answers at once, as without it."""
        try:
            params = {"launch_id": launch_id} if launch_id else {}
            if wait > 0:
                params["wait"] = str(wait)
            r = self._session.get(f"{self.base_url}/ready", params=params,
                                  timeout=(timeout, wait + timeout))
            if r.status_code != 200:
                return None
            try:
                body = r.json()
            except ValueError:
                body = None
            return body if isinstance(body, dict) else {"ready": True}
        except _requests.RequestException:
            return None

    def is_ready(self, launch_id: Optional[str] = None,
                 timeout: float = 2.0) -> bool:
        return self.ready_body(launch_id, timeout) is not None

    # -- metric streaming -----------------------------------------------------

    @staticmethod
    def _format_metrics(text: str) -> str:
        """Compact one-liner from a pod's /metrics exposition: summed HBM
        across devices, in-flight count, request counter."""
        hbm_use = hbm_lim = 0.0
        inflight = reqs = None
        for ln in text.splitlines():
            if not ln.startswith(("kt_", "kubetorch_")):
                continue
            try:
                name, val = ln.rsplit(" ", 1)
                v = float(val)
            except ValueError:
                continue
            if name.startswith("kt_tpu_hbm_bytes_in_use"):
                hbm_use += v
            elif name.startswith("kt_tpu_hbm_bytes_limit"):
                hbm_lim += v
            elif name == "kt_inflight_requests":
                inflight = int(v)
            elif name == "kt_http_requests_total":
                reqs = int(v)
        parts = []
        if hbm_lim:
            parts.append(f"hbm={hbm_use / 2**30:.2f}/{hbm_lim / 2**30:.2f}GiB"
                         f" ({100 * hbm_use / hbm_lim:.0f}%)")
        if inflight is not None:
            parts.append(f"inflight={inflight}")
        if reqs is not None:
            parts.append(f"reqs={reqs}")
        return "  ".join(parts)

    def _resource_scope_line(self) -> Optional[str]:
        """Service-aggregate gauges via PromQL through the controller
        (reference ``scope="resource"`` queries, http_client.py:758-795).
        Needs deploy/metrics.yaml; any failure returns None and the pump
        falls back to pod scope."""
        api = config().api_url
        if not api or not self.service:
            return None
        parts = []
        queries = {
            "hbm_used": f'sum(kt_tpu_hbm_bytes_in_use{{service="{self.service}"}})',
            "inflight": f'sum(kt_inflight_requests{{service="{self.service}"}})',
        }
        for label, q in queries.items():
            try:
                r = _requests.get(f"{api}/controller/metrics/query",
                                  params={"query": q}, timeout=5)
                if r.status_code == 503:
                    # Latch ONLY the controller's own "no metrics stack
                    # configured" sentinel (dedicated header; body match for
                    # older controllers). The query route relays upstream
                    # status codes, so a 503 from a transiently-overloaded
                    # Prometheus must stay retryable — latching it would
                    # disable resource-scope metrics for the client's
                    # lifetime over a blip.
                    if (r.headers.get("X-KT-Unconfigured") == "metrics"
                            or "no metrics stack configured"
                            in r.text[:200]):
                        self._resource_scope_dead = True
                    return None
                results = r.json().get("data", {}).get("result", [])
                if r.status_code == 200 and results:
                    val = float(results[0]["value"][1])
                    parts.append(
                        f"{label}={val / 2**30:.2f}GiB"
                        if label.startswith("hbm") else
                        f"{label}={val:.0f}")
            except (_requests.RequestException, ValueError, KeyError,
                    IndexError):
                return None
        return "  ".join(parts) if parts else None

    def _start_metric_stream(self, interval: Optional[float] = None,
                             scope: str = "pod"):
        """Poll metrics during a call and echo compact lines alongside the
        streamed logs (reference streams DCGM GPU util via PromQL,
        ``http_client.py:758-795``). ``scope="pod"``: the service's own
        /metrics (TPU HBM gauges), via the controller proxy when the pod
        isn't directly reachable. ``scope="resource"``: PromQL aggregates
        across the service's pods, degrading to pod scope when no metrics
        stack answers."""
        stop = threading.Event()
        if interval is None:
            interval = float(os.environ.get("KT_METRIC_STREAM_INTERVAL", "3"))

        def pump():
            # module-level requests, NOT self._session: Session isn't
            # thread-safe and the main thread's POST is in flight
            tick = 0
            while not stop.wait(interval):
                tick += 1
                if scope == "resource" and not self._resource_scope_dead:
                    # exponential backoff on consecutive failures: a fresh
                    # deploy's not-yet-scraped window recovers (unlike a
                    # permanent latch), but a dead/stale controller can't
                    # charge every tick two 5s query timeouts. The explicit
                    # "no stack configured" 503 still latches immediately
                    # (inside _resource_scope_line).
                    if self._resource_scope_fails and (
                            tick % min(2 ** self._resource_scope_fails, 32)):
                        pass
                    else:
                        line = self._resource_scope_line()
                        if line:
                            self._resource_scope_fails = 0
                            print(f"[metrics] {line}", flush=True)
                            continue
                        self._resource_scope_fails += 1
                for url in (self.base_url, self.proxy_url):
                    if not url:
                        continue
                    try:
                        r = _requests.get(f"{url}/metrics", timeout=3)
                    except _requests.RequestException:
                        continue
                    if r.status_code != 200:
                        continue
                    line = self._format_metrics(r.text)
                    if line:
                        print(f"[metrics] {line}", flush=True)
                    break

        threading.Thread(target=pump, daemon=True).start()
        return stop.set

    # -- log streaming --------------------------------------------------------

    def _start_log_stream(self, request_id: str, include_name: bool = True,
                          grace: Optional[float] = None):
        """Poll the controller's log buffer for this request's lines and echo
        them locally (reference streams from Loki over WS; our controller
        exposes the same data over HTTP long-poll)."""
        api = config().api_url
        if not api:
            return None
        stop = threading.Event()
        # Keep draining after the call returns: the pod batches log pushes
        # (~1s) and the controller ingest adds latency, so the lines printed
        # at the end of a request land AFTER its response (the reference's
        # LoggingConfig grace-period behavior, globals.py:61-102).
        if grace is None:
            grace = float(os.environ.get("KT_LOG_STREAM_GRACE", "3.0"))

        def pump():
            seen = 0
            stopped_at = None
            while True:
                if stop.is_set() and stopped_at is None:
                    stopped_at = time.monotonic()
                got = 0
                try:
                    r = _requests.get(
                        f"{api}/controller/logs",
                        params={"request_id": request_id, "offset": seen},
                        timeout=5)
                    if r.status_code == 200:
                        data = r.json()
                        for entry in data.get("entries", []):
                            tag = (entry.get("pod") or "remote"
                                   if include_name else "remote")
                            print(f"[{tag}] {entry['line']}")
                            got += 1
                        seen = data.get("offset", seen)
                except _requests.RequestException:
                    pass
                if stopped_at is not None:
                    elapsed = time.monotonic() - stopped_at
                    # drain until quiet: once the pod's ~1s flush interval has
                    # passed and a fetch comes back empty, everything the
                    # request produced has been echoed; grace bounds it
                    if elapsed >= grace or (got == 0 and elapsed >= 1.25):
                        return
                    time.sleep(0.25)    # Event.wait would return instantly now
                else:
                    stop.wait(0.5)

        def run_pump():
            try:
                pump()
            finally:
                try:
                    _LIVE_PUMPS.remove(t)
                except ValueError:
                    pass

        t = threading.Thread(target=run_pump, daemon=True)
        _LIVE_PUMPS.append(t)
        t.start()

        def stopper():
            # no join here: that would charge every streamed call the ~1.25s
            # quiet-drain minimum. The pump drains in the background; the
            # atexit hook below joins survivors so a one-shot script still
            # sees the trailing lines (batched ~1s in the pod) before exit.
            stop.set()

        return stopper

"""The in-pod HTTP server (aiohttp).

Re-design of the reference pod runtime (``serving/http_server.py``, 1971 LoC,
FastAPI/uvicorn — neither exists in this image, and aiohttp's single-loop
model suits the fan-out design anyway). Feature parity map:

- pod identity from env/hostname (reference :146-204)
- metadata application → env contract (reference :254)
- callable/supervisor loading, config-hash keyed, lock-guarded (:878-1134)
- ``TerminationCheckMiddleware`` racing requests vs SIGTERM, with typed
  ``PodTerminatedError`` carrying OOMKilled/Evicted/**TPU-preemption** reasons
  (:1184-1235 + serving/utils.py:111-191)
- ``X-Request-ID`` propagation (:1237-1249)
- routes: /health, /ready?launch_id, /metrics, /app/status,
  POST /{fn}[/{method}] (:1645-1946)
- serialization negotiation via ``X-Serialization`` with server-side
  allowlist (:1768-1891)
- exception packaging (:1478-1530)
- hot reload: re-apply metadata → re-sync code → recreate supervisor → new
  launch_id, no process restart (:352-410)

Run: ``python -m kubetorch_tpu.serving.http_server --port 32300``.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import hashlib
import json
import os
import re
import signal
import socket
import sys
import time
import uuid
from typing import Any, Dict, Optional, Tuple

from aiohttp import web

from .. import serialization as ser
from .. import telemetry
from ..exceptions import (AdmissionShedError, DeadlineExceededError,
                          KubetorchError, PodTerminatedError,
                          SerializationError, WorkerDiedError,
                          package_exception)
from ..resilience import DEADLINE_HEADER, Deadline, IdempotencyCache
from ..parallel.mesh import DistributedConfig
from ..resources.pointers import Pointers
from .env_contract import (KT_ALLOWED_SERIALIZATION, KT_CALLABLE_TYPE,
                           KT_CLS_OR_FN_NAME, KT_DISTRIBUTED_CONFIG,
                           KT_FILE_PATH, KT_INIT_ARGS, KT_LAUNCH_ID,
                           KT_MODULE_NAME, KT_NAMESPACE, KT_PROJECT_ROOT,
                           KT_SERVICE_NAME, apply_metadata)
from .process_pool import RANK_BOOT_DETAIL, RANK_BOOT_PHASES
from .supervisor_factory import supervisor_for

from ..constants import READY_WAIT_CAP_S, server_port
request_id_var: contextvars.ContextVar[str] = contextvars.ContextVar(
    "kt_request_id", default="")

# phases of a launch's boot, as /ready reports them (ServerState.boot_body)
BOOT_PHASES = ("pod_boot_s", "pool_spawn_s") + RANK_BOOT_PHASES

RESERVED_ROUTES = {"health", "ready", "metrics", "app", "_kt", "debug"}

# how often a held /ready (``wait=``, at most READY_WAIT_CAP_S) looks
# again: a launch that turns ready is answered within this
READY_RECHECK_S = 0.025

# probes and the observability surface itself are never spanned: a 3s
# scrape cadence would churn the whole trace ring in minutes (they still
# get X-Request-ID — the header contract covers every response)
TRACE_EXEMPT_PATHS = ("/health", "/ready", "/metrics", "/debug/traces")


class ServerState:
    """All mutable pod-runtime state, attachable to a fresh app per test."""

    def __init__(self):
        self.pod_name = os.environ.get("POD_NAME", socket.gethostname())
        self.namespace = os.environ.get(KT_NAMESPACE, "default")
        self.launch_id: Optional[str] = os.environ.get(KT_LAUNCH_ID)
        self.termination = asyncio.Event()
        self.termination_reason: Optional[str] = None
        self.supervisor = None
        # config key of the supervisor being (or last) built — set when its
        # build STARTS, so a same-launch reload can see it mid-warm-up
        self._supervisor_key: Optional[str] = None
        self._prewarm_task: Optional[asyncio.Task] = None
        self._prewarm_error: Optional[str] = None
        self._load_lock = asyncio.Lock()
        self.started_at = time.time()
        # boot record of the current launch (ISSUE 26), durations only:
        # pod_boot_s (this process's start to its server listening) and
        # pool_spawn_s (the launch received, or the server listening if
        # that came later, to the rank pool spawned); the ranks' own
        # phases live on the pool. /ready hands them to the deploying
        # client.
        self.boot: Dict[str, float] = {}
        self._launch_mono = time.monotonic()
        # the launch whose boot kt_cold_start_* last observed (none yet:
        # a pod may run with no launch id at all)
        self._cold_start_noted: Any = object()
        self.request_count = 0
        self.inflight = 0          # concurrency signal for the autoscaler
        self.last_activity = time.time()
        self.log_capture = None
        self.metrics_pusher = None
        self.controller_ws = None
        self.app_process = None
        self.blobd_proc = None
        # retried-POST dedupe (see resilience.IdempotencyCache): a client
        # that retries with X-KT-Idempotency-Key must never execute twice
        self.idempotency = IdempotencyCache(
            ttl_s=float(os.environ.get("KT_IDEMPOTENCY_TTL_S", "600")),
            max_entries=int(os.environ.get("KT_IDEMPOTENCY_MAX", "1024")))

    # -- metadata / supervisor ------------------------------------------------

    def allowed_serialization(self):
        raw = os.environ.get(KT_ALLOWED_SERIALIZATION)
        if raw:
            return [s.strip() for s in raw.split(",") if s.strip()]
        return list(ser.DEFAULT_ALLOWED)

    def pointers(self) -> Optional[Pointers]:
        if not os.environ.get(KT_CLS_OR_FN_NAME):
            return None
        return Pointers(
            project_root=os.environ.get(KT_PROJECT_ROOT, os.getcwd()),
            module_name=os.environ.get(KT_MODULE_NAME, ""),
            file_path=os.environ.get(KT_FILE_PATH, ""),
            cls_or_fn_name=os.environ[KT_CLS_OR_FN_NAME],
        )

    def distributed_config(self) -> Optional[DistributedConfig]:
        raw = os.environ.get(KT_DISTRIBUTED_CONFIG)
        if not raw:
            return None
        return DistributedConfig.from_dict(json.loads(raw))

    def init_args(self) -> Optional[Dict]:
        raw = os.environ.get(KT_INIT_ARGS)
        return json.loads(raw) if raw else None

    def _config_key(self) -> str:
        blob = json.dumps({
            "ptr": os.environ.get(KT_CLS_OR_FN_NAME),
            "mod": os.environ.get(KT_MODULE_NAME),
            "dist": os.environ.get(KT_DISTRIBUTED_CONFIG),
            "init": os.environ.get(KT_INIT_ARGS),
        }, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    async def get_supervisor(self):
        """Config-hash-keyed supervisor (reference load_supervisor :971)."""
        if (self.supervisor is not None
                and self._config_key() == self._supervisor_key):
            return self.supervisor
        async with self._load_lock:
            # recompute INSIDE the lock: a reload may have changed the env
            # while we waited, and building from new env under a stale key
            # would force an immediate tear-down/rebuild of warming workers
            key = self._config_key()
            if self.supervisor is not None and key == self._supervisor_key:
                return self.supervisor
            if self.supervisor is not None:
                await asyncio.to_thread(self.supervisor.cleanup)
                self.supervisor = None
            self._supervisor_key = key
            pointers = self.pointers()
            if pointers is None:
                raise KubetorchError(
                    "No callable configured on this pod (missing metadata)")
            sup = supervisor_for(
                self.distributed_config(), pointers, self.init_args(),
                service_name=os.environ.get(KT_SERVICE_NAME, ""),
                namespace=self.namespace,
                server_port=server_port(),
                fn_name=pointers.cls_or_fn_name,
            )
            await asyncio.to_thread(sup.setup)
            self.boot["pool_spawn_s"] = time.monotonic() - self._launch_mono
            self.supervisor = sup
            return sup

    def note_listening(self) -> None:
        """The server accepts connections from now on: closes ``pod_boot_s``
        (from this process's start, by the kernel's record of it) and
        starts the clock of the launch this pod was booted for."""
        import psutil
        self.boot["pod_boot_s"] = max(
            0.0, time.time() - psutil.Process().create_time())
        self._launch_mono = time.monotonic()

    def boot_body(self) -> Dict[str, float]:
        """``boot`` of a ready ``/ready``: every phase of the launch in
        seconds (0.0 where it did not happen in this launch), the slowest
        rank's ``rank_*_s`` and what they hold (``RANK_BOOT_DETAIL``), and
        ``ready_for_s``: how long the service has been ready, so the
        polling client can tell how late it noticed. The first ready body
        of a launch also records it as a cold start (:meth:`_note_cold_start`)."""
        rec = {}
        pool = getattr(self.supervisor, "pool", None)
        if pool is not None and hasattr(pool, "boot_record"):
            rec = pool.boot_record()
        ready_mono = rec.pop("ready_mono", None)
        out = {k: 0.0 for k in BOOT_PHASES + RANK_BOOT_DETAIL}
        out.update(self.boot)
        out.update(rec)
        if ready_mono is not None:
            self._note_cold_start(out, ready_mono)
        out["ready_for_s"] = (0.0 if ready_mono is None
                              else max(0.0, time.monotonic() - ready_mono))
        return {k: round(v, 6) for k, v in out.items()}

    def _note_cold_start(self, boot: Dict[str, float],
                         ready_mono: float) -> None:
        """The launch's boot, once, into the replica cold-start family
        (``kt_cold_start_*``, ISSUE 16), which the controller's fast-scale
        gate reads: a plain deploy is a measured cold start too. Phases:
        ``import`` (jax and the target's module), ``compile_or_cache``
        (``rank_jit_s``), ``engine_init`` (constructor and warm-up); the
        total runs from this process's start, or the launch if it came to
        a pod that was up, to the last rank's readiness."""
        if self._cold_start_noted == self.launch_id:
            return
        self._cold_start_noted = self.launch_id
        fam = telemetry.cold_start_metrics()
        for phase, seconds in (
                ("import", boot["rank_jax_import_s"] + boot["rank_import_s"]),
                ("compile_or_cache", boot["rank_jit_s"]),
                ("engine_init", boot["rank_init_s"] + boot["rank_warmup_s"])):
            fam["phase_seconds"].observe(seconds, phase=phase)
        fam["total"].set(boot["pod_boot_s"]
                         + max(0.0, ready_mono - self._launch_mono))
        fam["boot_ts"].set(time.time() - (time.monotonic() - ready_mono))

    async def reload(self, metadata: Dict[str, Any], launch_id: str) -> None:
        """Hot reload (reference _handle_reload :352): metadata → code sync →
        supervisor recreation → only then flip the launch_id."""
        apply_metadata(metadata)
        changed = await self._sync_code()
        # replay changed dockerfile instructions (reference run_image_setup)
        dockerfile = os.environ.get("KT_DOCKERFILE") or metadata.get("KT_DOCKERFILE")
        if dockerfile:
            from .image_setup import run_image_setup
            changed += (await run_image_setup(dockerfile,
                                              state=self))["effects"]
        if os.environ.get("KT_APP_CMD") and not dockerfile:
            from .image_setup import start_app_process
            await start_app_process(self, os.environ["KT_APP_CMD"])
            changed += 1
        async with self._load_lock:
            loading = self.supervisor is not None or (
                self._prewarm_task is not None
                and not self._prewarm_task.done())
            if (loading and not changed and launch_id == self.launch_id
                    and self._config_key() == self._supervisor_key):
                # a pod booted BY this launch (its env carried the launch id
                # and metadata) is already loading exactly this: tearing its
                # rank pool down now would only wait out the warm-up and then
                # load the whole model a second time (seen on the chip: every
                # fresh deploy initialized and compiled twice)
                return
            if self.supervisor is not None:
                await asyncio.to_thread(self.supervisor.cleanup)
                self.supervisor = None
            self._supervisor_key = None
            # purge the user's modules under the same lock so a queued call
            # can't rebuild a supervisor from the stale module cache. Never
            # purge the runtime itself or __main__ (mp spawn needs it, and
            # the user's project root may contain this package).
            root = os.environ.get(KT_PROJECT_ROOT)
            if root:
                for name, mod in list(sys.modules.items()):
                    if name == "__main__" or name.split(".")[0] == "kubetorch_tpu":
                        continue
                    f = getattr(mod, "__file__", None)
                    if f and f.startswith(root) and "site-packages" not in f:
                        sys.modules.pop(name, None)
            self.launch_id = launch_id
            os.environ[KT_LAUNCH_ID] = launch_id
            # a launch onto a pod that was already up: its boot starts here
            self.boot = {}
            self._launch_mono = time.monotonic()
        # open the load+warmup window NOW (readiness gates on it) instead of
        # on the first request — otherwise the warmup hook defers to exactly
        # the request it was supposed to pre-pay
        self.prewarm_supervisor()

    def prewarm_supervisor(self) -> None:
        """Fire-and-forget supervisor creation so rank workers start their
        eager load + ``__kt_warmup__`` immediately and ``/ready`` can observe
        the warming window. A failure is recorded for ``/ready`` (a pod that
        cannot build its supervisor must not join the endpoint pool) and the
        same error resurfaces, typed, on the first direct call — which also
        retries the build."""
        # a new config supersedes any previous prewarm outcome — a stale
        # error must not keep /ready at 503 for a config it doesn't describe
        self._prewarm_error = None
        if self.pointers() is None:
            # drop a finished task's handle; an in-flight one stays tracked
            # so cleanup still awaits it
            if self._prewarm_task is not None and self._prewarm_task.done():
                self._prewarm_task = None
            return

        async def _go():
            try:
                await self.get_supervisor()
                self._prewarm_error = None
            except Exception as e:  # noqa: BLE001
                self._prewarm_error = f"{type(e).__name__}: {e}"
                print(f"[kt] supervisor prewarm failed (will retry on first "
                      f"call): {e}")

        self._prewarm_task = asyncio.create_task(_go())

    async def _sync_code(self) -> int:
        """Pull latest code from the data store (reference rsync pull :1140);
        returns how many files changed.

        No code tree in the store + a locally-present project root means the
        client shares our filesystem (local backend) and never pushed —
        nothing to sync. A missing tree with a missing root is a real error.
        """
        store_url = os.environ.get("KT_DATA_STORE_URL")
        service = os.environ.get(KT_SERVICE_NAME)
        root = os.environ.get(KT_PROJECT_ROOT)
        if not (store_url and service and root):
            return 0
        from ..data_store.sync import pull_tree
        from ..exceptions import SyncError
        try:
            stats = await asyncio.to_thread(pull_tree, store_url,
                                            f"__code__/{service}", root)
            return stats["fetched"] + stats["deleted"]
        except SyncError as e:
            if "No tree" in str(e) and os.path.isdir(root):
                return 0
            raise

    def terminate(self, reason: str) -> None:
        self.termination_reason = reason
        self.termination.set()
        # the watchdog classifies a rank's SIGTERM during this drain window
        # as Evicted/Preempted rather than an anonymous kill
        from .watchdog import set_draining
        set_draining(reason)


# ---------------------------------------------------------------------------
# Middleware
# ---------------------------------------------------------------------------


@web.middleware
async def request_id_middleware(request: web.Request, handler):
    """Outermost middleware: request-id binding + the server span.

    Every response — success, middleware short-circuit (504 deadline
    rejection, 503 recovering/terminating, idempotent replay), and
    ``HTTPException`` raises — carries ``X-Request-ID`` back, so a client
    holding only the id can always find the failing request in logs and
    traces. The span continues the client's ``X-KT-Trace`` context when
    present (its id is echoed in ``X-KT-Trace-Id``); chaos, deadline, and
    idempotency middlewares all run inside it, so injected faults and
    rejections land on the request's own span."""
    rid = request.headers.get("X-Request-ID") or uuid.uuid4().hex[:16]
    request_id_var.set(rid)
    request["kt_request_id"] = rid
    if request.path.startswith(TRACE_EXEMPT_PATHS):
        sp = telemetry.NOOP_SPAN
    else:
        sp = telemetry.span("server.request",
                            parent=telemetry.extract(request.headers),
                            request_id=rid, path=request.path,
                            method=request.method)
    with sp:
        try:
            resp = await handler(request)
        except web.HTTPException as e:
            # aiohttp exception-responses bypass the normal return path —
            # they must not lose the id
            e.headers["X-Request-ID"] = rid
            sp.set_attr("status", e.status)
            raise
        resp.headers["X-Request-ID"] = rid
        if sp:
            sp.set_attr("status", resp.status)
            resp.headers.setdefault("X-KT-Trace-Id", sp.trace_id)
    return resp


@web.middleware
async def deadline_middleware(request: web.Request, handler):
    """Enforce the client's propagated deadline (``X-KT-Deadline``, absolute
    unix seconds) before AND during dispatch: a request that arrives past
    its deadline — or runs past it — gets a rehydratable
    ``DeadlineExceededError`` instead of burning a TPU slot on work the
    client already abandoned."""
    deadline = Deadline.from_header(request.headers.get(DEADLINE_HEADER))
    if deadline is None:
        return await handler(request)
    if deadline.expired():
        return _error_response(DeadlineExceededError(
            f"request arrived {-deadline.remaining():.3f}s past its "
            f"deadline; not dispatched", deadline=deadline.at), status=504)
    try:
        return await asyncio.wait_for(handler(request),
                                      timeout=deadline.remaining())
    except asyncio.TimeoutError:
        return _error_response(DeadlineExceededError(
            "deadline expired during dispatch; handler cancelled",
            deadline=deadline.at), status=504)


@web.middleware
async def idempotency_middleware(request: web.Request, handler):
    """Dedupe retried POSTs carrying ``X-KT-Idempotency-Key``: the first
    execution's response is recorded in a TTL cache and replayed for any
    retry of the same key, so a client-side retry never runs the user
    function twice. Concurrent duplicates await the original execution
    instead of racing it."""
    key = request.headers.get("X-KT-Idempotency-Key")
    if not key or request.method != "POST":
        return await handler(request)
    state: ServerState = request.app["state"]
    cache = state.idempotency
    entry = cache.lookup(key)
    if entry is None and key in cache.inflight:
        try:
            entry = await asyncio.shield(cache.inflight[key])
        except Exception:
            entry = None            # original died; fall through and execute
    if entry is not None:
        status, body, headers = entry
        return web.Response(status=status, body=body,
                            headers={**headers,
                                     "X-KT-Idempotent-Replay": "1"})
    fut = asyncio.get_running_loop().create_future()
    cache.inflight[key] = fut
    try:
        resp = await handler(request)
        body = resp.body if isinstance(getattr(resp, "body", None), bytes) \
            else None
        if body is not None:
            headers = {k: resp.headers[k]
                       for k in ("Content-Type", "X-Serialization")
                       if k in resp.headers}
            entry = (resp.status, body, headers)
            cache.store(key, entry)
            fut.set_result(entry)
        else:
            # streaming/file response: not replayable — drop the claim so a
            # retry re-executes rather than hanging on a never-set future
            fut.set_exception(KubetorchError("response not replayable"))
        return resp
    except BaseException as e:
        if not fut.done():
            fut.set_exception(
                KubetorchError(f"original execution failed: {e}"))
        raise
    finally:
        cache.inflight.pop(key, None)
        # a consumed exception on an unawaited future is expected noise
        if fut.done() and fut.exception() is not None:
            fut.exception()


@web.middleware
async def termination_middleware(request: web.Request, handler):
    """Race the handler against pod termination (reference :1184-1235)."""
    state: ServerState = request.app["state"]
    if state.termination.is_set():
        return _error_response(PodTerminatedError(
            "Pod is terminating", reason=state.termination_reason,
            pod_name=state.pod_name), status=503)
    handler_task = asyncio.ensure_future(handler(request))
    term_task = asyncio.ensure_future(state.termination.wait())
    try:
        done, _ = await asyncio.wait({handler_task, term_task},
                                     return_when=asyncio.FIRST_COMPLETED)
        if handler_task in done:
            return handler_task.result()
        handler_task.cancel()
        return _error_response(PodTerminatedError(
            "Pod was terminated while handling the request",
            reason=state.termination_reason, pod_name=state.pod_name),
            status=503)
    finally:
        term_task.cancel()


def _error_response(exc: BaseException, status: int = 500) -> web.Response:
    return web.json_response(package_exception(exc), status=status)


# ---------------------------------------------------------------------------
# Routes
# ---------------------------------------------------------------------------


async def health(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    sup = state.supervisor
    body = {
        "status": "ok",
        "pod": state.pod_name,
        "launch_id": state.launch_id,
        "uptime_s": round(time.time() - state.started_at, 1),
        "supervisor_healthy": bool(sup and sup.healthy),
    }
    # watchdog restart state (ISSUE 3): deaths, budget remaining, whether
    # the pool is mid-respawn or permanently failed — the operator's view
    # of worker-level self-healing
    if sup is not None and hasattr(sup, "restart_state"):
        try:
            body["workers"] = sup.restart_state()
        except Exception:  # noqa: BLE001 — health must never 500 over this
            pass
    # serving front door (ISSUE 9): admission/affinity/batching accounting
    # for load_balanced services — the operator's `kt serve status` source
    if sup is not None and hasattr(sup, "router_state"):
        try:
            body["router"] = sup.router_state()
        except Exception:  # noqa: BLE001 — health must never 500 over this
            pass
    # elastic pipeline parallelism (ISSUE 17): stage membership epoch,
    # bubble fraction, and recent re-groups — the operator's view of a
    # pipe that degraded around a lost stage instead of stalling
    if sup is not None and hasattr(sup, "pipeline_state"):
        try:
            body["pipeline"] = sup.pipeline_state()
        except Exception:  # noqa: BLE001 — health must never 500 over this
            pass
    return web.json_response(body)


def _readiness(state: ServerState,
               want: Optional[str]) -> Tuple[int, Dict[str, Any], bool]:
    """``/ready``'s answer right now: status, body, and whether a "not yet"
    is one that waiting can turn into ready (the launch has not arrived or
    is still loading) rather than one that will stand (a launch that
    failed)."""
    if want and want != state.launch_id:
        return 409, {"ready": False, "launch_id": state.launch_id,
                     "expected": want}, True
    # the whole load+warmup window: supervisor being built (prewarm task in
    # flight), rank workers still warming, or a rank that DIED during warmup
    # (a pod that can never serve must not report ready)
    task = state._prewarm_task
    if task is not None and not task.done():
        return 503, {"ready": False, "launch_id": state.launch_id,
                     "warming": True}, True
    if state._prewarm_error is not None and state.supervisor is None:
        return 503, {"ready": False, "launch_id": state.launch_id,
                     "error": state._prewarm_error}, False
    sup = state.supervisor
    warming = bool(getattr(sup, "warming", False))
    recovering = bool(getattr(sup, "recovering", False))
    healthy = bool(getattr(sup, "healthy", True))
    if sup is not None and (warming or recovering or not healthy):
        # recovering: the watchdog is respawning dead ranks — readiness
        # flips down for the recovery window and back up once healed
        # (permanent restart-budget exhaustion keeps healthy False forever,
        # so /ready stays down for good)
        return 503, {"ready": False, "launch_id": state.launch_id,
                     "warming": warming, "recovering": recovering,
                     "healthy": healthy}, warming or recovering
    return 200, {"ready": True, "launch_id": state.launch_id,
                 "boot": state.boot_body()}, False


def _ready_wait(request: web.Request) -> float:
    """Seconds the caller lets this pod hold its ``/ready`` (``wait=``), cut
    to the pod's cap; 0.0 for none, which is every probe's."""
    try:
        wait = float(request.query.get("wait", 0))
    except ValueError:
        return 0.0
    return min(wait, READY_WAIT_CAP_S) if wait > 0 else 0.0


async def ready(request: web.Request) -> web.Response:
    """Reload-completion barrier (reference :1670): ready only when the pod's
    launch_id matches the client's freshly deployed one AND the rank workers
    have finished their load+warmup window (``__kt_warmup__`` pays jit
    compilation before the pod joins the endpoint pool).

    With ``wait=<seconds>`` the pod does the deploying client's waiting: a
    "not yet" that can still become ready is held open and answered when
    it does, or as it stands once ``wait`` (at most ``READY_WAIT_CAP_S``)
    has run out. Without it, and for a launch that cannot become ready,
    the answer is immediate."""
    state: ServerState = request.app["state"]
    want = request.query.get("launch_id")
    status, body, pending = _readiness(state, want)
    until = time.monotonic() + _ready_wait(request)
    while pending and (left := until - time.monotonic()) > 0:
        await asyncio.sleep(min(READY_RECHECK_S, left))
        status, body, pending = _readiness(state, want)
    return web.json_response(body, status=status)


async def metrics(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    try:
        from prometheus_client import generate_latest, REGISTRY
        body = generate_latest(REGISTRY)
    except Exception:
        body = b""
    from .metrics_push import tpu_gauges
    lines = {
        "kubetorch_last_activity_timestamp": state.last_activity,
        "kt_http_requests_total": state.request_count,
        "kt_inflight_requests": state.inflight,
        # HBM gauges on the SCRAPE endpoint too (not just the push loop):
        # Prometheus (deploy/metrics.yaml) and live client streaming read
        # the TPU signal from here. Off-loop: memory_stats() can stall on a
        # busy chip and a 3s-interval scraper must not block /health.
        **(await asyncio.to_thread(tpu_gauges)),
    }
    # user gauges: rank 0's __kt_metrics__ hook (the __kt_warmup__ sibling)
    # — serving state like the generation engine's tokens/s and slot
    # occupancy, merged under kt_user_. Best-effort with a short cap: a
    # stuck rank must not wedge the 3s scraper.
    sup = state.supervisor
    if (sup is not None and getattr(sup, "pool", None) is not None
            and not getattr(sup, "warming", False)):
        # warming gate: the worker loop doesn't poll its queue until the
        # load+warmup window ends — submitting during it would stall every
        # scrape for the full timeout AND backlog one stale op per scrape
        try:
            user = await asyncio.wait_for(sup.pool.user_metrics(0),
                                          timeout=3.0)
        except Exception:  # noqa: BLE001
            user = {}
        for k, v in (user or {}).items():
            safe = re.sub(r"[^a-zA-Z0-9_]", "_", str(k))
            lines[f"kt_user_{safe}"] = v
    # TYPE-headed exposition (ISSUE 5): the registry (stage histograms,
    # retry/death/chaos counters) + the state-derived gauge lines above,
    # label-escaped and grouped — never hand-joined "k v" pairs.
    extra = (telemetry.REGISTRY.render()
             + telemetry.render_untyped_gauges(lines)).encode()
    return web.Response(body=body + extra, content_type="text/plain")


async def debug_traces(request: web.Request) -> web.Response:
    """``GET /debug/traces[?q=<request_id|trace_id>][&limit=N]`` — this
    process's span ring (including rank-worker spans shipped back over the
    response queue). The flight recorder behind ``kt trace``."""
    limit = None
    try:
        if request.query.get("limit"):
            limit = max(1, int(request.query["limit"]))
    except ValueError:
        return web.json_response({"error": "bad limit"}, status=400)
    return web.json_response(telemetry.debug_traces_payload(
        request.query.get("q") or request.query.get("request_id"),
        limit=limit))


async def app_status(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    proc = state.app_process
    if proc is None:
        return web.json_response({"running": False}, status=404)
    running = proc.returncode is None
    return web.json_response({"running": running, "returncode": proc.returncode})


async def rollout_status(request: web.Request) -> web.Response:
    """Live weight-rollout state of every engine coordinator in THIS
    process (ISSUE 11): per-replica manifest version, fingerprint, canary
    phase, bytes moved by source — the rows ``kt rollout status``
    aggregates across the fleet. Engines whose coordinator runs in a rank
    worker surface through the pod's ``/metrics`` (``kt_rollout_*``)
    instead; an empty list here just means no in-process rollout."""
    def _collect():
        try:
            from ..serve.rollout import local_status
            return local_status()
        except Exception:       # noqa: BLE001 — serve/ absent or jax-less
            return []

    rollouts = await asyncio.to_thread(_collect)
    return web.json_response({"rollouts": rollouts})


async def reload_route(request: web.Request) -> web.Response:
    """HTTP reload path (controller WS push calls state.reload directly)."""
    state: ServerState = request.app["state"]
    try:
        body = json.loads(await request.read())
        await state.reload(body.get("metadata", {}),
                           body.get("launch_id", uuid.uuid4().hex))
        return web.json_response({"ok": True, "launch_id": state.launch_id})
    except BaseException as e:  # noqa: BLE001
        return _error_response(e)


async def profile_route(request: web.Request) -> web.Response:
    """POST /_kt/profile {duration_s} → capture a jax.profiler trace in the
    rank-0 subprocess, return it as a tar.gz (TensorBoard-loadable)."""
    state: ServerState = request.app["state"]
    try:
        body = json.loads(await request.read() or b"{}")
        sup = await state.get_supervisor()
        result = await sup.pool.profile(
            duration_s=float(body.get("duration_s", 3.0)))
        import io
        import tarfile

        def _tar() -> bytes:
            buf = io.BytesIO()
            with tarfile.open(fileobj=buf, mode="w:gz") as tar:
                tar.add(result["trace_dir"],
                        arcname=os.path.basename(result["trace_dir"]))
            return buf.getvalue()

        # real traces are tens of MB — never compress on the event loop
        # (stalled /health probes would make this pod look dead mid-profile)
        payload = await asyncio.to_thread(_tar)
        return web.Response(body=payload,
                            content_type="application/gzip",
                            headers={"X-KT-Trace-Dir": result["trace_dir"]})
    except BaseException as e:  # noqa: BLE001
        return _error_response(e)


async def serve_cached_data(request: web.Request) -> web.Response:
    """P2P broadcast parent role (reference PodDataServer TCP serving,
    pod_data_server.py:668-745 — TPU redesign per SURVEY §2.9: host-staged
    bytes over the pod's existing HTTP server instead of a CUDA-IPC daemon):
    serve a data-store key this pod already fetched, so later joiners in the
    fan-out pull from us instead of the central store."""
    from ..data_store.peer_cache import cache_get

    key = request.match_info["key"]
    entry = await asyncio.to_thread(cache_get, key)
    if entry is None:
        return web.json_response({"error": "not cached"}, status=404)
    data, meta = entry
    import json as _json
    return web.Response(body=data, content_type="application/octet-stream",
                        headers={"X-KT-Meta": _json.dumps(meta)})


async def exec_route(request: web.Request) -> web.Response:
    """POST /_kt/exec {"cmd": ..., "timeout": ...} → {rc, stdout, stderr}.

    Backs ``Compute.run_bash``/``pip_install`` (reference pod ops,
    compute.py:2400-2493). The reference reaches pods via ``kubectl exec``;
    here the pod's own server runs the command, so the same surface works on
    the local backend and through the controller's service proxy without
    kubectl credentials. No privilege escalation: this server already
    executes arbitrary user callables by design."""
    body = await request.json()
    cmd = body.get("cmd")
    if not cmd:
        return web.json_response({"error": "missing cmd"}, status=400)
    timeout = float(body.get("timeout", 600))
    try:
        proc = await asyncio.create_subprocess_shell(
            cmd, stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE)
        out, err = await asyncio.wait_for(proc.communicate(), timeout)
    except asyncio.TimeoutError:
        with contextlib.suppress(ProcessLookupError):
            proc.kill()
        return web.json_response({"rc": -1, "stdout": "",
                                  "stderr": f"timed out after {timeout}s"})
    return web.json_response({
        "rc": proc.returncode,
        "stdout": out.decode(errors="replace"),
        "stderr": err.decode(errors="replace"),
    })


async def run_callable(request: web.Request) -> web.Response:
    """POST /{fn}[/{method}] → supervisor (reference run_callable :1720)."""
    state: ServerState = request.app["state"]
    state.request_count += 1
    state.inflight += 1
    state.last_activity = time.time()
    # the call's server-side timeline goes back on the response (ISSUE 26):
    # every stage below adds its seconds to this request's collector, the
    # pool adds the rank's. None, and no header, when tracing is disabled.
    timing = telemetry.begin_call_timing()
    sp = telemetry.current_span()
    try:
        resp = await _run_callable_inner(request, state)
        if timing is not None:
            if sp is not None:
                timing["pod.total"] = sp.seconds()
            resp.headers[telemetry.TIMING_HEADER] = telemetry.format_timing(
                telemetry.finish_call_timing(timing))
        return resp
    finally:
        state.inflight -= 1
        state.last_activity = time.time()


async def _run_callable_inner(request: web.Request,
                              state: "ServerState") -> web.Response:
    fn_name = request.match_info["fn_name"]
    method = request.match_info.get("method") or None
    fmt = request.headers.get("X-Serialization", ser.JSON)
    try:
        raw = await request.read()
        try:
            with telemetry.stage("deserialize", bytes=len(raw), fmt=fmt):
                body = ser.deserialize(
                    raw, fmt, allowed=state.allowed_serialization()) or {}
        except SerializationError as e:
            return _error_response(e, status=415)

        sup = await state.get_supervisor()
        expected = sup.pointers.cls_or_fn_name if sup.pointers else None
        if expected and fn_name != expected:
            return _error_response(
                KubetorchError(f"This service hosts {expected!r}, not {fn_name!r}"),
                status=404)

        args = body.get("args", [])
        kwargs = body.get("kwargs", {})
        is_subcall = request.query.get("distributed_subcall") == "true"
        call_kwargs: Dict[str, Any] = {}
        if is_subcall:
            call_kwargs["subtree"] = body.get("_kt_subtree") or []
            if body.get("_kt_sel_ips"):
                call_kwargs["sel_ips"] = body["_kt_sel_ips"]
        elif "_kt_workers" in body:
            call_kwargs["workers"] = body.pop("_kt_workers")
        if hasattr(sup, "server_port"):
            fwd = {"X-Request-ID": request["kt_request_id"],
                   "X-Serialization": ser.JSON}
            # the front-door vocabulary must survive the hop: the router
            # sheds on the deadline and tier, and the peer pod re-enforces
            # the deadline on the forwarded leg (ISSUE 9)
            from ..constants import PRIORITY_HEADER, SESSION_HEADER
            for h in (DEADLINE_HEADER, PRIORITY_HEADER, SESSION_HEADER):
                if request.headers.get(h):
                    fwd[h] = request.headers[h]
            call_kwargs.setdefault("headers", fwd)

        if body.get("debugger"):
            from .pdb_ws import arm_debugger
            arm_debugger(body["debugger"])

        with telemetry.stage("execute", fn=fn_name, method=method or ""):
            result = await sup.call(method, args, kwargs, **call_kwargs)
        return web.Response(body=ser.serialize(result, fmt),
                            headers={"X-Serialization": fmt},
                            content_type="application/octet-stream"
                            if fmt != ser.JSON else "application/json")
    except (PodTerminatedError, WorkerDiedError) as e:
        # infra faults, not user errors: 503 so load balancers shed traffic
        # while the watchdog restarts the rank pool
        return _error_response(e, status=503)
    except AdmissionShedError as e:
        # the front door refused before prefill: typed 429 + the router's
        # backpressure hint, so clients back off instead of hammering
        resp = _error_response(e, status=429)
        if e.retry_after is not None:
            resp.headers["Retry-After"] = f"{max(e.retry_after, 0.0):.3f}"
        return resp
    except DeadlineExceededError as e:
        # router-level shed of an expired deadline (the middleware catches
        # arrivals; this catches expiry inside the admission queue)
        return _error_response(e, status=504)
    except BaseException as e:  # noqa: BLE001
        return _error_response(e)


# ---------------------------------------------------------------------------
# App assembly / lifespan
# ---------------------------------------------------------------------------


def create_app(state: Optional[ServerState] = None) -> web.Application:
    # order matters: request-id first; chaos next (faults model the network,
    # so they hit before any server logic); deadline before the dedupe cache
    # (an expired replay is still expired); idempotency outside termination
    # so the cached entry is exactly what the client saw.
    middlewares = [request_id_middleware, deadline_middleware,
                   idempotency_middleware, termination_middleware]
    from ..chaos import maybe_chaos_middleware
    chaos_mw, chaos_engine = maybe_chaos_middleware()
    if chaos_mw is not None:
        middlewares.insert(1, chaos_mw)
    app = web.Application(middlewares=middlewares,
                          client_max_size=1024 ** 3)
    app["chaos"] = chaos_engine
    app["state"] = state or ServerState()
    app.router.add_get("/health", health)
    app.router.add_get("/ready", ready)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/traces", debug_traces)
    app.router.add_get("/app/status", app_status)
    app.router.add_get("/rollout/status", rollout_status)
    app.router.add_post("/_kt/reload", reload_route)
    app.router.add_post("/_kt/profile", profile_route)
    app.router.add_post("/_kt/exec", exec_route)
    app.router.add_get("/_kt/data/{key:.+}", serve_cached_data)
    app.router.add_post("/{fn_name}", run_callable)
    app.router.add_post("/{fn_name}/{method}", run_callable)
    app.on_startup.append(_on_startup)
    app.on_cleanup.append(_on_cleanup)
    return app


async def _on_startup(app: web.Application) -> None:
    state: ServerState = app["state"]

    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(
                sig, lambda s=sig: state.terminate(_termination_reason()))
        except (NotImplementedError, RuntimeError):
            pass

    # observability
    from .log_capture import LogCapture
    from .metrics_push import MetricsPusher
    if os.environ.get("KT_LOG_SINK_URL"):
        state.log_capture = LogCapture.start_global(
            sink_url=os.environ["KT_LOG_SINK_URL"],
            labels={"service": os.environ.get(KT_SERVICE_NAME, ""),
                    "pod": state.pod_name, "namespace": state.namespace})
    if os.environ.get("KT_METRICS_GATEWAY_URL"):
        state.metrics_pusher = MetricsPusher(
            gateway_url=os.environ["KT_METRICS_GATEWAY_URL"], state=state)
        state.metrics_pusher.start()

    # native bulk-transfer daemon (reference PodDataServer role): serves the
    # peer cache over epoll+sendfile so fan-out bulk bytes never ride the
    # Python event loop. Children learn the port via the store's /route
    # registry; rank workers inherit KT_BLOBD_PORT for their registrations.
    # Pod-only (POD_IP): without an advertisable address the fetchers can
    # never route to it, and an unadvertised 0.0.0.0 listener is pure risk.
    if os.environ.get("POD_IP"):
        from ..native import spawn_blobd
        from ..data_store.peer_cache import cache_dir
        proc, port = spawn_blobd(str(cache_dir()),
                                 host=os.environ["POD_IP"])
        if port is not None:
            state.blobd_proc = proc
            os.environ["KT_BLOBD_PORT"] = str(port)

    # controller WebSocket (metadata + reload push)
    ws_url = os.environ.get("KT_CONTROLLER_WS_URL")
    if ws_url:
        from .controller_ws import ControllerWebSocket
        state.controller_ws = ControllerWebSocket(ws_url, state)
        await state.controller_ws.start()

    # env-driven metadata (BYO pods, `kt server start`): open the load+warmup
    # window now so /ready gates on it; WS-driven pods prewarm from reload()
    state.prewarm_supervisor()


def _termination_reason() -> str:
    """Classify why we are being killed (reference serving/utils.py:111-191).

    On GKE TPU slices, maintenance/preemption arrives as SIGTERM with a node
    taint; we surface it as ``Preempted`` so clients can programmatically
    resize/retry rather than treating it as a crash.
    """
    if os.environ.get("KT_PREEMPTIBLE") or os.path.exists(
            "/var/run/kubetorch/preemption"):
        return "Preempted"
    return os.environ.get("KT_TERMINATION_REASON", "Terminated")


async def _on_cleanup(app: web.Application) -> None:
    state: ServerState = app["state"]
    if state.controller_ws is not None:
        await state.controller_ws.stop()
    # a prewarm in flight is building a supervisor (spawning TPU-holding
    # workers): wait for it, so the cleanup below actually reaches that pool
    # instead of orphaning mid-compile subprocesses
    if state._prewarm_task is not None and not state._prewarm_task.done():
        # gather(return_exceptions) also absorbs CancelledError: even a
        # cancelled shutdown must fall through to supervisor.cleanup()
        await asyncio.gather(state._prewarm_task, return_exceptions=True)
    if state.supervisor is not None:
        await asyncio.to_thread(state.supervisor.cleanup)
    if state.metrics_pusher is not None:
        state.metrics_pusher.stop()
    if state.log_capture is not None:
        state.log_capture.stop()
    from .remote_worker_pool import RemoteWorkerPool
    if RemoteWorkerPool._instance is not None:
        await RemoteWorkerPool._instance.close()
    if state.blobd_proc is not None and state.blobd_proc.poll() is None:
        state.blobd_proc.terminate()


def main(argv: Optional[list] = None) -> None:
    import argparse

    p = argparse.ArgumentParser(description="kubetorch-tpu pod server")
    p.add_argument("--port", type=int, default=server_port())
    p.add_argument("--host", default="0.0.0.0")
    args = p.parse_args(argv)
    if args.port == 0:
        # bind-ephemeral: resolve the real port BEFORE advertising, or the
        # WS registration and peer subcalls would publish the unroutable :0
        from ..utils.procs import free_port

        args.port = free_port()
    # Advertise the BOUND port to everything that derives URLs from env —
    # the controller-WS registration and the supervisor's peer subcalls —
    # regardless of how the server was launched (CLI, -m, embedder). A
    # --port flag alone must not leave them pointing at the default.
    os.environ["KT_SERVER_PORT"] = str(args.port)
    # flight recorder (ISSUE 20): armed only when KT_OBS_SPOOL is set —
    # then this pod's telemetry history survives its own SIGKILL
    from ..obs import maybe_start_recorder
    maybe_start_recorder("pod")
    asyncio.run(_serve(create_app(), args.host, args.port))


async def _serve(app: web.Application, host: str, port: int) -> None:
    """Run until SIGTERM/SIGINT, then drain and exit (k8s semantics: a pod
    must vacate before the kubelet's SIGKILL; locally, an orphaned pod that
    kept serving would squat its IP:port and wedge every revival after a
    controller restart). ``web.run_app`` can't express this — the signal
    handlers installed in ``_on_startup`` only set the termination flag, so
    the serve loop below owns the actual shutdown."""
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()          # fires on_startup (installs handlers)
    await web.TCPSite(runner, host, port).start()
    state: ServerState = app["state"]
    state.note_listening()
    await state.termination.wait()
    deadline = time.monotonic() + float(
        os.environ.get("KT_TERMINATION_DRAIN_S", "25"))
    while state.inflight > 0 and time.monotonic() < deadline:
        await asyncio.sleep(0.25)
    await runner.cleanup()        # fires on_cleanup (pools, WS, capture)


if __name__ == "__main__":
    # Re-import under the canonical name: ``python -m ...http_server`` makes
    # this file ``__main__``, and building the app from that duplicate module
    # would split every module-level singleton — request_id_var above, the
    # ServerState caches — from the copies the rest of the package imports
    # (symptom: rank logs lose their request-id labels because the middleware
    # sets one ContextVar and ProcessPool._submit reads another).
    from kubetorch_tpu.serving.http_server import main as _canonical_main

    _canonical_main()

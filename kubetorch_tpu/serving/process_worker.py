"""Rank subprocess: loads the user callable and executes requests.

Reference model (``serving/process_worker.py``): a spawned
``multiprocessing.Process`` running an asyncio loop that polls a request
queue and handles requests concurrently (async callables awaited, sync ones
in a thread pool), with per-request distributed env vars and child-process
cleanup on teardown.

TPU-first deltas:
- **spawn** start method is mandatory (fork would duplicate a libtpu handle;
  TPU chips are exclusively owned per-process).
- The framework env (JAX coordinator, TPU_WORKER_ID) and the compile-cache
  directory are applied *before* the callable module is imported, because
  importing user code typically imports jax, which reads these at import or
  at first device query.
- A rank whose environment names the ``tpu`` platform first proves it holds
  the chip before it loads user code; otherwise its load fails with a typed
  ``AcceleratorUnavailableError`` — it never serves from the CPU.
- HBM OOM from XLA is detected and repackaged as a typed ``HbmOomError``.
"""

from __future__ import annotations

import asyncio
import contextvars
import multiprocessing as mp
import os
import queue as queue_mod
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

from ..exceptions import detect_hbm_oom, package_exception
from ..resources.pointers import Pointers, import_callable
from .env_contract import RankInfo, framework_for

# sync calls of the target in flight at once: a served engine is called by
# as many callers as it has slots (64 for a hybrid model's state grid), each
# blocking a thread until its reply. Threads start only as calls arrive.
_SYNC_EXECUTOR_THREADS = 128


# The HTTP X-Request-ID travels server → worker in the request item and is
# re-bound here per handled request, so rank prints stay correlated to the
# originating call even across the process boundary (the reference threads
# the same label through its subprocess LogCapture queue). The trace
# context rides the same envelope: the rank's execute span joins the
# request's trace, and rank log lines carry its trace_id.
_rank_request_id: contextvars.ContextVar[str] = contextvars.ContextVar(
    "kt_rank_request_id", default="")


class _QueueTee:
    """Mirror a worker's stream into the response queue so the server-side
    LogCapture ships rank logs too (reference create_subprocess_log_capture,
    serving/log_capture.py:416). Dual-writes so `kubectl logs` still works."""

    def __init__(self, original, response_q, source: str):
        self.original = original
        self.response_q = response_q
        self.source = source

    def write(self, data: str):
        self.original.write(data)
        if data.strip():
            try:
                from .. import telemetry
                self.response_q.put({"op": "log", "line": data.rstrip("\n"),
                                     "source": self.source,
                                     "rank": os.environ.get("RANK", "0"),
                                     "request_id": _rank_request_id.get(""),
                                     "trace_id":
                                         telemetry.current_trace_id() or ""})
            except Exception:
                pass
        return len(data)

    def flush(self):
        self.original.flush()

    def fileno(self):
        # libraries probing the stream (absl/jax logging, subprocess
        # stdout= pass-through) need the REAL descriptor; without this the
        # first fileno() call kills the rank worker mid-request
        return self.original.fileno()

    def isatty(self):
        return False


def _worker_main(request_q: mp.Queue, response_q: mp.Queue,
                 env: Dict[str, str], pointers_dict: Optional[Dict],
                 init_args: Optional[Dict], framework_name: str,
                 identity_env: Optional[Dict[str, str]] = None,
                 shm_spec: Optional[Dict[str, str]] = None) -> None:
    os.environ.update(env)
    sys.stdout = _QueueTee(sys.stdout, response_q, "stdout")
    sys.stderr = _QueueTee(sys.stderr, response_q, "stderr")
    # Cooperative preemption (ISSUE 6): SIGTERM no longer kills the rank
    # mid-step — it flips the process-local drain flag, the in-flight user
    # step observes it via elastic.drain_requested() and flushes a committed
    # checkpoint inside the grace window, then the loop below exits cleanly.
    # The sender's SIGKILL (kubelet / term-rank chaos) stays the backstop.
    from .elastic import install_sigterm_drain
    install_sigterm_drain()
    # before anything in this process can import jax, which reads it once
    from ..compile_cache import ensure_compile_cache
    ensure_compile_cache()
    # flight recorder (ISSUE 20): armed only when KT_OBS_SPOOL is set —
    # a kill-rank SIGKILL mid-call then leaves this rank's in-flight span
    # and final metric snapshot in its own spool
    from ..obs import maybe_start_recorder
    rank = (identity_env or {}).get("RANK", os.environ.get("RANK", ""))
    maybe_start_recorder(f"rank{rank}" if rank != "" else "rank")
    asyncio.run(_worker_loop(request_q, response_q, pointers_dict, init_args,
                             framework_name, identity_env, shm_spec))


async def _worker_loop(request_q, response_q, pointers_dict, init_args,
                       framework_name, identity_env=None,
                       shm_spec=None) -> None:
    loop = asyncio.get_running_loop()
    executor = ThreadPoolExecutor(max_workers=_SYNC_EXECUTOR_THREADS)
    target: Any = None
    load_error: Optional[BaseException] = None
    # zero-copy envelope rings (ISSUE 10): the parent created one segment
    # per direction; attach both (req: parent writes / this rank reads,
    # resp: this rank writes / parent reads). Attach failure downgrades to
    # the classic queue path — never a dead rank.
    rings: Dict[str, Any] = {}
    if shm_spec:
        from . import shm_ring
        try:
            rings["req"] = shm_ring.ShmRing(shm_spec["req"])
            rings["resp"] = shm_ring.ShmRing(shm_spec["resp"])
        except Exception:  # noqa: BLE001 — degrade, don't die
            for r in rings.values():
                r.close()
            rings = {}
            print("[kt] shm ring attach failed; falling back to queue "
                  "path:\n" + traceback.format_exc())
    # process-level chaos (ISSUE 3/6): KT_CHAOS kill-rank verbs make THIS
    # rank kill itself at a chosen call index — the deterministic stand-in
    # for an OOM kill landing mid-call — and term-rank verbs deliver the
    # graceful SIGTERM + grace-window SIGKILL pair (the GKE preemption
    # contract) so the drain-and-checkpoint path is testable too
    from ..chaos import rank_kill_plan, rank_term_plan
    from .elastic import drain_requested
    kill_plan = rank_kill_plan()
    term_plan = rank_term_plan()
    call_index = 0

    # Eager-load the callable at spawn (reference :236-247) so first-request
    # latency excludes import cost, and failures surface in health checks.
    # The state ops bracket the load+warmup window: the parent ProcessPool
    # marks the worker in_warmup and (a) /ready reports not-ready until done,
    # (b) shutdown withholds its SIGKILL escalation — a jit compile in
    # flight must never be force-killed (it can wedge the TPU runtime).
    # The boot's phases ride the same state ops (ISSUE 26): the pool learns
    # how long this rank took to import jax, open the chip, import the
    # target, build it and warm it up, and /ready hands that to the
    # deploying client. The phases are one span tree (ISSUE 38), shipped to
    # the pod's ring like a call's, with every program jax compiled inside.
    from .. import telemetry
    response_q.put({"op": "state", "warmup": "started"})
    boot = telemetry.BootTree("rank.boot", boundary=_count_jit,
                              rank=os.environ.get("RANK", "0"))
    with boot:
        if pointers_dict:
            try:
                require_accelerator(boot)
                target = _load_target(pointers_dict, init_args, boot)
            except BaseException as e:  # noqa: BLE001 — must report, not die
                load_error = e
            else:
                with boot.phase("warmup"):
                    await _run_warmup(target)
        record = boot_record(boot)
        boot.root.attrs.update(record)
    for span_dict in boot.spans():
        response_q.put({"op": "span", "span": span_dict})
    response_q.put({"op": "state", "warmup": "done", "boot": record})

    pending = set()

    def poll():
        try:
            return request_q.get(timeout=0.2)
        except queue_mod.Empty:
            return None

    while True:
        item = await loop.run_in_executor(None, poll)
        if item is None:
            pending = {t for t in pending if not t.done()}
            if drain_requested() and not pending:
                # cooperative drain completed: every in-flight step has
                # observed the flag (and flushed its checkpoint) — exit
                # cleanly so the parent's watchdog classifies a drained
                # rank, not an anonymous kill, and the elastic layer can
                # resume from the fresh commit with zero lost steps
                print("[kt] rank draining: all in-flight work done, exiting")
                framework_for(framework_name).worker_cleanup()
                break
            continue
        if item.get("op") == "shutdown":
            framework_for(framework_name).worker_cleanup()
            break
        if item.get("op") == "profile":
            task = asyncio.ensure_future(_handle_profile(item, response_q))
        elif item.get("op") == "user_metrics":
            task = asyncio.ensure_future(
                _handle_user_metrics(item, target, response_q, executor))
        else:
            if kill_plan:
                sig = kill_plan.get(call_index)
                if sig is not None:
                    # mid-call by construction: the parent registered this
                    # req's future at submit, and no response will ever come
                    print(f"[kt] chaos: kill-rank sig={sig} "
                          f"at call index {call_index}")
                    os.kill(os.getpid(), sig)
            if term_plan:
                grace = term_plan.get(call_index)
                if grace is not None:
                    term_plan.pop(call_index)
                    _chaos_term_self(grace, call_index)
            call_index += 1
            if item.get("_kt_shm"):
                # envelopes decode IMMEDIATELY at dequeue (queue order ==
                # ring order, so slots free in allocation order); a hash
                # mismatch answers this req_id with the typed corruption
                # error — the parent pool retries once over the queue path
                from . import shm_ring
                try:
                    copy = telemetry.stage("shm_copy", dir="req")
                    with copy:
                        shm_ring.decode_item_fields(
                            item, rings.get("req"), ("args", "kwargs"),
                            "req")
                    item["shm_req_s"] = copy.seconds
                except BaseException as e:  # noqa: BLE001
                    from ..exceptions import package_exception
                    response_q.put({"req_id": item.get("req_id"),
                                    "ok": False,
                                    "error": package_exception(e)})
                    continue
            task = asyncio.ensure_future(
                _handle(item, target, load_error, response_q, executor,
                        identity_env, rings.get("resp")))
        pending.add(task)
    for r in rings.values():
        r.close()


def _chaos_term_self(grace_s: float, call_index: int) -> None:
    """term-rank chaos: the GKE preemption contract, self-delivered — the
    op just dequeued still runs and can flush a checkpoint inside the
    grace window. Delivery itself (SIGTERM + daemon SIGKILL timer) is the
    shared :func:`~..chaos.deliver_term_with_grace` contract, the same one
    scheduler-preemption tests use against external pids."""
    from ..chaos import deliver_term_with_grace

    deliver_term_with_grace(os.getpid(), grace_s,
                            label=f"term-rank at call index {call_index}")


def _host_view(obj: Any) -> Any:
    """Device arrays can't cross the mp.Queue (no cross-process device
    handles on TPU — SURVEY §2.9); pull them to host numpy here."""
    t = type(obj)
    if t.__module__.startswith(("jax", "jaxlib")) and hasattr(obj, "dtype"):
        import numpy as np
        return np.asarray(obj)
    if isinstance(obj, dict):
        return {k: _host_view(v) for k, v in obj.items()}
    if isinstance(obj, tuple):
        vals = [_host_view(v) for v in obj]
        return type(obj)(*vals) if hasattr(obj, "_fields") else tuple(vals)
    if isinstance(obj, list):
        return [_host_view(v) for v in obj]
    return obj


async def _run_warmup(target: Any) -> None:
    """Run the user's ``__kt_warmup__`` hook (method on a class instance, or
    attribute attached to a function) right after the eager load — inference
    pools pay jit compilation at deploy time, not on the first user request
    (``/ready`` reports not-ready until the bracketing state ops complete).
    A failed warmup is logged (the stream tee ships it to the supervisor's
    rank logs) but never poisons the worker: requests may still succeed, and
    if not they produce their own errors."""
    hook = getattr(target, "__kt_warmup__", None)
    if hook is None:
        return
    try:
        result = hook()
        if asyncio.iscoroutine(result):
            await result
    except BaseException:  # noqa: BLE001
        print(f"[kt] __kt_warmup__ failed:\n{traceback.format_exc()}")


def _count_jit() -> None:
    """From the first phase boundary with jax in this process on, count
    what it compiles (``telemetry.JIT``); never import jax to count."""
    from .. import telemetry
    if "jax" in sys.modules and not telemetry.JIT.installed:
        import jax
        telemetry.JIT.install(jax.monitoring, jax.profiler.TraceAnnotation)


def boot_record(boot) -> Dict[str, float]:
    """What the rank reports of its boot, read off the boot's spans:
    ``rank_<phase>_s`` for each phase that ran; ``rank_accel_s``, the jax
    import and the TPU's open together; and, once jax is counted,
    ``rank_jit_s`` (the union of every trace, lowering and backend compile
    or fetch), ``rank_programs`` and ``rank_programs_compiled`` (of those,
    not fetched from the persistent cache)."""
    from .. import telemetry
    seconds = boot.seconds()
    out = {f"rank_{name}_s": s for name, s in seconds.items()}
    out["rank_accel_s"] = (seconds.get("jax_import", 0.0)
                           + seconds.get("tpu_open", 0.0))
    if telemetry.JIT.installed:
        jit = telemetry.JIT.totals()
        out.update(rank_jit_s=jit["jit_s"], rank_programs=jit["programs"],
                   rank_programs_compiled=jit["programs_compiled"])
    return out


def require_accelerator(boot) -> None:
    """One rank process per chip, and a rank that was given the chip holds
    it. ``JAX_PLATFORMS`` naming ``tpu`` first is the statement that this
    process owns the accelerator (the local backend sets it for pods whose
    ``Compute`` asks for a TPU, and ``cpu`` for every other pod): initialize
    jax now and refuse to load on any other backend. jax's import and the
    TPU client's open are the ``boot`` tree's phases ``jax_import`` and
    ``tpu_open``."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() != "tpu":
        return
    from ..exceptions import AcceleratorUnavailableError
    try:
        with boot.phase("jax_import"):
            import jax
        with boot.phase("tpu_open"):
            backend = jax.default_backend()
    except RuntimeError as e:    # jax: "Unable to initialize backend 'tpu'"
        raise AcceleratorUnavailableError(
            f"this rank was given the TPU but jax could not open it: {e}"
        ) from e
    if backend != "tpu":
        raise AcceleratorUnavailableError(
            f"this rank was given the TPU but jax came up on {backend!r}",
            backend=backend)


def _load_target(pointers_dict: Dict, init_args: Optional[Dict],
                 boot) -> Any:
    with boot.phase("import"):
        obj = import_callable(Pointers.from_dict(pointers_dict))
    if isinstance(obj, type):
        args = (init_args or {}).get("args", [])
        kwargs = (init_args or {}).get("kwargs", {})
        with boot.phase("init"):
            obj = obj(*args, **kwargs)
    return obj


async def _handle_profile(item: Dict, response_q) -> None:
    """Capture a jax.profiler trace in THIS process — the one that owns the
    TPU chips (the profiling story replacing the reference's DCGM/OTel,
    SURVEY §5.1). Produces a TensorBoard-loadable trace directory."""
    req_id = item.get("req_id")
    try:
        import glob
        import tempfile

        import jax

        duration = float(item.get("duration_s", 3.0))
        outdir = item.get("outdir") or tempfile.mkdtemp(prefix="kt-profile-")
        with jax.profiler.trace(outdir):
            await asyncio.sleep(duration)
        files = sorted(glob.glob(os.path.join(outdir, "**", "*"),
                                 recursive=True))
        response_q.put({"req_id": req_id, "ok": True,
                        "result": {"trace_dir": outdir,
                                   "files": [f for f in files
                                             if os.path.isfile(f)]}})
    except BaseException as e:  # noqa: BLE001
        response_q.put({"req_id": req_id, "ok": False,
                        "error": package_exception(e)})


async def _handle_user_metrics(item: Dict, target: Any, response_q,
                               executor) -> None:
    """Poll the user's ``__kt_metrics__`` hook (sibling of
    ``__kt_warmup__``): a dict of numeric gauges the pod's ``/metrics``
    scrape merges under a ``kt_user_`` prefix — how long-lived serving
    state (the generation engine's tokens/s, acceptance rate, slot
    occupancy) reaches Prometheus without the user writing an exporter.
    Runs on every scrape (3s): keep the hook cheap. Absent hook → {}.
    Sync hooks run in the executor like regular calls (``_handle``) — a
    blocking hook must stall its scrape, never the worker loop that every
    in-flight request's response rides on."""
    req_id = item.get("req_id")
    try:
        hook = getattr(target, "__kt_metrics__", None)
        result = {}
        if hook is not None:
            loop = asyncio.get_running_loop()
            out = await loop.run_in_executor(executor, hook)
            if asyncio.iscoroutine(out):
                out = await out
            result = {str(k): float(v) for k, v in (out or {}).items()
                      if isinstance(v, (int, float))}
        response_q.put({"req_id": req_id, "ok": True, "result": result})
    except BaseException as e:  # noqa: BLE001 — a broken hook must not
        # poison the worker; the scrape just misses user gauges
        response_q.put({"req_id": req_id, "ok": False,
                        "error": package_exception(e)})


def _ship_trace_spans(response_q, sp) -> None:
    """Send every finished span of this request's trace (the execute span
    plus whatever user code opened under it — store fetches, nested store
    requests) back to the parent process, where the pool ingests them into
    the server's ring. Re-shipped prefixes dedup there by span id."""
    from .. import telemetry

    d = sp.to_dict() if sp else None
    if d is None:
        return
    to_ship = telemetry.RING.find(d["trace_id"])
    # checkpoint spans can finish OFF this trace (the drain-path sync save,
    # an async commit whose step already returned): ship the recent ones
    # too so the pool can derive kt_checkpoint_seconds in the process that
    # actually serves /metrics — the parent ring dedups re-ships
    shipped = {(s.get("trace_id"), s.get("span_id")) for s in to_ship}
    for span_dict in telemetry.RING.snapshot(limit=32):
        if str(span_dict.get("name", "")).startswith("checkpoint.") and \
                (span_dict.get("trace_id"),
                 span_dict.get("span_id")) not in shipped:
            to_ship.append(span_dict)
    for span_dict in to_ship:
        try:
            response_q.put({"op": "span", "span": span_dict})
        except Exception:  # noqa: BLE001 — telemetry must not fail the call
            pass


async def _handle(item: Dict, target: Any, load_error, response_q, executor,
                  identity_env: Optional[Dict[str, str]] = None,
                  resp_ring=None) -> None:
    import time as _time

    from .. import telemetry

    req_id = item.get("req_id")
    _rank_request_id.set(item.get("request_id", ""))
    now = _time.time()
    queue_wait = max(0.0, now - float(item.get("submit_ts") or now))
    sp = telemetry.span(
        "worker.execute", parent=telemetry.parse_trace(item.get("trace")),
        rank=os.environ.get("RANK", "0"), method=item.get("method") or "",
        request_id=item.get("request_id", ""),
        queue_wait_s=round(queue_wait, 6))
    # this rank's side of the call's timeline, sent back ON the reply (the
    # spans below follow it and would come too late for the response)
    timing = telemetry.begin_call_timing()
    if timing is not None:
        telemetry.note_timing("queue_wait", queue_wait)
        if item.get("shm_req_s"):
            telemetry.note_timing("shm_copy", item["shm_req_s"])
    try:
        with sp:
            await _handle_inner(item, target, load_error, response_q,
                                executor, sp, identity_env, resp_ring)
    finally:
        _ship_trace_spans(response_q, sp)


async def _handle_inner(item: Dict, target: Any, load_error, response_q,
                        executor, sp,
                        identity_env: Optional[Dict[str, str]] = None,
                        resp_ring=None) -> None:
    from .. import telemetry

    req_id = item.get("req_id")
    try:
        if load_error is not None:
            raise load_error
        if target is None:
            raise RuntimeError("No callable loaded in worker")
        # Per-call rank identity: a worker-subset call carries dist_env with
        # selection-relative WORLD_SIZE/RANK/...; a full-set call carries
        # none and must restore the spawn identity (a previous subset call's
        # values would otherwise leak into it). Process-global by nature,
        # like the reference's per-request env writes — overlapping calls
        # with different selections are a caller error there too.
        dist_env = item.get("dist_env") or identity_env
        if dist_env:
            os.environ.update(dist_env)
        method = item.get("method")
        fn = getattr(target, method) if method else target
        args = item.get("args", [])
        kwargs = item.get("kwargs", {})
        if asyncio.iscoroutinefunction(fn):
            result = await fn(*args, **kwargs)
        else:
            loop = asyncio.get_running_loop()
            # copy_context: run_in_executor does not propagate contextvars,
            # and sync user code printing from the executor thread must keep
            # its request-id binding
            ctx = contextvars.copy_context()
            result = await loop.run_in_executor(
                executor, lambda: ctx.run(lambda: fn(*args, **kwargs)))
        with telemetry.stage("device_transfer"):
            # pulling device arrays to host numpy is the rank's last
            # per-request device touch — the transfer stage on the waterfall
            host = _host_view(result)
        resp = {"req_id": req_id, "ok": True, "result": host}
        if resp_ring is not None and not item.get("no_shm"):
            # result arrays ride the response ring the same way args rode
            # the request ring; encode and enqueue with no await between
            # them so queue order stays ring-allocation order
            from . import shm_ring
            threshold = shm_ring.shm_threshold()
            if threshold > 0:
                with telemetry.stage("shm_copy", dir="resp"):
                    n = shm_ring.encode_item_fields(
                        resp, resp_ring, ("result",), threshold, "resp")
                if n:
                    resp["_kt_shm"] = n
        _attach_timing(resp, sp)
        response_q.put(resp)
    except BaseException as e:  # noqa: BLE001
        oom = detect_hbm_oom(e)
        payload = package_exception(oom if oom is not None else e)
        sp.set_status("error")
        sp.set_attr("error", payload.get("error_type", type(e).__name__))
        resp = {"req_id": req_id, "ok": False, "error": payload}
        _attach_timing(resp, sp)
        response_q.put(resp)


def _attach_timing(resp: Dict, sp) -> None:
    """Put what this rank observed of the call on its reply: the stages
    collected in the call's context, the seconds since ``worker.execute``
    opened, and the numbers of the last ``engine.request`` event the call
    left on that span (``RequestHandle.result``). Nothing when tracing is
    disabled."""
    from .. import telemetry

    timing = telemetry.current_call_timing()
    if timing is None or not sp:
        return
    timing[telemetry.RANK_EXECUTE] = sp.seconds()
    for _ts, _mono, name, attrs in reversed(sp.events):
        if name == "engine.request":
            timing.update(telemetry.engine_timing(attrs))
            break
    resp["timing"] = timing


class ProcessWorker:
    """Handle to one rank subprocess."""

    def __init__(self, rank_info: RankInfo, framework_name: str,
                 pointers: Optional[Pointers], init_args: Optional[Dict],
                 base_env: Optional[Dict[str, str]] = None):
        self.rank_info = rank_info
        self.framework_name = framework_name
        ctx = mp.get_context("spawn")
        self.request_q: mp.Queue = ctx.Queue()
        self.response_q: mp.Queue = ctx.Queue()
        fw = framework_for(framework_name)
        fw_env = fw.env(rank_info)
        env = dict(base_env or {})
        env.update(fw_env)
        self.env = env
        # Spawn-time identity, re-applied on every full-set call for
        # frameworks with per-call identity so a subset call's rebinding
        # never leaks into the next request. None for spawn-fixed identity
        # (JAX/TPU): those workers never touch env per request.
        identity_env = fw_env if fw.per_call_identity else None
        # flipped by ProcessPool._route_responses from the worker's state ops
        self.in_warmup = True
        # boot phases in seconds, and ready_mono once warm (note_boot)
        self.boot: Dict[str, float] = {}
        self._started_mono = 0.0
        # zero-copy envelope rings (ISSUE 10): one segment per direction,
        # created by THIS side (which owns their lifecycle — see
        # cleanup_shm) and attached by name in the child. Only built when
        # KT_SHM_THRESHOLD opts the deployment in; creation failure (tiny
        # /dev/shm, exotic platform) downgrades to the queue path.
        self.shm_req = self.shm_resp = None
        shm_spec = None
        from . import shm_ring
        if shm_ring.enabled():
            try:
                size = shm_ring.ring_bytes()
                tag = f"r{rank_info.local_rank}"
                self.shm_req = shm_ring.ShmRing(
                    shm_ring.make_name(f"{tag}-req"), size=size, create=True)
                self.shm_resp = shm_ring.ShmRing(
                    shm_ring.make_name(f"{tag}-resp"), size=size, create=True)
                shm_spec = {"req": self.shm_req.name,
                            "resp": self.shm_resp.name}
            except Exception as e:  # noqa: BLE001 — degrade, don't die
                self.cleanup_shm()
                print(f"[kt] shm ring create failed ({e}); "
                      "using queue path")
        self.process = ctx.Process(
            target=_worker_main,
            args=(self.request_q, self.response_q, env,
                  pointers.to_dict() if pointers else None, init_args,
                  framework_name, identity_env, shm_spec),
            daemon=True,
        )

    def start(self) -> None:
        self._started_mono = time.monotonic()
        self.process.start()

    def note_boot(self, state_op: Dict) -> None:
        """From the rank's two state ops: ``started`` closes ``rank_spawn_s``
        (process start to its worker loop running, seen from this side),
        ``done`` brings the rank's own phases and stamps the moment it
        became ready."""
        now = time.monotonic()
        if state_op.get("warmup") == "started":
            self.boot = {"rank_spawn_s": now - self._started_mono}
        else:
            self.boot.update(state_op.get("boot") or {})
            self.boot["ready_mono"] = now

    def submit(self, req: Dict) -> None:
        self.request_q.put(req)

    def request_shutdown(self) -> None:
        """Enqueue the graceful-stop op (non-blocking). The worker handles it
        after finishing any in-flight load/warmup."""
        try:
            self.request_q.put({"op": "shutdown"})
        except Exception:
            pass

    def force_kill_if_alive(self) -> None:
        """Last-resort SIGKILL. Callers (ProcessPool.shutdown) must have
        already granted the warmup grace — a process killed mid-jit-compile
        while holding the TPU can wedge the runtime for every successor.
        Always reclaims this worker's shared-memory rings afterwards: a
        rank retired by ANY path (watchdog restart, elastic re-mesh,
        shutdown) must never leak ``/dev/shm`` segments."""
        if self.process.is_alive():
            from ..utils.procs import kill_process_tree
            if self.in_warmup:
                print(f"[kt] rank {self.rank_info.rank} still in warmup at "
                      "kill escalation; TPU runtime may need a reset")
            kill_process_tree(self.process.pid)
        self.cleanup_shm()

    def cleanup_shm(self) -> None:
        """Close + unlink both envelope rings (idempotent). The creating
        side owns segment lifecycle; the watchdog and every restart path
        land here, so a dead rank's segments are reclaimed within one
        watchdog interval."""
        for attr in ("shm_req", "shm_resp"):
            ring = getattr(self, attr, None)
            if ring is not None:
                setattr(self, attr, None)
                ring.unlink()
                ring.close()

    @property
    def alive(self) -> bool:
        return self.process.is_alive()

    @property
    def exitcode(self) -> Optional[int]:
        """``multiprocessing`` exitcode (negative = killed by that signal);
        None while alive or never started — the watchdog's classification
        input."""
        return self.process.exitcode

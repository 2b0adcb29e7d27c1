"""Pool of rank subprocesses with an async request/response router.

Reference (``serving/process_pool.py``): N ProcessWorkers + mp queues, a
response-router thread matching req_ids to futures, ``call`` (one rank) and
``call_all`` (every local rank in parallel), queue draining on restart.

Liveness is owned by the pool's :class:`~.watchdog.Watchdog` (ISSUE 3): a
rank that dies *mid-call* gets its in-flight futures failed with a typed
:class:`~..exceptions.WorkerDiedError` within the watchdog interval — not
the call timeout — and the pool self-heals within a bounded restart budget
(full-pool for spawn-fixed collective identity, single-rank otherwise).
"""

from __future__ import annotations

import asyncio
import itertools
import os
import threading
import time
import queue as queue_mod
from typing import Any, Dict, List, Optional, Tuple

from ..exceptions import DataCorruptionError, rehydrate_exception
from ..resources.pointers import Pointers
from . import shm_ring
from .env_contract import RankInfo
from .watchdog import Watchdog


class ProcessPool:
    def __init__(self, num_procs: int, framework_name: str,
                 pointers: Optional[Pointers], init_args: Optional[Dict],
                 node_rank: int = 0, num_nodes: int = 1,
                 pod_ips: Optional[List[str]] = None,
                 base_env: Optional[Dict[str, str]] = None):
        self.num_procs = num_procs
        self.framework_name = framework_name
        # spawn parameters are kept so the watchdog can respawn dead ranks
        # with their original identity
        self._pointers = pointers
        self._init_args = init_args
        self._node_rank = node_rank
        self._num_nodes = num_nodes
        self._pod_ips = list(pod_ips or ["127.0.0.1"])
        self._base_env = base_env
        self.workers: List[Any] = [self._new_worker(lr)
                                   for lr in range(num_procs)]
        # req_id → (future, worker index): the index is what lets a death
        # fail exactly the dead rank's in-flight calls
        self._futures: Dict[str, Tuple[asyncio.Future, int]] = {}
        self._futures_lock = threading.Lock()
        self._req_counter = itertools.count()
        self._router_threads: List[threading.Thread] = []
        self._stopping = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # router wake pipe (ISSUE 10): response routers BLOCK on the
        # queue's pipe instead of polling at 5 Hz; state changes that a
        # queue read can't observe (shutdown, a rank death noticed by the
        # watchdog) write a byte here to wake every router immediately
        self._wake_r, self._wake_w = os.pipe()
        os.set_blocking(self._wake_r, False)
        # elastic re-mesh hook (ISSUE 6): set by supervisors; called with the
        # new LOCAL world size on a resizing restart and returns env
        # overrides (a shrunken KT_MESH) so the fresh ranks rebuild a mesh
        # that matches the surviving device count instead of the spawn-time N
        self.remesh_env: Optional[Any] = None
        self.watchdog = Watchdog(self)

    def _new_worker(self, local_rank: int):
        from .process_worker import ProcessWorker

        info = RankInfo(node_rank=self._node_rank, local_rank=local_rank,
                        nproc_per_node=self.num_procs,
                        num_nodes=self._num_nodes, pod_ips=self._pod_ips)
        return ProcessWorker(info, self.framework_name, self._pointers,
                             self._init_args, self._base_env)

    def start(self) -> None:
        # NOTE: often called from a worker thread (asyncio.to_thread), where
        # there is no event loop — the loop is captured on first call().
        for w in self.workers:
            w.start()
        for w in self.workers:
            self._start_router(w)
        self.watchdog.start()

    def _start_router(self, worker) -> None:
        t = threading.Thread(target=self._route_responses, args=(worker,),
                             daemon=True)
        t.start()
        self._router_threads.append(t)

    # -- restart hooks (driven by the watchdog thread only) -------------------

    def restart_worker(self, idx: int) -> None:
        """Respawn one dead rank in place (per-call-identity frameworks:
        live ranks keep serving). The old router thread exits on its own
        once the dead worker's queue is drained."""
        old = self.workers[idx]
        old.force_kill_if_alive()
        self.wake_routers()            # the old router exits now, not later
        fresh = self._new_worker(idx)
        self.workers[idx] = fresh
        fresh.start()
        self._start_router(fresh)

    def restart_all(self, exc: Optional[BaseException] = None,
                    num_procs: Optional[int] = None,
                    extra_env: Optional[Dict[str, str]] = None) -> None:
        """Full-pool respawn for spawn-fixed collective identity (JAX/TPU
        mesh): surviving ranks hold half a broken collective, so their
        in-flight futures fail with the dead rank's typed cause and every
        rank restarts together.

        ``num_procs``/``extra_env`` are the elastic re-mesh surface
        (ISSUE 6): a resize respawns the pool at the surviving N-1 world
        size, folds the coordinator's env overrides (batch scale) into the
        base env, and asks ``remesh_env`` for a mesh matching the new size
        — the fresh ranks come up as a coherent smaller world, not a
        truncated copy of the old one."""
        if exc is not None:
            self.cancel_pending(exc)
        for w in self.workers:
            w.request_shutdown()
        deadline = time.monotonic() + 2.0
        while any(w.alive for w in self.workers) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        for w in self.workers:
            w.force_kill_if_alive()
        self.wake_routers()            # retired routers exit now
        resized = num_procs is not None and num_procs != self.num_procs
        if num_procs is not None:
            self.num_procs = max(1, num_procs)
        if extra_env:
            self._base_env = {**(self._base_env or {}), **extra_env}
        if self.remesh_env is not None and (resized or extra_env):
            try:
                self._base_env = {**(self._base_env or {}),
                                  **(self.remesh_env(
                                      self.num_procs * self._num_nodes) or {})}
            except Exception:  # noqa: BLE001 — a bad hook must not stop heal
                import traceback as _tb
                print("[kt] pool remesh_env hook failed:\n" + _tb.format_exc())
        self.workers = [self._new_worker(lr) for lr in range(self.num_procs)]
        for w in self.workers:
            w.start()
        for w in self.workers:
            self._start_router(w)

    # -- response routing -----------------------------------------------------

    def wake_routers(self) -> None:
        """Write the wake byte: every blocked router re-checks stop/death
        state immediately instead of on its next (late) poll tick."""
        try:
            os.write(self._wake_w, b"w")
        except OSError:
            pass

    def _drain_wake(self) -> None:
        try:
            while os.read(self._wake_r, 4096):
                pass
        except (BlockingIOError, OSError):
            pass

    def _route_responses(self, worker) -> None:
        """Poll-free response router (ISSUE 10): blocks on the queue's
        underlying pipe AND the pool wake pipe via
        ``multiprocessing.connection.wait`` — a response wakes it the
        instant the feeder writes it, with no 5 Hz poll burning a wakeup
        (and no 0–200 ms artificial tail when a get/timeout raced the
        arrival). The 1 s timeout is a belt-and-braces heartbeat only."""
        from multiprocessing.connection import wait as mpc_wait

        reader = worker.response_q._reader
        while not self._stopping.is_set():
            try:
                if not reader.poll(0):
                    try:
                        ready = mpc_wait([reader, self._wake_r],
                                         timeout=1.0)
                    except OSError:      # wake fd reclaimed mid-teardown
                        ready = []
                    if self._wake_r in ready:
                        self._drain_wake()
                    if reader not in ready:
                        if not worker.alive:
                            self._drain_dead_queue(worker)
                            return
                        continue
                resp = worker.response_q.get_nowait()
            except (queue_mod.Empty, OSError, ValueError, EOFError):
                if not worker.alive:
                    # dead worker: ship whatever its feeder already wrote,
                    # then exit — a router thread pinned to a queue that
                    # can never produce again would leak per death for the
                    # pod's lifetime
                    self._drain_dead_queue(worker)
                    return
                continue
            self._dispatch_response(resp, worker)

    def _drain_dead_queue(self, worker) -> None:
        while True:
            try:
                resp = worker.response_q.get(timeout=0.2)
            except (queue_mod.Empty, OSError, ValueError, EOFError):
                return
            self._dispatch_response(resp, worker)

    def _dispatch_response(self, resp: Dict, worker) -> None:
        if resp.get("op") == "log":
            self._forward_log(resp, worker)
            return
        if resp.get("op") == "span":
            # finished rank-side spans (worker.execute + everything the user
            # code opened under it, e.g. store fetches) merge into THIS
            # process's ring so one /debug/traces query shows the whole
            # request; the dedup ring absorbs re-shipped trace prefixes
            from .. import telemetry
            span = resp.get("span") or {}
            fresh = telemetry.ingest_span(span)
            qwait = span.get("attrs", {}).get("queue_wait_s")
            if isinstance(qwait, (int, float)):
                telemetry.observe_stage("queue_wait", float(qwait))
            # kt_checkpoint_seconds is observed in the RANK process (where
            # Checkpointer runs) but scraped from THIS one: re-derive it
            # from the shipped span, first arrival only (prefixes re-ship)
            if fresh and span.get("name") in ("checkpoint.save",
                                              "checkpoint.restore"):
                dur = (span.get("end") or 0) - (span.get("start") or 0)
                if dur >= 0:
                    telemetry.histogram(
                        "kt_checkpoint_seconds",
                        "Checkpoint commit/restore wall-clock seconds",
                        labels=("op",),
                    ).observe(dur, op=span["name"].split(".", 1)[1])
            return
        if resp.get("op") == "state":
            # load+warmup bracket: gates /ready and shutdown escalation.
            # The two ops also bound the rank's boot (ISSUE 26): "started"
            # arrives when its worker loop runs, "done" carries its own
            # phases, and the moment "done" is seen is when this rank
            # became ready
            worker.in_warmup = resp.get("warmup") == "started"
            worker.note_boot(resp)
            return
        req_id = resp.get("req_id")
        decode_error: Optional[BaseException] = None
        if resp.get("_kt_shm"):
            # decode BEFORE the future lookup: ring slots must free in
            # queue order even when the waiter already timed out/cancelled
            from .. import telemetry
            try:
                copy = telemetry.stage("shm_copy", dir="resp")
                with copy:
                    shm_ring.decode_item_fields(
                        resp, getattr(worker, "shm_resp", None),
                        ("result",), "resp")
                # this thread is outside the request's context: the
                # copy's seconds ride the reply's own timing
                telemetry.add_timing(resp.get("timing"), "shm_copy",
                                     copy.seconds)
            except BaseException as e:  # noqa: BLE001
                decode_error = e
        with self._futures_lock:
            entry = self._futures.pop(req_id, None)
        if entry is None:
            return
        fut, _idx = entry
        if decode_error is not None:
            self._fail_future(fut, decode_error)
            return
        if self._loop is not None and not fut.done():
            self._loop.call_soon_threadsafe(self._resolve, fut, resp)

    @staticmethod
    def _forward_log(resp: Dict, worker) -> None:
        from .log_capture import LogCapture

        cap = LogCapture._global
        if cap is not None:
            cap.add(resp.get("line", ""),
                    source=f"rank{resp.get('rank', '?')}-{resp.get('source', 'stdout')}",
                    request_id=resp.get("request_id", ""),
                    trace_id=resp.get("trace_id", ""))

    @staticmethod
    def _resolve(fut: asyncio.Future, resp: Dict) -> None:
        if fut.done():
            return
        # the rank's side of the call's timeline (X-KT-Timing) travels on
        # the future to the awaiting _submit, which is in the request's
        # context and can hand it to the request's collector
        fut.kt_timing = resp.get("timing")
        if resp.get("ok"):
            fut.set_result(resp.get("result"))
        else:
            fut.set_exception(rehydrate_exception(resp["error"]))

    # -- failing futures (watchdog + shutdown paths) --------------------------

    def _fail_future(self, fut: asyncio.Future, exc: BaseException) -> None:
        if fut.done():
            return
        if self._loop is not None:
            self._loop.call_soon_threadsafe(
                lambda f=fut: (not f.done()) and f.set_exception(exc))
        else:
            # no loop ever served a call (pool set up but never hit):
            # fail synchronously so shutdown never strands a waiter
            try:
                fut.set_exception(exc)
            except Exception:  # noqa: BLE001 — e.g. already-cancelled
                pass

    def fail_worker_futures(self, idx: int, exc: BaseException) -> None:
        """Fail every in-flight future registered to rank ``idx`` — the
        watchdog's fail-fast path on observed death."""
        with self._futures_lock:
            doomed = [(rid, fut) for rid, (fut, i) in self._futures.items()
                      if i == idx]
            for rid, _ in doomed:
                self._futures.pop(rid, None)
        for _, fut in doomed:
            self._fail_future(fut, exc)

    def cancel_pending(self, exc: BaseException) -> None:
        with self._futures_lock:
            entries, self._futures = list(self._futures.values()), {}
        for fut, _idx in entries:
            self._fail_future(fut, exc)

    def raise_if_failed(self) -> None:
        """Raise the permanent typed failure after restart-budget
        exhaustion — callers (and fan-out coordinators) fail immediately
        instead of submitting into a pool that can never answer."""
        exc = self.watchdog.permanent_error()
        if exc is not None:
            raise exc

    # -- submission -----------------------------------------------------------

    async def _submit(self, idx: int, payload: Dict,
                      timeout: Optional[float]) -> Any:
        """Shared request plumbing: liveness check, future registration,
        queue submit, awaited response."""
        worker = self.workers[idx]
        self.raise_if_failed()
        if not worker.alive:
            raise self.watchdog.death_error(idx, worker)
        self._loop = asyncio.get_running_loop()
        # zero-copy envelope encode (ISSUE 10): large arrays in
        # args/kwargs move through the worker's request ring; the queue
        # item carries only {pos, len, dtype, shape, hash} headers. Done
        # BEFORE future registration so an encode failure leaks nothing.
        if getattr(worker, "shm_req", None) is not None \
                and not payload.get("no_shm"):
            threshold = shm_ring.shm_threshold()
            if threshold > 0:
                from .. import telemetry
                with telemetry.stage("shm_copy", dir="req"):
                    n_env = shm_ring.encode_item_fields(
                        payload, worker.shm_req, ("args", "kwargs"),
                        threshold, "req")
                if n_env:
                    payload["_kt_shm"] = n_env
        req_id = f"r{next(self._req_counter)}"
        fut = self._loop.create_future()
        with self._futures_lock:
            self._futures[req_id] = (fut, idx)
        # carry the HTTP request id AND the trace context across the process
        # boundary so the worker's prints stay correlated to this call in
        # the log stream and its spans join the request's trace; submit_ts
        # lets the worker measure queue-wait on its own clock axis
        from .. import telemetry
        from .http_server import request_id_var
        try:
            worker.submit({"req_id": req_id,
                           "request_id": request_id_var.get(""),
                           "trace": telemetry.current_header(),
                           "submit_ts": time.time(), **payload})
        except BaseException as e:  # noqa: BLE001
            # the worker died between the liveness check and the queue put:
            # pop the registered future (it would leak in self._futures
            # forever) and surface the typed death, not a bare queue error
            with self._futures_lock:
                self._futures.pop(req_id, None)
            raise self.watchdog.death_error(idx, worker) from e
        try:
            return await asyncio.wait_for(fut, timeout)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            # a wedged worker never answers this req_id — drop the future
            # or periodic submitters (the 3s user_metrics scrape) leak one
            # registry entry per attempt for the pod's lifetime
            with self._futures_lock:
                self._futures.pop(req_id, None)
            raise
        finally:
            telemetry.merge_timing(telemetry.current_call_timing(),
                                   getattr(fut, "kt_timing", None))

    async def call(self, idx: int, method: Optional[str], args: list,
                   kwargs: dict, timeout: Optional[float] = None,
                   dist_env: Optional[Dict[str, str]] = None) -> Any:
        def _payload(no_shm: bool = False) -> Dict[str, Any]:
            p: Dict[str, Any] = {"method": method, "args": args,
                                 "kwargs": kwargs}
            if dist_env:
                p["dist_env"] = dist_env
            if no_shm:
                p["no_shm"] = True
            return p

        try:
            return await self._submit(idx, _payload(), timeout)
        except DataCorruptionError as e:
            if getattr(e, "source", None) != "shm" \
                    or getattr(e, "key", None) != "req":
                # response-direction corruption means the call already
                # EXECUTED — blind re-execution would violate the
                # never-replay-established discipline, so it surfaces
                # typed instead
                raise
            # a request envelope failed its blake2b check in the worker
            # BEFORE any user code ran (flipped bit in the segment, chaos
            # shm-corrupt): the original arrays are intact on this side, so
            # retry ONCE over the classic queue path — garbage never
            # reaches device_put, and a persistently bad segment degrades
            # to pre-envelope behavior
            print(f"[kt] shm envelope corruption on rank {idx} "
                  f"({e}); retrying over the queue path")
            return await self._submit(idx, _payload(no_shm=True), timeout)

    def subset_env(self, local_rank: int, sel_ips: List[str],
                   sel_node_rank: int) -> Optional[Dict[str, str]]:
        """Selection-relative rank env for a worker-subset call (reference
        per-call env assembly, spmd_supervisor.py:345-364): WORLD_SIZE/RANK/
        MASTER_ADDR reflect the *selected* pods, so e.g. ``workers=[2, 5]``
        behaves as a clean 2-node world for frameworks that initialize their
        collectives inside the request. ``None`` when the framework's identity
        is fixed at spawn (JAX/TPU)."""
        from .env_contract import framework_for

        fw = framework_for(self.framework_name)
        if not fw.per_call_identity:
            return None
        info = RankInfo(node_rank=sel_node_rank, local_rank=local_rank,
                        nproc_per_node=self.num_procs,
                        num_nodes=len(sel_ips), pod_ips=list(sel_ips))
        return fw.env(info)

    async def profile(self, idx: int = 0, duration_s: float = 3.0,
                      timeout: Optional[float] = None) -> Any:
        """Capture a jax.profiler trace in rank subprocess ``idx``."""
        return await self._submit(idx, {"op": "profile",
                                        "duration_s": duration_s},
                                  timeout or duration_s + 60)

    async def user_metrics(self, idx: int = 0,
                           timeout: float = 5.0) -> Dict[str, float]:
        """Rank ``idx``'s ``__kt_metrics__`` gauges ({} when undefined) —
        merged into the pod /metrics scrape by the server."""
        return await self._submit(idx, {"op": "user_metrics"}, timeout)

    async def call_all(self, method: Optional[str], args: list, kwargs: dict,
                       timeout: Optional[float] = None,
                       subset: Optional[tuple] = None) -> List[Any]:
        """``subset=(sel_ips, sel_node_rank)`` rebinds rank identity to the
        selected pod set for this request (see :meth:`subset_env`)."""
        tasks = [self.call(i, method, args, kwargs, timeout,
                           dist_env=(self.subset_env(i, *subset)
                                     if subset else None))
                 for i in range(self.num_procs)]
        return list(await asyncio.gather(*tasks))

    # -- teardown / health ----------------------------------------------------

    def shutdown(self) -> None:
        """Stop every worker: the watchdog stops FIRST (intentional exits
        must not classify as deaths or burn the restart budget), shutdown
        ops go out to ALL workers, one shared join deadline covers them
        together (not per-worker serially), and the response routers stay
        alive until the end so a worker's ``warmup: done`` state op can
        still flip ``in_warmup`` mid-wait — the flag that decides whether
        SIGKILL escalation is allowed (a jit compile in flight must never
        be force-killed while it holds the TPU). Workers still warming get
        one shared KT_WARMUP_SHUTDOWN_GRACE window (default 600s) before
        the last-resort kill."""
        self.watchdog.stop()
        self.cancel_pending(RuntimeError("ProcessPool shutting down"))
        for w in self.workers:
            w.request_shutdown()

        def join_all(deadline: float) -> bool:
            while any(w.alive for w in self.workers):
                if time.monotonic() >= deadline:
                    return False
                time.sleep(0.1)
            return True

        done = join_all(time.monotonic() + 5.0)
        if not done and any(w.alive and w.in_warmup for w in self.workers):
            grace = float(os.environ.get("KT_WARMUP_SHUTDOWN_GRACE", "600"))
            deadline = time.monotonic() + grace
            while (time.monotonic() < deadline
                   and any(w.alive and w.in_warmup for w in self.workers)):
                time.sleep(1.0)
            # stragglers past warmup get the normal short window
            join_all(time.monotonic() + 5.0)
        self._stopping.set()
        self.wake_routers()
        for w in self.workers:
            w.force_kill_if_alive()
        # reclaim the wake pipe once every router thread has actually
        # exited — closing an fd a selector still waits on invites reuse
        # races, so a straggler (bounded dead-queue drain) keeps it open
        for t in self._router_threads:
            t.join(timeout=2.0)
        if not any(t.is_alive() for t in self._router_threads):
            for fd in (self._wake_r, self._wake_w):
                try:
                    os.close(fd)
                except OSError:
                    pass

    @property
    def healthy(self) -> bool:
        if self.watchdog.failed:
            return False
        return all(w.alive for w in self.workers)

    @property
    def recovering(self) -> bool:
        """True while the watchdog is mid-respawn — /ready flips unhealthy
        for exactly this window."""
        return self.watchdog.recovering

    @property
    def warming(self) -> bool:
        """True while any live rank is still in its load+warmup window."""
        return any(w.alive and w.in_warmup for w in self.workers)

    def boot_record(self) -> Dict[str, float]:
        """The slowest rank's boot phases (``rank_*_s``, durations) and
        ``ready_mono``: the monotonic time the last rank became ready
        (absent while any is still warming)."""
        boots = [getattr(w, "boot", {}) for w in self.workers]
        out = dict(max(boots, key=lambda b: sum(
            v for k, v in b.items() if k.endswith("_s")), default={}))
        out.pop("ready_mono", None)
        if boots and all("ready_mono" in b for b in boots):
            out["ready_mono"] = max(b["ready_mono"] for b in boots)
        return out

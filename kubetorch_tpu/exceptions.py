"""Typed exception taxonomy for kubetorch-tpu.

The reference surfaces infrastructure failures as typed Python exceptions that
the client can catch programmatically (reference:
``python_client/kubetorch/resources/compute/utils.py:57-157`` for launch
failures, ``serving/utils.py:111-264`` for pod-termination and membership
faults, ``serving/http_client.py:87-194`` for cross-process rehydration).

This module is the TPU-native re-design of that surface:

- the launch taxonomy is kept (image pulls, quota, health, timeouts) because it
  is Kubernetes-level, not accelerator-level;
- the termination taxonomy adds first-class **TPU preemption** (GKE spot /
  maintenance events) and **HBM OOM** flags, which replace the reference's
  CUDA-centric OOMKilled-only view;
- every exception is registered in :data:`EXCEPTION_REGISTRY` so the HTTP
  client can rehydrate the *same type* on the caller's side, preserving
  ``except kt.PodTerminatedError`` ergonomics across the wire.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional


class KubetorchError(Exception):
    """Base for every kubetorch-tpu exception."""


# ---------------------------------------------------------------------------
# Launch / provisioning failures (reference resources/compute/utils.py:57-157)
# ---------------------------------------------------------------------------


class ImagePullError(KubetorchError):
    """Container image could not be pulled (bad tag, missing pull secret)."""


class ResourceNotAvailableError(KubetorchError):
    """Cluster cannot satisfy the resource request (quota, no TPU slice free)."""


class TpuSliceUnavailableError(ResourceNotAvailableError):
    """No TPU slice of the requested topology is schedulable.

    TPU slices are atomic units (a v5p-64 is 8 hosts that must co-schedule);
    this carries the topology so callers can programmatically fall back to a
    smaller slice.
    """

    def __init__(self, message: str, accelerator: Optional[str] = None, topology: Optional[str] = None):
        super().__init__(message)
        self.accelerator = accelerator
        self.topology = topology


class StartupError(KubetorchError):
    """Deploy-time startup failure (reference ``serving/utils.py``
    StartupError): base for the health/timeout variants so callers can
    catch every way a ``.to()`` fails to produce a serving pod."""


class ServiceHealthError(StartupError):
    """Service came up but failed its health probe."""


class ServiceTimeoutError(StartupError):
    """Service did not become ready within the launch timeout."""


class AcceleratorUnavailableError(StartupError):
    """A rank that was given the TPU could not open it.

    The pod's environment names the ``tpu`` platform first in
    ``JAX_PLATFORMS`` (the local backend sets that for every pod whose
    ``Compute`` asks for a TPU), but jax in the rank came up on another
    backend or failed to initialize. The rank fails its load with this
    instead of serving from the CPU. ``backend`` is what jax reported
    (None when initialization itself raised)."""

    def __init__(self, message: str, backend: Optional[str] = None):
        super().__init__(message)
        self.backend = backend


class SecretNotFound(KubetorchError):
    """Named Secret does not exist in the cluster (reference
    ``compute/utils.py`` SecretNotFound)."""


class KubernetesCredentialsError(KubetorchError):
    """kubectl missing or cluster credentials unusable (reference
    ``provisioning/utils.py`` KubernetesCredentialsError)."""


class PodContainerError(KubetorchError):
    """A container in the workload pod crashed or errored during launch."""


class VersionMismatchError(KubetorchError):
    """Client and in-cluster server versions are incompatible."""


class ControllerRequestError(KubetorchError):
    """The controller rejected or failed a request."""

    def __init__(self, message: str, status_code: Optional[int] = None):
        super().__init__(message)
        self.status_code = status_code


class SyncError(KubetorchError):
    """Code/data synchronisation to or from the cluster failed.

    Replaces the reference's ``RsyncError`` — this framework ships its own
    content-hash delta-sync protocol rather than shelling out to rsync.
    """


class SerializationError(KubetorchError):
    """Payload could not be (de)serialized, or format not in the allowlist."""


class DataStoreError(KubetorchError):
    """Data-store operation (put/get/ls/rm/broadcast) failed."""


class StoreFullError(DataStoreError):
    """The data store's disk is full (ENOSPC/EDQUOT mid-write → HTTP 507).

    Non-retryable by design: a 507 is a capacity verdict, not a transient
    blip — retrying would hammer a full disk. Callers should free space
    (``POST /gc``, ``kt.rm``) or grow the volume; see the operations
    runbook. ``path`` is the server-side file that failed, when known.
    """

    def __init__(self, message: str = "data store is out of disk space",
                 path: Optional[str] = None):
        super().__init__(message)
        self.path = path


class RingEpochMismatch(DataStoreError):
    """The client's view of the store ring is stale (HTTP 409).

    Every data-plane request carries the ``X-KT-Ring-Epoch`` the client
    routed with; a store node whose membership epoch moved on rejects the
    request *before* touching disk, because a stale router may have hashed
    the key onto the wrong replica set. Retryable by design: the client
    refreshes the ring from ``/ring`` and re-routes — ``ring.request``
    absorbs the whole cycle transparently, so callers only ever see this
    when refresh itself keeps failing. ``expected`` is the server's epoch,
    ``actual`` the stale one the client sent.
    """

    def __init__(self, message: str = "store ring epoch mismatch",
                 expected: Optional[int] = None,
                 actual: Optional[int] = None):
        super().__init__(message)
        self.expected = expected
        self.actual = actual


class DataCorruptionError(DataStoreError):
    """Fetched bytes do not match their content address.

    The data plane is content-addressed end to end (blob names and kv meta
    both carry blake2b-160), so every GET is verifiable for free. The
    client raises this instead of handing corrupt weights to a training
    loop; the P2P fetcher additionally *repairs* — it evicts the corrupt
    source (local cache entry or peer via ``/route/failed``) and re-fetches
    from the origin before surfacing anything. Server-side, the scrubber
    quarantines the mismatched file so the next GET is a clean 404.
    """

    def __init__(self, message: str = "content hash mismatch on fetch",
                 key: Optional[str] = None, expected: Optional[str] = None,
                 actual: Optional[str] = None, source: Optional[str] = None):
        super().__init__(message)
        self.key = key
        self.expected = expected
        self.actual = actual
        self.source = source


class RolloutError(KubetorchError):
    """A live weight rollout refused to swap (ISSUE 11).

    Raised by ``serve/rollout.py`` — the only weight-swap site — when a
    staged delta fails its bit-equality gate (index/manifest fingerprint
    mismatch, a leaf whose shape/dtype no longer matches the engine's
    compiled step, or a manifest pointing at weights the store no longer
    holds). The engine's live params are untouched whenever this raises:
    every check runs BEFORE the batch-boundary swap, so a bad manifest
    can never leave a replica mixed-version."""

    def __init__(self, message: str = "weight rollout refused",
                 reason: Optional[str] = None,
                 version: Optional[int] = None,
                 expected: Optional[str] = None,
                 actual: Optional[str] = None):
        super().__init__(message)
        self.reason = reason
        self.version = version
        self.expected = expected
        self.actual = actual


class UnsupportedMechanismError(KubetorchError, NotImplementedError):
    """A serving mechanism that a model family's cache kind does not carry
    yet (ISSUE 33).

    Raised where the mechanism is asked for — at ``GenerationEngine``
    construction for an option (``quantize_kv``, ``prefill_chunk``,
    ``auto_prefix``, an AOT cache, a sharded mesh, ``SpeculativeEngine``),
    at the call for a method (``register_prefix``, ``register_adapter``,
    ``models.generate.generate``) — so nothing runs silently wrong.
    ``mechanism`` names what was asked for and ``cache_kind`` the cache that
    lacks it (``"latent"``: the MLA family of ``models.mla``)."""

    def __init__(self, mechanism: str, cache_kind: str = "latent",
                 hint: str = ""):
        super().__init__(
            f"{mechanism} is not served over a {cache_kind} attention cache "
            f"yet{': ' + hint if hint else ''}")
        self.mechanism = mechanism
        self.cache_kind = cache_kind


class AOTCacheMissError(KubetorchError):
    """The persistent AOT compile cache holds no entry for this key
    (ISSUE 16).

    Raised by ``serve/aot_cache.py`` — the only compile-path entry in
    ``serve/`` — when an engine asks for a serialized executable the cache
    has never seen: a genuinely new ``(model config, mesh shape, bucket
    set, jax/backend version)`` tuple, or a key component that moved
    (version upgrade, mesh reshape, bucket change). Always recoverable:
    the caller traces + compiles fresh and publishes the result, so the
    fleet pays the compile exactly once per distinct key. ``reason``
    distinguishes ``absent`` (never compiled) from ``incompatible``
    (an entry exists for the name but under a different key digest)."""

    def __init__(self, message: str = "AOT compile cache miss",
                 key: Optional[str] = None, name: Optional[str] = None,
                 reason: str = "absent"):
        super().__init__(message)
        self.key = key
        self.name = name
        self.reason = reason


class AOTCacheCorruptError(AOTCacheMissError):
    """A cached AOT executable failed its content check (ISSUE 16).

    The payload's blake2b did not match the digest recorded at publish
    time, or deserialization itself refused the bytes. Semantically a
    MISS — the caller falls back to a fresh trace + compile and republishes
    — but counted separately (``kt_aot_cache_total{result="corrupt"}``)
    because a corrupt entry means bit-rot or a torn write, never a
    version skew. A wrong or stale executable is never returned: the hash
    gate runs before ``deserialize_and_load`` ever sees the bytes."""

    def __init__(self, message: str = "AOT cache entry corrupt",
                 key: Optional[str] = None, name: Optional[str] = None,
                 expected: Optional[str] = None,
                 actual: Optional[str] = None):
        super().__init__(message, key=key, name=name, reason="corrupt")
        self.expected = expected
        self.actual = actual


class StaleLeaseError(KubetorchError):
    """A placement attempt carried a fenced-off lease epoch (ISSUE 13).

    The federation's global scheduler (``federation/scheduler.py``) grants
    every cross-region placement a ``(region, epoch)`` lease and bumps the
    epoch on every re-grant — including the automatic migrate-and-resume
    that follows a region death. A controller that was partitioned away
    while its region was declared Dead still *believes* it holds the
    workload; when the partition heals and it tries to confirm or act on
    that placement, its stale epoch is rejected with this error instead of
    silently double-placing the workload next to the migrated copy. The
    stale side's only valid move is to tear its local placement down.
    ``current_epoch``/``current_region`` name the lease that actually
    holds."""

    def __init__(self, message: str = "placement lease epoch is stale",
                 workload: Optional[str] = None,
                 region: Optional[str] = None,
                 epoch: Optional[int] = None,
                 current_epoch: Optional[int] = None,
                 current_region: Optional[str] = None):
        super().__init__(message)
        self.workload = workload
        self.region = region
        self.epoch = epoch
        self.current_epoch = current_epoch
        self.current_region = current_region


class StaleStageEpochError(KubetorchError):
    """A pipeline stage acted under a fenced-off membership epoch (ISSUE 17).

    Elastic pipeline parallelism (``parallel/pipeline_elastic.py``) stamps
    every stage gang with a membership epoch and bumps it on every
    re-group — a stage death, a straggler demotion, a partial-gang
    preemption. A zombie stage from before the re-group (SIGSTOPped, GC
    paused, or just slow) that wakes up and tries to confirm its
    assignment or publish a boundary activation is refused with this
    error instead of silently double-driving layers the survivors already
    absorbed. The stale side's only valid move is to exit; the membership
    brain has already re-placed its layer shard. ``current_epoch`` names
    the membership that actually holds."""

    def __init__(self, message: str = "stage membership epoch is stale",
                 job: Optional[str] = None,
                 stage: Optional[int] = None,
                 epoch: Optional[int] = None,
                 current_epoch: Optional[int] = None):
        super().__init__(message)
        self.job = job
        self.stage = stage
        self.epoch = epoch
        self.current_epoch = current_epoch


class SloBurnAlert(KubetorchError):
    """A fleet stage is burning its SLO error budget too fast (ISSUE 20).

    Emitted by the fleet aggregator (``obs/fleet.py``) — the only
    burn-rate computation site — when a stage's multi-window burn rate
    crosses the alert threshold: ``burn_rate`` is the rate at which the
    error budget is being spent (1.0 = exactly sustainable; 14.4 on the
    fast window is the classic page-now rate), ``window`` names which
    window tripped (``fast``/``slow``), ``slo_s`` the latency threshold
    that defines a "bad" request and ``target`` the availability
    objective. Registered + rehydratable so ``/fleet/alerts`` consumers
    get the same type the controller raised, not a dict."""

    def __init__(self, message: str = "SLO burn-rate alert",
                 stage: Optional[str] = None, window: Optional[str] = None,
                 burn_rate: Optional[float] = None,
                 threshold: Optional[float] = None,
                 slo_s: Optional[float] = None,
                 target: Optional[float] = None,
                 at: Optional[float] = None):
        super().__init__(message)
        self.stage = stage
        self.window = window
        self.burn_rate = burn_rate
        self.threshold = threshold
        self.slo_s = slo_s
        self.target = target
        self.at = at


class PodUnreachableError(KubetorchError):
    """A pod that should be serving did not answer (ISSUE 20 satellite).

    Raised by surfaces that query a live pod (``kt trace``) when the
    connection itself fails — the pod is dead, restarting, or partitioned.
    Carries the black-box spool hint: a dead pod's last telemetry interval
    survives in its flight-recorder spool (``KT_OBS_SPOOL``), so the
    actionable next step is ``kt blackbox <spool_dir>``, not a retry."""

    def __init__(self, message: str = "pod is unreachable",
                 url: Optional[str] = None,
                 spool_hint: Optional[str] = None):
        super().__init__(message)
        self.url = url
        self.spool_hint = spool_hint


class DebuggerError(KubetorchError):
    """Remote debugger attach/session failure."""


class DeadlineExceededError(KubetorchError):
    """The request's propagated deadline (``X-KT-Deadline``) passed.

    Raised client-side when the retry budget runs out against the deadline,
    and server-side (rehydratable) when a request arrives past — or runs
    past — its deadline: the server refuses to burn a TPU slot on a request
    the client already abandoned. ``deadline`` is the absolute unix time
    that was exceeded.
    """

    def __init__(self, message: str = "Request deadline exceeded",
                 deadline: Optional[float] = None):
        super().__init__(message)
        self.deadline = deadline


class CircuitOpenError(KubetorchError):
    """A circuit breaker is open: the target has failed repeatedly and calls
    are being rejected locally until the cool-down elapses. ``retry_after``
    is the seconds remaining until the breaker half-opens."""

    def __init__(self, message: str = "Circuit breaker is open",
                 retry_after: Optional[float] = None):
        super().__init__(message)
        self.retry_after = retry_after


class AdmissionShedError(KubetorchError):
    """The serving front door shed this request at admission (HTTP 429).

    Raised by ``serving/router.py`` BEFORE any prefill compute runs: the
    bounded admission queue was full (lowest priority tier sheds first) or
    the request's propagated ``X-KT-Deadline`` cannot be met by the
    estimated queue wait — a doomed request is refused at the door instead
    of burning a decode slot on an answer the client will never read.
    ``reason`` is ``queue_full`` or ``doomed``; ``retry_after`` is the
    router's backpressure hint in seconds.
    """

    def __init__(self, message: str = "Request shed at admission",
                 reason: Optional[str] = None, tier: Optional[str] = None,
                 queue_depth: Optional[int] = None,
                 retry_after: Optional[float] = None):
        super().__init__(message)
        self.reason = reason
        self.tier = tier
        self.queue_depth = queue_depth
        self.retry_after = retry_after


# ---------------------------------------------------------------------------
# Runtime faults (reference serving/utils.py:111-264)
# ---------------------------------------------------------------------------


class PodTerminatedError(KubetorchError):
    """The pod serving the request was terminated mid-flight.

    Reference parses OOMKilled/Evicted from container status
    (``serving/utils.py:111-191``). The TPU rebuild adds ``preempted`` (GKE
    spot reclaim / TPU maintenance — surfaced via the graceful-termination
    signal) and ``hbm_oom`` (device out-of-memory from libtpu/XLA, which is a
    *process* fault rather than a cgroup kill and therefore invisible to the
    reference's design).
    """

    def __init__(
        self,
        message: str = "Pod was terminated while handling the request",
        reason: Optional[str] = None,
        pod_name: Optional[str] = None,
        exit_code: Optional[int] = None,
    ):
        super().__init__(message)
        self.reason = reason
        self.pod_name = pod_name
        self.exit_code = exit_code

    @property
    def oom_killed(self) -> bool:
        return self.reason == "OOMKilled"

    @property
    def evicted(self) -> bool:
        return self.reason == "Evicted"

    @property
    def preempted(self) -> bool:
        return self.reason in ("Preempted", "TPUMaintenance", "SpotReclaim")

    @property
    def hbm_oom(self) -> bool:
        return self.reason == "HbmOom"


class HbmOomError(PodTerminatedError):
    """XLA failed to allocate on-device (HBM) memory.

    Raised when a RESOURCE_EXHAUSTED from the TPU runtime is detected in a
    worker process; carries the requested/available bytes when parseable so
    clients can programmatically shrink batch size and retry.
    """

    def __init__(self, message: str, requested_bytes: Optional[int] = None, available_bytes: Optional[int] = None):
        super().__init__(message, reason="HbmOom")
        self.requested_bytes = requested_bytes
        self.available_bytes = available_bytes


class WorkerMembershipChanged(KubetorchError):
    """The set of worker pods changed during a distributed call.

    Mirrors reference ``serving/utils.py:193-264``: carries added/removed IPs
    and criticality so the client can resize (``.distribute(workers=N-1)``)
    and redeploy — the elastic-recovery recipe. On TPU an XLA-compiled mesh
    cannot shrink in place, so this exception *is* the resize trigger.

    ``resumable`` (ISSUE 6) downgrades the event from fan-out-fatal to a
    recoverable signal: when the serving side has an elastic policy
    attached, the supervisor re-meshes to the surviving ranks, resumes from
    the last committed checkpoint, and retries — the client never has to
    orchestrate the resize itself.
    """

    def __init__(
        self,
        message: str = "Worker membership changed during execution",
        added: Optional[List[str]] = None,
        removed: Optional[List[str]] = None,
        previous: Optional[List[str]] = None,
        current: Optional[List[str]] = None,
        resumable: bool = False,
    ):
        super().__init__(message)
        self.added = added or []
        self.removed = removed or []
        self.previous = previous or []
        self.current = current or []
        self.resumable = resumable

    @property
    def is_critical(self) -> bool:
        """Removed workers always invalidate an SPMD mesh; additions do not."""
        return bool(self.removed)


class WorkerCallError(KubetorchError):
    """A fanned-out subcall to a worker pod failed; wraps the remote error."""

    def __init__(self, message: str, worker: Optional[str] = None):
        super().__init__(message)
        self.worker = worker


class WorkerDiedError(KubetorchError):
    """A rank *subprocess* died while (or before) handling a call.

    The process-level sibling of :class:`PodTerminatedError`: the pod is
    fine, but the subprocess that owns the TPU chips is gone. Raised
    fail-fast by the liveness watchdog (``serving/watchdog.py``) the moment
    the death is observed — bounded by ``KT_WATCHDOG_INTERVAL_S``, never by
    the call timeout — with the classified cause attached:

    - ``OOMKilled``  — SIGKILL with cgroup OOM evidence (host memory)
    - ``Evicted``    — SIGTERM while the pod is draining (kubelet eviction)
    - ``Preempted``  — SIGTERM under a GKE spot-reclaim / maintenance marker
    - ``Crashed``    — SIGSEGV/SIGABRT/… or a nonzero exit (user/XLA crash)
    - ``Killed``     — SIGKILL without OOM evidence (external kill)
    - ``Exited``     — clean exit 0 without a shutdown request

    ``rank`` is the local rank index, ``exitcode`` the raw
    ``multiprocessing.Process.exitcode`` (negative = signal number).
    """

    def __init__(self, message: str = "Rank subprocess died",
                 cause: Optional[str] = None, rank: Optional[int] = None,
                 exitcode: Optional[int] = None):
        super().__init__(message)
        self.cause = cause
        self.rank = rank
        self.exitcode = exitcode

    @property
    def oom_killed(self) -> bool:
        return self.cause == "OOMKilled"

    @property
    def evicted(self) -> bool:
        return self.cause == "Evicted"

    @property
    def preempted(self) -> bool:
        return self.cause == "Preempted"

    @property
    def crashed(self) -> bool:
        return self.cause == "Crashed"


# ---------------------------------------------------------------------------
# Cross-process rehydration (reference serving/http_client.py:87-194)
# ---------------------------------------------------------------------------

EXCEPTION_REGISTRY: Dict[str, type] = {
    cls.__name__: cls
    for cls in (
        KubetorchError,
        StartupError,
        AcceleratorUnavailableError,
        SecretNotFound,
        KubernetesCredentialsError,
        ImagePullError,
        ResourceNotAvailableError,
        TpuSliceUnavailableError,
        ServiceHealthError,
        ServiceTimeoutError,
        PodContainerError,
        VersionMismatchError,
        ControllerRequestError,
        SyncError,
        SerializationError,
        DataStoreError,
        StoreFullError,
        RingEpochMismatch,
        DataCorruptionError,
        RolloutError,
        StaleLeaseError,
        StaleStageEpochError,
        SloBurnAlert,
        PodUnreachableError,
        DebuggerError,
        DeadlineExceededError,
        CircuitOpenError,
        AdmissionShedError,
        PodTerminatedError,
        HbmOomError,
        WorkerMembershipChanged,
        WorkerCallError,
        WorkerDiedError,
    )
}

# Keyword-only attrs each registered type accepts beyond the message, used to
# round-trip structured fields through :func:`package_exception`.
_STRUCTURED_ATTRS: Dict[str, List[str]] = {
    "TpuSliceUnavailableError": ["accelerator", "topology"],
    "AcceleratorUnavailableError": ["backend"],
    "ControllerRequestError": ["status_code"],
    "StoreFullError": ["path"],
    "RingEpochMismatch": ["expected", "actual"],
    "DataCorruptionError": ["key", "expected", "actual", "source"],
    "RolloutError": ["reason", "version", "expected", "actual"],
    "StaleLeaseError": ["workload", "region", "epoch", "current_epoch",
                        "current_region"],
    "StaleStageEpochError": ["job", "stage", "epoch", "current_epoch"],
    "SloBurnAlert": ["stage", "window", "burn_rate", "threshold", "slo_s",
                     "target", "at"],
    "PodUnreachableError": ["url", "spool_hint"],
    "DeadlineExceededError": ["deadline"],
    "CircuitOpenError": ["retry_after"],
    "AdmissionShedError": ["reason", "tier", "queue_depth", "retry_after"],
    "PodTerminatedError": ["reason", "pod_name", "exit_code"],
    "HbmOomError": ["requested_bytes", "available_bytes"],
    "WorkerMembershipChanged": ["added", "removed", "previous", "current",
                                "resumable"],
    "WorkerCallError": ["worker"],
    "WorkerDiedError": ["cause", "rank", "exitcode"],
}


def package_exception(exc: BaseException) -> Dict[str, Any]:
    """Flatten an exception into a JSON-safe dict for the wire.

    Mirrors reference ``serving/http_server.py:1478-1530`` but also captures
    the structured attrs of registered types so rehydration is lossless.
    """
    import traceback as _tb

    name = type(exc).__name__
    data: Dict[str, Any] = {
        "error_type": name,
        "module": type(exc).__module__,
        "message": str(exc),
        "traceback": "".join(_tb.format_exception(type(exc), exc, exc.__traceback__)),
    }
    attrs = {}
    for attr in _STRUCTURED_ATTRS.get(name, []):
        val = getattr(exc, attr, None)
        if val is not None:
            attrs[attr] = val
    if attrs:
        data["attrs"] = attrs
    return data


def rehydrate_exception(data: Dict[str, Any]) -> BaseException:
    """Reconstruct an exception from :func:`package_exception` output.

    Resolution order (reference ``http_client.py:87-194``): a registered
    kubetorch type (with structured attrs), then a Python builtin, then a
    dynamically created subclass of :class:`KubetorchError` whose ``__str__``
    carries the remote traceback.
    """
    import builtins

    name = data.get("error_type", "Exception")
    message = data.get("message", "")
    remote_tb = data.get("traceback", "")
    attrs = data.get("attrs", {})

    if name in EXCEPTION_REGISTRY:
        cls = EXCEPTION_REGISTRY[name]
        try:
            exc = cls(message, **attrs)
        except TypeError:
            exc = cls(message)
        exc.remote_traceback = remote_tb  # type: ignore[attr-defined]
        return exc

    builtin = getattr(builtins, name, None)
    if isinstance(builtin, type) and issubclass(builtin, BaseException):
        try:
            exc = builtin(message)
        except TypeError:
            exc = Exception(f"{name}: {message}")
        exc.remote_traceback = remote_tb  # type: ignore[attr-defined]
        return exc

    # Unknown remote type: synthesize a subclass carrying the traceback.
    dynamic = type(name, (KubetorchError,), {
        "__str__": lambda self: f"{message}\n\nRemote traceback:\n{remote_tb}",
    })
    exc = dynamic(message)
    exc.remote_traceback = remote_tb  # type: ignore[attr-defined]
    return exc


def detect_hbm_oom(exc: BaseException) -> Optional[HbmOomError]:
    """Map an XLA RESOURCE_EXHAUSTED error to :class:`HbmOomError`, else None.

    XLA raises ``XlaRuntimeError: RESOURCE_EXHAUSTED: ... Attempting to
    allocate X. ... available Y`` on HBM exhaustion. We match on the message
    because the exception type lives in jaxlib and we must not import jax in
    every process that handles errors.
    """
    import re

    msg = str(exc)
    if "RESOURCE_EXHAUSTED" not in msg and "Out of memory allocating" not in msg:
        return None
    req = avail = None
    m = re.search(r"[Aa]llocat\w*\s+([\d.]+)\s*([KMGT]?i?B)", msg)
    if m:
        req = _parse_bytes(m.group(1), m.group(2))
    m = re.search(r"available[:\s]+([\d.]+)\s*([KMGT]?i?B)", msg)
    if m:
        avail = _parse_bytes(m.group(1), m.group(2))
    return HbmOomError(msg, requested_bytes=req, available_bytes=avail)


def _parse_bytes(num: str, unit: str) -> int:
    mult = {"B": 1, "KB": 10**3, "MB": 10**6, "GB": 10**9, "TB": 10**12,
            "KIB": 2**10, "MIB": 2**20, "GIB": 2**30, "TIB": 2**40}
    return int(float(num) * mult.get(unit.upper(), 1))

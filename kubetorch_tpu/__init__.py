"""kubetorch-tpu: a TPU-native compute-dispatch and serving fabric.

A ground-up rebuild of the capabilities of run-house/kubetorch (reference
mounted at /root/reference) designed for TPU pods on GKE: ``kt.fn(train).to(
kt.Compute(tpu="v5p-64"))`` provisions a TPU slice, syncs your working
directory in ~1-2s, hot-reloads code without pod restarts, and exposes the
function as an HTTP service with JAX-SPMD fan-out, device-mesh parallelism
(DP/FSDP/TP/SP/EP/CP) as a launcher-level concern, log/metric/exception
propagation, a P2P data store with ICI-collective tensor transfer, autoscaling
and fault surfacing (TPU preemption / HBM OOM) as typed exceptions.

Import is lazy: ``import kubetorch_tpu as kt`` never imports jax — device
libraries load only in the worker processes that need them.
"""

from __future__ import annotations

__version__ = "0.1.0"

from .exceptions import (  # noqa: F401
    KubetorchError,
    StartupError,
    SecretNotFound,
    KubernetesCredentialsError,
    ImagePullError,
    ResourceNotAvailableError,
    TpuSliceUnavailableError,
    AcceleratorUnavailableError,
    ServiceHealthError,
    ServiceTimeoutError,
    PodContainerError,
    VersionMismatchError,
    ControllerRequestError,
    SyncError,
    SerializationError,
    DataStoreError,
    StoreFullError,
    DataCorruptionError,
    DebuggerError,
    DeadlineExceededError,
    CircuitOpenError,
    PodTerminatedError,
    HbmOomError,
    WorkerMembershipChanged,
    WorkerCallError,
    WorkerDiedError,
    StaleStageEpochError,
)
from .config import config, KTConfig  # noqa: F401

_LAZY = {
    # user-facing API (reference python_client/kubetorch/__init__.py surface)
    "Compute": ".resources.compute",
    "Image": ".resources.image",
    "images": ".resources.images",
    "Volume": ".resources.volume",
    "Secret": ".resources.secret",
    "secret": ".resources.secret",
    "RetryPolicy": ".resilience",
    "CircuitBreaker": ".resilience",
    "Deadline": ".resilience",
    "MetricsConfig": ".config",
    "LoggingConfig": ".config",
    "DebugConfig": ".config",
    "Endpoint": ".resources.endpoint",
    "fn": ".resources.fn",
    "Fn": ".resources.fn",
    "cls": ".resources.cls",
    "Cls": ".resources.cls",
    "app": ".resources.app",
    "App": ".resources.app",
    "actors": ".resources.actors",
    "ActorMesh": ".resources.actors",
    "compute": ".resources.decorators",
    "distribute": ".resources.decorators",
    "autoscale": ".resources.decorators",
    "async_": ".resources.decorators",
    "AutoscalingConfig": ".resources.autoscaling",
    "put": ".data_store.commands",
    "get": ".data_store.commands",
    "ls": ".data_store.commands",
    "rm": ".data_store.commands",
    "BroadcastWindow": ".data_store.types",
    "distributed": ".serving.distributed_env",
    # user-facing breakpoint hook (reference serving/utils.deep_breakpoint)
    "kt_breakpoint": ".serving.pdb_ws",
    "deep_breakpoint": ".serving.pdb_ws",
    "MeshSpec": ".parallel.mesh",
    # elastic SPMD (ISSUE 6): the policy users attach via
    # .distribute(elastic={...}), the in-step drain poll for cooperative
    # preemption, and the commit-marked checkpointer behind resume
    "ElasticPolicy": ".serving.elastic",
    "drain_requested": ".serving.elastic",
    "batch_scale": ".serving.elastic",
    "Checkpointer": ".train.checkpoint",
    # elastic pipeline parallelism (ISSUE 17): the membership authority a
    # multi-pod pipeline job shares with its supervisor — stage spans,
    # epoch-fenced re-grouping, activation keys
    "ElasticPipeline": ".parallel.pipeline_elastic",
    "PipelineMembership": ".parallel.pipeline_elastic",
    "StageAssignment": ".parallel.pipeline_elastic",
    "PipelineSupervisor": ".serving.pipeline_supervisor",
    # module-valued: kt.models.load_hf / kt.models.LlamaConfig (the HF
    # migration surface); resolved to the module itself by __getattr__
    "models": ".models",
    # module-valued: kt.telemetry.span / kt.telemetry.counter — the
    # user-facing half of the tracing + metrics plane (ISSUE 5): user code
    # can open spans inside a traced request and register its own series
    "telemetry": ".telemetry",
}


def __getattr__(name: str):
    mod_path = _LAZY.get(name)
    if mod_path is None:
        raise AttributeError(f"module 'kubetorch_tpu' has no attribute {name!r}")
    import importlib
    try:
        mod = importlib.import_module(mod_path, __name__)
    except ImportError as e:
        # Module-__getattr__ convention: surface AttributeError so hasattr()
        # and dir()-driven tooling keep working.
        raise AttributeError(f"kubetorch_tpu.{name} unavailable: {e}") from e
    # module-valued entries (e.g. "models" → .models) resolve to the module
    # itself; everything else to the module's same-named attribute
    val = mod if mod_path.lstrip(".").split(".")[-1] == name \
        and not hasattr(mod, name) else getattr(mod, name)
    globals()[name] = val
    return val


def __dir__():
    return sorted(set(globals()) | set(_LAZY))

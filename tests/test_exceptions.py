"""Exception packaging/rehydration across the wire (reference
serving/http_client.py:87-194, http_server.py:1478-1530)."""

import pytest

from kubetorch_tpu import exceptions as exc

# Synthetic values for every structured attr in the registry, typed to match
# each constructor's expectation — the whole-registry round-trip below breaks
# loudly when someone adds an attr without a sample here.
_ATTR_SAMPLES = {
    "backend": "cpu",
    "accelerator": "v5p-64",
    "topology": "4x4x4",
    "status_code": 503,
    "reason": "Evicted",
    "pod_name": "pod-3",
    "exit_code": 137,
    "requested_bytes": 8 << 30,
    "available_bytes": 1 << 30,
    "added": ["10.0.0.9"],
    "removed": ["10.0.0.3"],
    "resumable": True,
    "previous": ["10.0.0.3"],
    "current": ["10.0.0.9"],
    "worker": "10.0.0.7",
    "deadline": 1722787200.25,
    "retry_after": 2.5,
    "tier": "batch",
    "queue_depth": 17,
    "cause": "OOMKilled",
    "rank": 2,
    "exitcode": -9,
    "path": "/data/blobs/ab/abcdef",
    "key": "ckpt/step100/layers/wq",
    "version": 7,
    "expected": "aa" * 20,
    "actual": "bb" * 20,
    "source": "peer",
    # StaleLeaseError (ISSUE 13 federation lease fencing)
    "workload": "ns/train-llama",
    "region": "iowa",
    "epoch": 3,
    "current_epoch": 4,
    "current_region": "oregon",
    # StaleStageEpochError (ISSUE 17 pipeline membership fencing)
    "job": "train-llama",
    "stage": 2,
    # SloBurnAlert (ISSUE 20 fleet SLO burn rollup)
    "window": "fast",
    "burn_rate": 16.2,
    "threshold": 14.4,
    "slo_s": 0.25,
    "target": 0.99,
    "at": 1722787200.25,
    # PodUnreachableError (ISSUE 20 dead-pod surfaces)
    "url": "http://10.0.0.7:8080",
    "spool_hint": "/var/kt/spool/rank-123",
}


@pytest.mark.parametrize("name", sorted(exc.EXCEPTION_REGISTRY))
def test_whole_registry_roundtrip(name):
    """package → rehydrate preserves type, message, and every structured
    attr, for EVERY registered exception — the wire contract the resilience
    layer (and every `except kt.X` user) depends on."""
    cls = exc.EXCEPTION_REGISTRY[name]
    attrs = {a: _ATTR_SAMPLES[a] for a in exc._STRUCTURED_ATTRS.get(name, [])}
    # HbmOomError pins reason="HbmOom" internally; its ctor has no reason kwarg
    if name == "HbmOomError":
        attrs.pop("reason", None)
    original = cls(f"{name} message", **attrs)
    out = exc.rehydrate_exception(exc.package_exception(original))
    assert type(out) is cls
    assert str(out) == f"{name} message"
    for attr in exc._STRUCTURED_ATTRS.get(name, []):
        assert getattr(out, attr) == getattr(original, attr), attr
    assert hasattr(out, "remote_traceback")


def test_structured_attrs_all_registered():
    """Every _STRUCTURED_ATTRS key must name a registered type (a rename in
    one table but not the other silently drops attrs on the wire)."""
    assert set(exc._STRUCTURED_ATTRS) <= set(exc.EXCEPTION_REGISTRY)


def test_deadline_exceeded_roundtrip():
    out = exc.rehydrate_exception(exc.package_exception(
        exc.DeadlineExceededError("too late", deadline=123.5)))
    assert isinstance(out, exc.DeadlineExceededError)
    assert out.deadline == 123.5


def test_roundtrip_registered_type():
    try:
        raise exc.PodTerminatedError("pod died", reason="OOMKilled", pod_name="p-0", exit_code=137)
    except exc.PodTerminatedError as e:
        data = exc.package_exception(e)
    out = exc.rehydrate_exception(data)
    assert isinstance(out, exc.PodTerminatedError)
    assert out.oom_killed and not out.evicted
    assert out.pod_name == "p-0" and out.exit_code == 137
    assert "pod died" in str(out)
    assert "test_roundtrip_registered_type" in out.remote_traceback


def test_tpu_preemption_flags():
    e = exc.PodTerminatedError("preempted", reason="SpotReclaim")
    assert e.preempted and not e.oom_killed
    out = exc.rehydrate_exception(exc.package_exception(e))
    assert out.preempted


def test_membership_changed_roundtrip():
    e = exc.WorkerMembershipChanged(added=["10.0.0.9"], removed=["10.0.0.3"],
                                    previous=["10.0.0.3"], current=["10.0.0.9"])
    out = exc.rehydrate_exception(exc.package_exception(e))
    assert isinstance(out, exc.WorkerMembershipChanged)
    assert out.removed == ["10.0.0.3"] and out.is_critical


def test_builtin_rehydration():
    data = exc.package_exception(ValueError("bad value"))
    out = exc.rehydrate_exception(data)
    assert isinstance(out, ValueError)
    assert str(out) == "bad value"


def test_unknown_type_dynamic_subclass():
    data = {"error_type": "SomeUserError", "message": "boom", "traceback": "tb-here"}
    out = exc.rehydrate_exception(data)
    assert isinstance(out, exc.KubetorchError)
    assert type(out).__name__ == "SomeUserError"
    assert "tb-here" in str(out)


def test_hbm_oom_detection():
    e = RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory in memory "
        "space hbm. Attempting to allocate 8.52GiB. available 3.99GiB"
    )
    oom = exc.detect_hbm_oom(e)
    assert oom is not None and oom.hbm_oom
    assert oom.requested_bytes == int(8.52 * 2**30)
    assert oom.available_bytes == int(3.99 * 2**30)
    assert exc.detect_hbm_oom(RuntimeError("unrelated")) is None

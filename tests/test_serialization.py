"""Serialization round-trips including array-bearing pytrees (SURVEY §2.3
serialization block; reference serving/http_server.py:1768-1891)."""

import numpy as np
import pytest

from kubetorch_tpu import serialization as ser
from kubetorch_tpu.exceptions import SerializationError


@pytest.mark.parametrize("fmt", [ser.JSON, ser.PICKLE, ser.MSGPACK])
def test_roundtrip_scalars(fmt):
    obj = {"a": 1, "b": [1.5, "x", None, True], "c": {"d": 2}}
    out = ser.deserialize(ser.serialize(obj, fmt), fmt, allowed=[fmt])
    assert out == obj


@pytest.mark.parametrize("fmt", [ser.JSON, ser.MSGPACK])
@pytest.mark.parametrize("dtype", ["float32", "int32", "float64", "bfloat16"])
def test_roundtrip_arrays(fmt, dtype):
    if dtype == "bfloat16":
        import ml_dtypes
        arr = np.arange(12, dtype=np.float32).reshape(3, 4).astype(ml_dtypes.bfloat16)
    else:
        arr = np.arange(12, dtype=dtype).reshape(3, 4)
    obj = {"w": arr, "nested": [arr, {"x": arr}]}
    out = ser.deserialize(ser.serialize(obj, fmt), fmt)
    np.testing.assert_array_equal(np.asarray(out["w"], dtype=np.float32),
                                  np.asarray(arr, dtype=np.float32))
    assert out["w"].dtype == arr.dtype
    assert out["nested"][1]["x"].shape == (3, 4)


def test_jax_array_roundtrip():
    import jax.numpy as jnp
    x = jnp.arange(8.0).reshape(2, 4)
    out = ser.deserialize(ser.serialize({"x": x}, ser.JSON), ser.JSON)
    np.testing.assert_array_equal(out["x"], np.asarray(x))


def test_bytes_roundtrip_json():
    obj = {"blob": b"\x00\x01binary"}
    out = ser.deserialize(ser.serialize(obj, ser.JSON), ser.JSON)
    assert out["blob"] == b"\x00\x01binary"


def test_pickle_gated_by_allowlist():
    data = ser.serialize({"x": 1}, ser.PICKLE)
    with pytest.raises(SerializationError):
        ser.deserialize(data, ser.PICKLE, allowed=ser.DEFAULT_ALLOWED)
    assert ser.deserialize(data, ser.PICKLE, allowed=["pickle"]) == {"x": 1}


def test_none_passthrough():
    assert ser.deserialize(ser.serialize(b"raw", ser.NONE), ser.NONE) == b"raw"
    assert ser.serialize(None, ser.NONE) == b""


def test_unserializable_raises():
    with pytest.raises(SerializationError):
        ser.serialize({"f": lambda: 1}, ser.JSON)


@pytest.mark.parametrize("key", ["__arr__", "~__arr__", "~~__arr__",
                                 "~~~__arr__"])
def test_msgpack_sentinel_key_roundtrip(key):
    """User keys colliding with the '__arr__' typed-leaf sentinel round-trip
    at any '~'-stacking depth — escape pushes exactly one level, the decode
    hook pops exactly one (symmetric with the JSON _escape_key pair)."""
    obj = {key: [1, 2], "nested": {key: {"deeper": {key: "x"}}}}
    out = ser.deserialize(ser.serialize(obj, ser.MSGPACK), ser.MSGPACK)
    assert out == obj


def test_msgpack_sentinel_key_next_to_real_array():
    """An escaped user key and an encoder-produced array coexist in one
    dict: the array decodes, the user key unescapes."""
    import numpy as np

    arr = np.arange(6, dtype=np.int32).reshape(2, 3)
    obj = {"~__arr__": "mine", "w": arr}
    out = ser.deserialize(ser.serialize(obj, ser.MSGPACK), ser.MSGPACK)
    assert out["~__arr__"] == "mine"
    np.testing.assert_array_equal(out["w"], arr)


@pytest.mark.parametrize("key", ["__kt_array__", "~__kt_array__",
                                 "~~__kt_array__"])
def test_json_sentinel_key_roundtrip(key):
    obj = {key: 1, "nested": {key: [True]}}
    out = ser.deserialize(ser.serialize(obj, ser.JSON), ser.JSON)
    assert out == obj


def test_decoded_arrays_are_writable():
    """Preallocated-buffer decode must hand back writable arrays (the old
    frombuffer view would be read-only without the extra copy)."""
    import numpy as np

    obj = {"w": np.zeros(4, np.float32)}
    for fmt in (ser.JSON, ser.MSGPACK):
        out = ser.deserialize(ser.serialize(obj, fmt), fmt)
        out["w"][0] = 7.0
        assert out["w"][0] == 7.0


# ---------------------------------------------------------------------------
# ISSUE 10: _msgpack_escape fast path
# ---------------------------------------------------------------------------


def test_msgpack_escape_fastpath_returns_original_object():
    """A payload with no sentinel keys must come back UNTOUCHED — the
    identical object, containers not rebuilt, large bytes leaves by
    reference."""
    from kubetorch_tpu.serialization import _msgpack_escape

    big = b"\x01" * (1 << 20)
    obj = {"layers": {f"w{i}": big for i in range(8)},
           "cfg": [1, 2.5, "x", None, (3, 4)]}
    out = _msgpack_escape(obj)
    assert out is obj                       # no rebuild at all


def test_msgpack_escape_rebuild_keeps_bytes_by_reference():
    """Even when a sentinel key forces a rebuild, bytes leaves must pass
    by reference (the rebuild copies containers, never payload bytes)."""
    from kubetorch_tpu.serialization import _msgpack_escape

    big = b"\x02" * (1 << 20)
    obj = {"~__arr__": {"x": 1}, "blob": big, "nested": [big]}
    out = _msgpack_escape(obj)
    assert out is not obj                   # rebuild happened
    assert out["~~__arr__"] == {"x": 1}     # escape applied
    assert out["blob"] is big               # by reference
    assert out["nested"][0] is big


def test_msgpack_escape_fastpath_roundtrip_unchanged():
    """Wire bytes with the fast path must round-trip exactly like before:
    clean payloads, sentinel-keyed payloads, and arrays."""
    import numpy as np

    from kubetorch_tpu import serialization as ser

    payloads = [
        {"a": [1, 2, {"b": b"xy"}]},
        {"__arr__": "user-key"},            # needs escaping
        {"~__arr__": "stacked"},            # needs double-stacking
        {"w": np.arange(16, dtype=np.float32)},
    ]
    for p in payloads:
        out = ser.deserialize(ser.serialize(p, ser.MSGPACK), ser.MSGPACK)
        if "w" in p:
            np.testing.assert_array_equal(out["w"], p["w"])
        else:
            assert out == p


def test_msgpack_escape_fastpath_is_faster_than_rebuild(monkeypatch):
    """What the fast path (ISSUE 10) is for, asserted as such and not by a
    clock: on a wide clean tree the scan-only pass hands back the object it
    was given and builds no container, while a tree with one escaped key is
    rebuilt."""
    import tracemalloc

    from kubetorch_tpu import serialization
    from kubetorch_tpu.serialization import (_msgpack_escape,
                                             _msgpack_escape_rebuild)

    rebuilt = []
    monkeypatch.setattr(
        serialization, "_msgpack_escape_rebuild",
        lambda obj: rebuilt.append(1) or _msgpack_escape_rebuild(obj))

    wide = {f"k{i}": [b"x" * 256, {"n": i, "m": [i, i + 1]}]
            for i in range(2000)}
    tracemalloc.start()
    try:
        out = _msgpack_escape(wide)
        _, peak_scan = tracemalloc.get_traced_memory()
        assert out is wide and not rebuilt
        tracemalloc.reset_peak()
        _msgpack_escape_rebuild(wide)
        _, peak_rebuild = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # the scan keeps a few frames and generators alive, never a container of
    # the tree's width; the rebuild allocates all 2,000 entries again
    assert peak_scan < 16 * 1024 < peak_rebuild, (peak_scan, peak_rebuild)

    del rebuilt[:]
    wide["k7"][1]["__arr__"] = 1
    out = _msgpack_escape(wide)
    assert rebuilt and out is not wide
    assert out["k7"][1] == {"n": 7, "m": [7, 8], "~__arr__": 1}
    assert out["k8"] == wide["k8"] and out["k8"] is not wide["k8"]

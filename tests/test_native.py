"""Native runtime: xxh64 vectors, shm arena, cross-process staging."""

import os
import subprocess
import sys

import numpy as np
import pytest

from kubetorch_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="kt_native not built (no toolchain)")


def test_xxh64_spec_vectors():
    assert native.xxh64(b"") == 0xEF46DB3751D8E999
    assert native.xxh64(b"a") == 0xD24EC4F1A98C6E5B
    assert native.xxh64(b"abc") == 0x44BC2CF5AD770999
    # seed changes the hash
    assert native.xxh64(b"abc", seed=1) != native.xxh64(b"abc")


def test_xxh64_file(tmp_path):
    f = tmp_path / "blob.bin"
    data = bytes(range(256)) * 513   # >32B path + odd tail
    f.write_bytes(data)
    assert native.xxh64_file(str(f)) == native.xxh64(data)
    with pytest.raises(OSError):
        native.xxh64_file(str(tmp_path / "missing"))


def test_shm_refcount_lifecycle():
    seg = native.ShmSegment.create("/kt-t1", 128)
    assert seg.refcount == 1
    seg2 = native.ShmSegment.attach("/kt-t1")
    assert seg.refcount == 2
    assert seg2.release() == 1
    assert seg.release() == 0
    assert not os.path.exists("/dev/shm/kt-t1")


def test_shm_create_collision():
    seg = native.ShmSegment.create("/kt-t2", 16)
    with pytest.raises(OSError):
        native.ShmSegment.create("/kt-t2", 16)
    seg.release()


def test_staging_cross_process():
    """Producer stages a pytree; a separate python process attaches, verifies
    content zero-copy, releases; segments vanish after producer release."""
    from kubetorch_tpu.data_store import staging

    tree = {"w": np.arange(8, dtype=np.float32),
            "nested": {"b": np.ones((2, 2), dtype=np.int32)}}
    handle = staging.stage_pytree("kt-t3", tree)
    payload = staging.handle_to_json(handle)

    consumer = (
        "import sys, json, numpy as np\n"
        "sys.path.insert(0, %r)\n"
        "from kubetorch_tpu.data_store import staging\n"
        "tree = staging.load_staged(sys.argv[1])\n"
        "assert (tree['w'] == np.arange(8, dtype=np.float32)).all()\n"
        "assert tree['nested']['b'].sum() == 4\n"
        "print('CONSUMER-OK')\n" % os.path.dirname(os.path.dirname(__file__))
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", consumer, payload],
                         capture_output=True, text=True, env=env, timeout=60)
    assert "CONSUMER-OK" in out.stdout, out.stderr
    staging.release_handle(handle)
    assert not os.path.exists("/dev/shm/kt-t3-0")

"""Crash-consistent, self-healing data store (ISSUE 4).

Deterministic proofs of every recovery path: kill the store mid-PUT
(``torn-write``), rot stored bytes (``corrupt-blob`` / direct flips), fill
the disk (``disk-full``) — then assert the durable-write layer, startup
recovery, scrubber quarantine, and client-side hash verification leave no
wrong answer visible anywhere. ``make test-store-chaos`` runs this file.
"""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
import requests

pytestmark = [pytest.mark.level("minimal"), pytest.mark.chaos]

from kubetorch_tpu.data_store import durability, scrub
from kubetorch_tpu.exceptions import DataCorruptionError, StoreFullError
from kubetorch_tpu.utils.procs import free_port, kill_process_tree, wait_for_port
from tests.assets.threaded_server import ThreadedAiohttpServer


def _store_app(root):
    from kubetorch_tpu.data_store.store_server import create_store_app
    return lambda: create_store_app(str(root))


def _b2(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=20).hexdigest()


def _spawn_store(root, port, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
         "--host", "127.0.0.1", "--port", str(port), "--root", str(root)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    assert wait_for_port("127.0.0.1", port, timeout=30)
    return proc


# ---------------------------------------------------------------------------
# Acceptance: kill-at-any-point safety (torn-write → restart → clean)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_torn_write_sigkill_then_restart_recovers(tmp_path):
    """SIGKILL the store mid-PUT (torn-write chaos), restart on the same
    --root: zero .tmp orphans, no partial value visible to GET or /kv/diff,
    and a clean re-upload succeeds."""
    root = tmp_path / "store"
    port = free_port()
    proc = _spawn_store(root, port,
                        {"KT_CHAOS": "torn-write:1024@/kv/ckpt",
                         "KT_CHAOS_SEED": "1234"})
    url = f"http://127.0.0.1:{port}"
    body = bytes(range(256)) * 64                  # 16 KiB > torn_bytes
    meta = json.dumps({"blake2b": _b2(body)})
    try:
        with pytest.raises(requests.RequestException):
            requests.put(f"{url}/kv/ckpt/w", data=body,
                         headers={"X-KT-Meta": meta}, timeout=30)
    finally:
        proc.wait(timeout=30)                      # chaos SIGKILLed it
    # the kill left a staged partial on disk — the exact orphan recovery
    # must sweep
    orphans = list(root.rglob("*.tmp"))
    assert orphans, "torn-write chaos should have staged a partial .tmp"

    port2 = free_port()
    proc2 = _spawn_store(root, port2)              # same root, no chaos
    url2 = f"http://127.0.0.1:{port2}"
    try:
        assert not list(root.rglob("*.tmp")), "recovery must sweep orphans"
        assert requests.get(f"{url2}/kv/ckpt/w", timeout=30).status_code == 404
        r = requests.post(f"{url2}/kv/diff",
                          json={"keys": {"ckpt/w": _b2(body)}}, timeout=30)
        assert r.json()["missing"] == ["ckpt/w"]
        # clean re-upload round-trips
        r = requests.put(f"{url2}/kv/ckpt/w", data=body,
                         headers={"X-KT-Meta": meta}, timeout=30)
        assert r.status_code == 200
        assert requests.get(f"{url2}/kv/ckpt/w", timeout=30).content == body
        r = requests.post(f"{url2}/kv/diff",
                          json={"keys": {"ckpt/w": _b2(body)}}, timeout=30)
        assert r.json()["missing"] == []
    finally:
        kill_process_tree(proc2.pid)


def test_startup_recovery_quarantines_torn_final_files(tmp_path):
    """An unclean death can also tear a file already renamed to its final
    name (rename persisted, data pages lost). With no clean-shutdown
    marker, startup re-verifies everything and quarantines the liars."""
    from kubetorch_tpu.data_store.store_server import StoreState

    root = tmp_path / "store"
    good = b"good blob bytes"
    gh = _b2(good)
    (root / "blobs" / gh[:2]).mkdir(parents=True)
    (root / "blobs" / gh[:2] / gh).write_bytes(good)
    bh = _b2(b"the full original content")
    (root / "blobs" / bh[:2]).mkdir(parents=True)
    (root / "blobs" / bh[:2] / bh).write_bytes(b"the full or")   # truncated
    (root / "kv").mkdir(parents=True)
    (root / "kv" / "k1").write_bytes(b"torn")
    (root / "kv" / "k1.meta").write_text(
        json.dumps({"blake2b": _b2(b"complete value"), "size": 14}))
    (root / "kv" / "k1.abc123.tmp").write_bytes(b"orphan")
    (root / "trees").mkdir(parents=True)

    st = StoreState(str(root))
    rep = st.recovery
    assert not rep["clean_shutdown"]
    assert rep["tmp_swept"] == 1
    assert rep["quarantined"] == 2                 # bad blob + kv pair
    assert (root / "blobs" / gh[:2] / gh).is_file()       # good one kept
    assert not (root / "blobs" / bh[:2] / bh).exists()
    assert not (root / "kv" / "k1").exists()
    assert not (root / "kv" / "k1.meta").exists(), \
        "stale meta must go with the data or /kv/diff lies forever"
    qdir = root / scrub.QUARANTINE_DIR
    assert sum(1 for p in qdir.iterdir()
               if not p.name.endswith(".why")) == 3  # blob + kv data + meta


def test_clean_shutdown_marker_bounds_verification(tmp_path):
    """A graceful stop stamps the marker; the next startup skips re-hashing
    objects older than it (the normal fast path)."""
    from kubetorch_tpu.data_store.store_server import StoreState

    root = tmp_path / "store"
    st = StoreState(str(root))
    blob = b"x" * 128
    h = _b2(blob)
    p = root / "blobs" / h[:2] / h
    p.parent.mkdir(parents=True)
    p.write_bytes(blob)
    old = os.stat(p).st_mtime - 120
    os.utime(p, (old, old))
    st.mark_clean_shutdown()

    st2 = StoreState(str(root))
    assert st2.recovery["clean_shutdown"]
    assert st2.recovery["verified"] == 0           # marker bounded the sweep
    # marker is consumed: a crash from here on is detectable again
    st3 = StoreState(str(root))
    assert not st3.recovery["clean_shutdown"]
    assert st3.recovery["verified"] == 1


# ---------------------------------------------------------------------------
# Acceptance: corrupt-blob → scrubber quarantine → client repair
# ---------------------------------------------------------------------------


def test_corrupt_blob_chaos_scrub_quarantine_reupload(tmp_path, monkeypatch):
    """corrupt-blob chaos rots the stored blob; the scrubber quarantines it
    within one sweep; GET turns 404 (repair signal); re-upload heals."""
    blob = bytes(range(256)) * 8
    h = _b2(blob)
    monkeypatch.setenv("KT_CHAOS", f"corrupt-blob@/blob/{h}")
    monkeypatch.setenv("KT_CHAOS_SEED", "1234")
    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")   # /scrub/run drives it
    root = tmp_path / "store"
    with ThreadedAiohttpServer(_store_app(root)) as srv:
        assert requests.put(f"{srv.url}/blob/{h}", data=blob,
                            timeout=30).status_code == 200
        # the chaos-consumed GET serves rotten bytes AND persists the rot
        rotten = requests.get(f"{srv.url}/blob/{h}", timeout=30)
        assert rotten.status_code == 200 and rotten.content != blob

        rep = requests.post(f"{srv.url}/scrub/run", timeout=60).json()
        assert rep["quarantined"] == 1
        status = requests.get(f"{srv.url}/scrub/status", timeout=30).json()
        assert status["sweeps"] == 1 and status["quarantine_files"] == 1
        assert requests.get(f"{srv.url}/blob/{h}",
                            timeout=30).status_code == 404

        assert requests.put(f"{srv.url}/blob/{h}", data=blob,
                            timeout=30).status_code == 200
        assert requests.get(f"{srv.url}/blob/{h}", timeout=30).content == blob
        rep = requests.post(f"{srv.url}/scrub/run", timeout=60).json()
        assert rep["quarantined"] == 0             # healed store scrubs clean


def test_client_get_raises_typed_corruption_then_repair(tmp_path, monkeypatch):
    """End-to-end kv corruption: flip a byte under a pytree leaf → kt.get
    raises DataCorruptionError; scrub + re-put repairs; get succeeds."""
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds

    monkeypatch.delenv("POD_IP", raising=False)
    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    root = tmp_path / "store"
    with ThreadedAiohttpServer(_store_app(root)) as srv:
        tree = {"w": np.arange(64, dtype=np.float32)}
        ds.put("rot/ckpt", tree, store_url=srv.url)

        leaf = root / "kv" / durability.escape_key("rot/ckpt/w")
        raw = bytearray(leaf.read_bytes())
        raw[0] ^= 0xFF
        leaf.write_bytes(bytes(raw))

        with pytest.raises(DataCorruptionError) as ei:
            ds.get("rot/ckpt", store_url=srv.url)
        assert ei.value.source == "store" and ei.value.key == "rot/ckpt/w"

        rep = requests.post(f"{srv.url}/scrub/run", timeout=60).json()
        assert rep["quarantined"] == 1
        # quarantined leaf counts as missing → the re-put re-uploads it
        again = ds.put("rot/ckpt", tree, store_url=srv.url)
        assert again["skipped"] == 0
        out = ds.get("rot/ckpt", store_url=srv.url)
        np.testing.assert_array_equal(out["w"], tree["w"])


def test_pull_tree_detects_corrupt_blob(tmp_path, monkeypatch):
    """ktsync pull verifies each streamed blob against its manifest hash —
    corrupt store bytes raise typed instead of landing in the dest tree."""
    from kubetorch_tpu.data_store.sync import push_tree, pull_tree

    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    root = tmp_path / "store"
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "model.py").write_text("weights = 42\n")
    with ThreadedAiohttpServer(_store_app(root)) as srv:
        push_tree(srv.url, "code/app", str(proj))
        blob = next(p for p in (root / "blobs").rglob("*") if p.is_file())
        raw = bytearray(blob.read_bytes())
        raw[0] ^= 0xFF
        blob.write_bytes(bytes(raw))

        dest = tmp_path / "dest"
        with pytest.raises(DataCorruptionError):
            pull_tree(srv.url, "code/app", str(dest))
        assert not (dest / "model.py").exists()
        assert not list(dest.glob("*.ktsync-tmp"))

        # repair: re-push (the diff sees the blob present — scrub first)
        requests.post(f"{srv.url}/scrub/run", timeout=60)
        push_tree(srv.url, "code/app", str(proj))
        pull_tree(srv.url, "code/app", str(dest))
        assert (dest / "model.py").read_text() == "weights = 42\n"


def test_corrupt_peer_evicted_and_origin_repairs(tmp_path, monkeypatch):
    """A peer serving corrupt bytes is treated like a dead one: typed
    detection → /route/failed eviction → transparent re-fetch from the
    origin store — the get still SUCCEEDS."""
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds

    monkeypatch.setenv("POD_IP", "127.0.0.1")
    monkeypatch.setenv("KT_SERVER_PORT", str(free_port()))
    monkeypatch.setenv("KT_DATA_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    root = tmp_path / "store"
    with ThreadedAiohttpServer(_store_app(root)) as srv:
        arr = np.arange(32, dtype=np.float32)
        ds.put("p2p/rot", {"w": arr}, store_url=srv.url)

        failed_reports = []
        fetcher = ds._RoutedFetcher(srv.url, "p2p/rot", peer=True)
        fetcher.peer_url = "http://10.9.9.9:1"
        fetcher._resolved = True
        good = np.asarray(arr).tobytes()
        corrupt = b"\x7f" + good[1:]               # differs from good[0]
        meta = {"dtype": "float32", "shape": [32], "kind": "array",
                "blake2b": _b2(good)}
        monkeypatch.setattr(
            fetcher, "_fetch_from_peer",
            lambda subkey, timeout: ds._CachedResponse(corrupt, meta))
        monkeypatch.setattr(fetcher, "_report_failed",
                            lambda peer: failed_reports.append(peer))

        r = fetcher.fetch("p2p/rot/w", expect_hash=_b2(good))
        assert r.status_code == 200 and r.content == good   # origin repaired
        assert failed_reports == ["http://10.9.9.9:1"]      # peer evicted
        assert fetcher.peer_url is None


def test_corrupt_pod_cache_self_evicts(tmp_path, monkeypatch):
    """A rotten pod-cache entry is evicted on read (never served to this
    pod or its children); the get falls through to the store."""
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.data_store import peer_cache

    monkeypatch.setenv("POD_IP", "127.0.0.1")
    monkeypatch.setenv("KT_SERVER_PORT", str(free_port()))
    monkeypatch.setenv("KT_DATA_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    root = tmp_path / "store"
    with ThreadedAiohttpServer(_store_app(root)) as srv:
        arr = np.full((16,), 3, dtype=np.int32)
        ds.put("cache/rot", {"w": arr}, store_url=srv.url)
        good = np.asarray(arr).tobytes()
        peer_cache.cache_put(
            "cache/rot/w", b"\xff" + good[1:],
            {"dtype": "int32", "shape": [16], "kind": "array",
             "blake2b": _b2(good)})
        assert peer_cache.cache_get("cache/rot/w") is None   # self-evicted
        out = ds.get("cache/rot", store_url=srv.url, peer=True)
        np.testing.assert_array_equal(out["w"], arr)


# ---------------------------------------------------------------------------
# disk-full → typed, non-retryable StoreFullError
# ---------------------------------------------------------------------------


def test_disk_full_maps_to_typed_store_full_error(tmp_path, monkeypatch):
    """A 507 is a capacity verdict: ONE injected disk-full fails the put
    with typed StoreFullError — were it retried, the second attempt would
    pass chaos and succeed, masking the full disk."""
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds

    monkeypatch.delenv("POD_IP", raising=False)
    monkeypatch.setenv("KT_CHAOS", "disk-full@/kv/full")
    monkeypatch.setenv("KT_CHAOS_SEED", "1234")
    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    root = tmp_path / "store"
    with ThreadedAiohttpServer(_store_app(root)) as srv:
        with pytest.raises(StoreFullError) as ei:
            ds.put("full/ckpt", {"w": np.ones(8, np.float32)},
                   store_url=srv.url)
        assert ei.value.status_code == 507
        assert srv.app["chaos"].injected == 1
        # chaos schedule exhausted → the retry-after-free-space story works
        stats = ds.put("full/ckpt", {"w": np.ones(8, np.float32)},
                       store_url=srv.url)
        assert stats["leaves"] == 1


def test_enospc_classifier():
    import errno

    assert durability.is_disk_full(OSError(errno.ENOSPC, "no space"))
    assert durability.is_disk_full(OSError(errno.EDQUOT, "quota"))
    assert not durability.is_disk_full(OSError(errno.EACCES, "denied"))
    assert not durability.is_disk_full(ValueError("x"))


# ---------------------------------------------------------------------------
# Scrubber unit behavior
# ---------------------------------------------------------------------------


def test_scrubber_double_checks_kv_race(tmp_path):
    """A kv pair replaced between meta read and data hash must NOT be
    quarantined — the double-check re-reads before condemning."""
    root = tmp_path / "store"
    (root / "kv").mkdir(parents=True)
    val = b"consistent value"
    (root / "kv" / "k").write_bytes(val)
    (root / "kv" / "k.meta").write_text(
        json.dumps({"blake2b": _b2(val), "size": len(val)}))
    assert not scrub._verify_kv_pair(root, root / "kv" / "k",
                                     root / "kv" / "k.meta")
    assert (root / "kv" / "k").is_file()


def test_gc_reclaims_unreferenced_blobs(tmp_path, monkeypatch):
    """tree_delete strands its blobs; /gc with grace 0 reclaims exactly the
    unreferenced ones and keeps everything a manifest still points at."""
    from kubetorch_tpu.data_store.sync import push_tree

    monkeypatch.setenv("KT_SCRUB_INTERVAL_S", "0")
    root = tmp_path / "store"
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "a.py").write_text("a = 1\n")
    (proj / "b.py").write_text("b = 2\n")
    with ThreadedAiohttpServer(_store_app(root)) as srv:
        push_tree(srv.url, "code/app", str(proj))
        stray = b"never referenced by any manifest"
        sh = _b2(stray)
        assert requests.put(f"{srv.url}/blob/{sh}", data=stray,
                            timeout=30).status_code == 200

        rep = requests.post(f"{srv.url}/gc", json={"grace_s": 0},
                            timeout=60).json()
        assert rep["deleted"] == 1 and rep["bytes_freed"] == len(stray)
        assert rep["kept"] == 2                     # manifest-pinned blobs
        # young blobs survive the default grace window (in-flight uploads)
        assert requests.put(f"{srv.url}/blob/{sh}", data=stray,
                            timeout=30).status_code == 200
        rep = requests.post(f"{srv.url}/gc", timeout=60).json()
        assert rep["deleted"] == 0

        requests.delete(f"{srv.url}/tree/code/app", timeout=30)
        rep = requests.post(f"{srv.url}/gc", json={"grace_s": 0},
                            timeout=60).json()
        assert rep["deleted"] == 3                  # everything reclaimed


def test_durable_replace_fsyncs_data_and_dir(tmp_path, monkeypatch):
    """KT_STORE_FSYNC=1 (default) pairs the commit rename with data + parent
    -dir fsync; =0 skips both (throwaway roots)."""
    calls = []
    real = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: calls.append(fd) or real(fd))

    monkeypatch.setenv("KT_STORE_FSYNC", "1")
    durability.durable_write_bytes(tmp_path / "f1", b"payload")
    assert len(calls) == 2                          # file + parent dir
    assert (tmp_path / "f1").read_bytes() == b"payload"

    calls.clear()
    monkeypatch.setenv("KT_STORE_FSYNC", "0")
    durability.durable_write_bytes(tmp_path / "f2", b"payload")
    assert calls == []
    assert (tmp_path / "f2").read_bytes() == b"payload"

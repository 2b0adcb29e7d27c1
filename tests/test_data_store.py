"""Delta-sync protocol + store server (reference test_store.py model)."""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.level("minimal")

from kubetorch_tpu.data_store.sync import build_manifest, push_tree, pull_tree
from kubetorch_tpu.exceptions import SyncError
from kubetorch_tpu.utils.procs import free_port, kill_process_tree, wait_for_port


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    root = tmp_path_factory.mktemp("store")
    port = free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
         "--host", "127.0.0.1", "--port", str(port), "--root", str(root)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    assert wait_for_port("127.0.0.1", port, timeout=30)
    yield f"http://127.0.0.1:{port}"
    kill_process_tree(proc.pid)


@pytest.fixture
def project(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "mod.py").write_text("x = 1\n")
    (tmp_path / "main.py").write_text("print('hello')\n")
    (tmp_path / "__pycache__").mkdir()
    (tmp_path / "__pycache__" / "junk.pyc").write_text("junk")
    (tmp_path / ".git").mkdir()
    (tmp_path / ".git" / "HEAD").write_text("ref")
    return tmp_path


def test_manifest_excludes(project):
    m = build_manifest(str(project))
    assert set(m) == {"pkg/mod.py", "main.py"}
    assert all("hash" in v and "size" in v for v in m.values())


@pytest.mark.slow
def test_push_pull_roundtrip(store, project, tmp_path_factory):
    stats = push_tree(store, "code/svc1", str(project))
    assert stats == {"files": 2, "uploaded": 2,
                     "uploaded_bytes": stats["uploaded_bytes"]}

    dest = tmp_path_factory.mktemp("dest")
    out = pull_tree(store, "code/svc1", str(dest))
    assert out["files"] == 2 and out["fetched"] == 2
    assert (dest / "pkg" / "mod.py").read_text() == "x = 1\n"
    assert (dest / "main.py").read_text() == "print('hello')\n"


@pytest.mark.slow
def test_delta_push_only_changed(store, project, tmp_path_factory):
    push_tree(store, "code/svc2", str(project))
    # no-op push: nothing uploaded
    stats = push_tree(store, "code/svc2", str(project))
    assert stats["uploaded"] == 0
    # change one file
    (project / "main.py").write_text("print('v2')\n")
    stats = push_tree(store, "code/svc2", str(project))
    assert stats["uploaded"] == 1

    dest = tmp_path_factory.mktemp("dest2")
    pull_tree(store, "code/svc2", str(dest))
    # delta pull: only the changed file
    (project / "pkg" / "mod.py").write_text("x = 3\n")
    push_tree(store, "code/svc2", str(project))
    out = pull_tree(store, "code/svc2", str(dest))
    assert out["fetched"] == 1
    assert (dest / "pkg" / "mod.py").read_text() == "x = 3\n"


@pytest.mark.slow
def test_pull_deletes_removed_files(store, project, tmp_path_factory):
    push_tree(store, "code/svc3", str(project))
    dest = tmp_path_factory.mktemp("dest3")
    pull_tree(store, "code/svc3", str(dest))
    assert (dest / "main.py").exists()
    # user-created file must survive; synced-then-removed file must go
    (dest / "user_scratch.txt").write_text("mine")
    (project / "main.py").unlink()
    push_tree(store, "code/svc3", str(project))
    out = pull_tree(store, "code/svc3", str(dest))
    assert out["deleted"] == 1
    assert not (dest / "main.py").exists()
    assert (dest / "user_scratch.txt").exists()


@pytest.mark.slow
def test_pull_missing_tree_raises(store, tmp_path):
    with pytest.raises(SyncError, match="No tree"):
        pull_tree(store, "code/nope", str(tmp_path / "x"))


@pytest.mark.slow
def test_kv_roundtrip(store):
    import requests
    r = requests.put(f"{store}/kv/ckpt/layer0.w", data=b"\x00\x01\x02",
                     headers={"X-KT-Meta": '{"dtype": "float32"}'})
    assert r.status_code == 200
    r = requests.get(f"{store}/kv/ckpt/layer0.w")
    assert r.content == b"\x00\x01\x02"
    assert "float32" in r.headers["X-KT-Meta"]
    r = requests.get(f"{store}/keys", params={"prefix": "ckpt/"})
    assert [k["key"] for k in r.json()["keys"]] == ["ckpt/layer0.w"]
    requests.delete(f"{store}/kv/ckpt/layer0.w")
    assert requests.get(f"{store}/kv/ckpt/layer0.w").status_code == 404


@pytest.mark.slow
def test_pytree_put_get_roundtrip(store):
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds

    tree = {"layers": {"wq": np.arange(12, dtype=np.float32).reshape(3, 4),
                       "scale": np.float32(2.5)},
            "steps": [np.ones(2, dtype=np.int32), np.zeros(3, dtype=np.int32)]}
    stats = ds.put("ckpt/run1", tree, store_url=store)
    assert stats["leaves"] == 4

    out = ds.get("ckpt/run1", store_url=store)
    np.testing.assert_array_equal(out["layers"]["wq"], tree["layers"]["wq"])
    np.testing.assert_array_equal(out["steps"][1], tree["steps"][1])

    keys = [k["key"] for k in ds.ls("ckpt/run1", store_url=store)]
    assert "ckpt/run1/layers/wq" in keys
    assert ds.rm("ckpt/run1", store_url=store)
    with pytest.raises(Exception):
        ds.get("ckpt/run1", store_url=store)


@pytest.mark.slow
def test_pytree_put_get_bfloat16(store):
    """bf16 is the standard dtype of the trainer→inference weight sync;
    ml_dtypes arrays refuse numpy buffer export, so the content-hash path
    must go through a uint8 view (regression: put() used to crash with
    'cannot include dtype in a buffer')."""
    import numpy as np
    import ml_dtypes
    from kubetorch_tpu.data_store import commands as ds

    tree = {"w": np.arange(24, dtype=np.float32).reshape(4, 6)
            .astype(ml_dtypes.bfloat16),
            "scale": np.asarray(np.float32(0.5)).astype(ml_dtypes.bfloat16)}
    stats = ds.put("ckpt/bf16", tree, store_url=store)
    assert stats["leaves"] == 2 and stats["skipped"] == 0

    out = ds.get("ckpt/bf16", store_url=store)
    assert out["w"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(out["w"], tree["w"])
    np.testing.assert_array_equal(out["scale"], tree["scale"])

    again = ds.put("ckpt/bf16", tree, store_url=store)
    assert again["skipped"] == 2 and again["bytes"] == 0
    ds.rm("ckpt/bf16", store_url=store)


@pytest.mark.slow
def test_pytree_reshard_on_get(store, cpu_mesh_devices):
    """Save from host, load sharded onto a mesh — per-leaf resharding."""
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.parallel.mesh import build_mesh
    from kubetorch_tpu.parallel.sharding import LLAMA_RULES

    tree = {"layers": {"wq": np.zeros((2, 8, 16), np.float32)}}
    ds.put("ckpt/shard", tree, store_url=store)
    mesh = build_mesh({"fsdp": 4, "tensor": 2})
    out = ds.get("ckpt/shard", store_url=store, mesh=mesh, rules=LLAMA_RULES)
    wq = out["layers"]["wq"]
    import jax
    assert wq.sharding.spec == jax.sharding.PartitionSpec(None, "fsdp", "tensor")
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {(2, 2, 8)}
    ds.rm("ckpt/shard", store_url=store)


@pytest.mark.slow
def test_coordinated_broadcast_window(store):
    """Producer put(broadcast=) blocks until all consumers join; consumers
    fetch after the quorum (reference SURVEY §3.3 weight-sync pattern)."""
    import threading
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.data_store.types import BroadcastWindow

    win = lambda: BroadcastWindow(world_size=3, timeout=30)
    results = {}

    def producer():
        results["put"] = ds.put("bcast/w", {"w": np.ones(4, np.float32)},
                                store_url=store, broadcast=win())

    def consumer(i):
        results[f"get{i}"] = ds.get_broadcast("bcast/w", win(), store_url=store)

    threads = [threading.Thread(target=producer),
               threading.Thread(target=consumer, args=(1,)),
               threading.Thread(target=consumer, args=(2,))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=40)
    assert results["put"]["leaves"] == 1
    np.testing.assert_array_equal(results["get1"]["w"], np.ones(4, np.float32))
    np.testing.assert_array_equal(results["get2"]["w"], np.ones(4, np.float32))
    ds.rm("bcast/w", store_url=store)


@pytest.mark.slow
def test_broadcast_window_timeout(store):
    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.data_store.types import BroadcastWindow
    from kubetorch_tpu.exceptions import DataStoreError

    with pytest.raises(DataStoreError, match="timed out"):
        ds.join_broadcast("bcast/lonely",
                          BroadcastWindow(world_size=2, timeout=1.5),
                          store_url=store)


def test_checkpoint_save_restore_roundtrip(store):
    """train.checkpoint: sync + async saves land identical state; restore
    rebuilds the optax namedtuple structure from the path-keyed store."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubetorch_tpu.models.mlp import MlpConfig, mlp_init
    from kubetorch_tpu.train import init_train_state
    from kubetorch_tpu.train.checkpoint import (async_save_state,
                                                restore_state, save_state)

    cfg = MlpConfig(in_dim=8, hidden=(4,), out_dim=2)
    opt = optax.adam(1e-3)
    state = init_train_state(mlp_init(jax.random.PRNGKey(0), cfg), opt)
    state = state._replace(step=jnp.asarray(7, jnp.int32))

    save_state("t-ckpt/sync", state, store_url=store)
    fut = async_save_state("t-ckpt/async", state, store_url=store)
    fut.result(timeout=60)  # durability barrier

    like = init_train_state(mlp_init(jax.random.PRNGKey(1), cfg), opt)
    for key in ("t-ckpt/sync", "t-ckpt/async"):
        got = restore_state(key, like, store_url=store)
        assert int(got.step) == 7
        np.testing.assert_array_equal(
            np.asarray(got.params["layers"][0]["w"]),
            np.asarray(state.params["layers"][0]["w"]))
        # optimizer state structure is a real optax namedtuple chain again
        chex_leaves = jax.tree_util.tree_leaves(got.opt_state)
        assert len(chex_leaves) == len(jax.tree_util.tree_leaves(like.opt_state))


def test_prefetch_to_device_orders_and_shards(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.train import prefetch_to_device

    mesh = build_mesh(MeshSpec(data=8), devices=jax.devices()[:8])
    sh = NamedSharding(mesh, P("data"))
    batches = ({"x": np.full((8, 4), i, np.float32)} for i in range(5))
    out = list(prefetch_to_device(batches, size=2, sharding=sh))
    assert len(out) == 5
    for i, b in enumerate(out):
        assert b["x"].sharding == sh
        np.testing.assert_array_equal(np.asarray(b["x"]),
                                      np.full((8, 4), i, np.float32))

    with pytest.raises(ValueError, match="size"):
        list(prefetch_to_device(iter([]), size=0))


def test_manifest_hash_cache(project, monkeypatch):
    """Warm manifest builds reuse cached hashes (stat-keyed); edits and
    cache corruption re-hash."""
    from kubetorch_tpu.data_store import sync as sync_mod

    calls = []
    real = sync_mod.file_hash
    monkeypatch.setattr(sync_mod, "file_hash",
                        lambda p, **k: calls.append(p) or real(p, **k))

    first = build_manifest(str(project))
    assert len(calls) == 2
    calls.clear()
    assert build_manifest(str(project)) == first          # warm: zero hashing
    assert calls == []

    (project / "main.py").write_text("print('bye')\n")    # edit → one re-hash
    m = build_manifest(str(project))
    assert [os.path.basename(p) for p in calls] == ["main.py"]
    assert m["main.py"]["hash"] != first["main.py"]["hash"]
    assert m["pkg/mod.py"] == first["pkg/mod.py"]

    for corrupt in ("not json", '"oops"', '{"main.py": "zzz"}'):
        (project / ".ktsync" / "hash-cache.json").write_text(corrupt)
        calls.clear()
        assert build_manifest(str(project)) == m          # corrupt cache: rebuilt
        assert len(calls) == 2


# ---------------------------------------------------------------------------
# Parallel data plane: content-addressed delta sync + concurrent put/get
# (ISSUE 1: /kv/diff protocol, KT_STORE_CONCURRENCY fan-out)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_delta_sync_skips_unchanged_leaves(store):
    """Repeated identical put moves zero leaf bytes (/kv/diff says all
    current); mutating one leaf re-uploads exactly that leaf."""
    import numpy as np
    from kubetorch_tpu.data_store import commands as ds

    tree = {"emb": np.arange(64, dtype=np.float32),
            "lora": {"a": np.ones((8, 2), np.float32),
                     "b": np.zeros((2, 8), np.float32)}}
    cold = ds.put("delta/w", tree, store_url=store)
    assert cold["skipped"] == 0 and cold["leaves"] == 3
    assert cold["bytes"] == 64 * 4 + 16 * 4 + 16 * 4

    warm = ds.put("delta/w", tree, store_url=store)
    assert warm["skipped"] == warm["leaves"] == 3
    assert warm["bytes"] == 0

    # LoRA-style update: one leaf changes, only it moves
    tree["lora"]["a"] = tree["lora"]["a"] * 2
    partial = ds.put("delta/w", tree, store_url=store)
    assert partial["skipped"] == 2
    assert partial["bytes"] == 16 * 4
    out = ds.get("delta/w", store_url=store)
    np.testing.assert_array_equal(out["lora"]["a"], tree["lora"]["a"])
    np.testing.assert_array_equal(out["emb"], tree["emb"])
    ds.rm("delta/w", store_url=store)


@pytest.mark.slow
def test_kv_diff_endpoint_wire_shape(store):
    """POST /kv/diff mirrors /tree/diff: {keys: {key: hash}} → {missing}.
    Unknown keys, stale hashes, and pre-hash keys all count as missing."""
    import hashlib
    import requests

    body = b"\x01\x02\x03"
    h = hashlib.blake2b(body, digest_size=20).hexdigest()
    r = requests.put(f"{store}/kv/diffkeys/a", data=body, timeout=30)
    assert r.status_code == 200
    r = requests.post(f"{store}/kv/diff", json={"keys": {
        "diffkeys/a": h,                  # current
        "diffkeys/a2": h,                 # unknown key
    }}, timeout=30)
    assert r.status_code == 200
    assert r.json()["missing"] == ["diffkeys/a2"]
    r = requests.post(f"{store}/kv/diff", json={"keys": {
        "diffkeys/a": "f" * 40}}, timeout=30)   # stale hash
    assert r.json()["missing"] == ["diffkeys/a"]
    requests.delete(f"{store}/kv/diffkeys/a", timeout=30)


@pytest.mark.slow
def test_kv_put_rejects_hash_mismatch(store):
    """A PUT whose X-KT-Meta blake2b doesn't match the body is rejected
    before the bad bytes become the delta baseline."""
    import json as _json
    import requests

    r = requests.put(f"{store}/kv/bad/leaf", data=b"\x00" * 16,
                     headers={"X-KT-Meta": _json.dumps(
                         {"blake2b": "0" * 40})}, timeout=30)
    assert r.status_code == 400
    assert requests.get(f"{store}/kv/bad/leaf", timeout=30).status_code == 404


@pytest.mark.slow
def test_streamed_blob_put_chunked(store):
    """put_blob streams request bodies (no full-body buffering): a chunked
    upload with no Content-Length lands bit-exact and hash-verified."""
    import hashlib
    import requests

    blob = bytes(range(256)) * (1 << 12)          # 1 MiB, compressible
    h = hashlib.blake2b(blob, digest_size=20).hexdigest()

    def gen(chunk=1 << 14):
        for i in range(0, len(blob), chunk):
            yield blob[i:i + chunk]

    r = requests.put(f"{store}/blob/{h}", data=gen(), timeout=60)
    assert r.status_code == 200 and r.json()["size"] == len(blob)
    assert requests.get(f"{store}/blob/{h}", timeout=60).content == blob
    # wrong-hash upload is rejected and leaves nothing behind
    bad = "ab" * 20
    r = requests.put(f"{store}/blob/{bad}", data=gen(), timeout=60)
    assert r.status_code == 400
    assert requests.get(f"{store}/blob/{bad}", timeout=60).status_code == 404


@pytest.mark.slow
def test_concurrent_put_get_stress(store, monkeypatch):
    """N client threads × M leaves hammer the store concurrently (each put
    itself fans out over the netpool executor): every index stays
    consistent with its leaves and no tree loses data."""
    import threading

    import numpy as np
    from kubetorch_tpu.data_store import commands as ds

    monkeypatch.setenv("KT_STORE_CONCURRENCY", "4")
    n_threads, n_leaves = 4, 12
    errors = []

    def worker(t):
        try:
            rng = np.random.default_rng(t)
            tree = {"layer": {f"w{i}": rng.standard_normal(64).astype(
                np.float32) for i in range(n_leaves)}}
            stats = ds.put(f"stress/t{t}", tree, store_url=store)
            assert stats["leaves"] == n_leaves, stats
            out = ds.get(f"stress/t{t}", store_url=store)
            assert sorted(out["layer"]) == sorted(tree["layer"])
            for name, arr in tree["layer"].items():
                np.testing.assert_array_equal(out["layer"][name], arr)
        except Exception as e:               # surface across the join
            errors.append((t, e))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors, errors
    keys = [k["key"] for k in ds.ls("stress/", store_url=store)]
    assert len(keys) == n_threads * n_leaves   # no lost leaves
    for t in range(n_threads):
        ds.rm(f"stress/t{t}", store_url=store)


# ---------------------------------------------------------------------------
# P2P fan-out (the reference's rolling-participation tree broadcast,
# data_store_client.py:376-688 / design.md)
# ---------------------------------------------------------------------------


def test_peer_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("KT_DATA_CACHE_DIR", str(tmp_path / "cache"))
    from kubetorch_tpu.data_store import peer_cache

    assert peer_cache.cache_get("k1") is None
    peer_cache.cache_put("k1", b"\x00\x01payload", {"kind": "array"})
    data, meta = peer_cache.cache_get("k1")
    assert data == b"\x00\x01payload" and meta == {"kind": "array"}
    peer_cache.cache_evict("k1")
    assert peer_cache.cache_get("k1") is None


# ---------------------------------------------------------------------------
# Crash-consistent store: key escaping, delete hygiene, peer persistence
# (ISSUE 4; the kill/corrupt/full proofs live in test_store_chaos.py)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_key_escaping_symmetric_and_traversal_rejected(store):
    """Keys containing a literal ``%2F`` and keys containing ``/`` are
    distinct entries that round-trip exactly through /keys; traversal keys
    are rejected with 400 instead of resolving outside the store root."""
    import requests

    # the two keys the old one-way escape collided: 'esc/key' vs 'esc%2Fkey'
    # (sent double-encoded on the wire so unquote yields the literal %2F)
    r1 = requests.put(f"{store}/kv/esc/key", data=b"slash", timeout=30)
    r2 = requests.put(f"{store}/kv/esc%252Fkey", data=b"percent", timeout=30)
    assert r1.status_code == r2.status_code == 200
    assert requests.get(f"{store}/kv/esc/key", timeout=30).content == b"slash"
    assert requests.get(f"{store}/kv/esc%252Fkey",
                        timeout=30).content == b"percent"
    keys = {k["key"] for k in requests.get(
        f"{store}/keys", params={"prefix": "esc"}, timeout=30).json()["keys"]}
    assert {"esc/key", "esc%2Fkey"} <= keys        # exact round-trip
    for key in ("esc/key", "esc%252Fkey"):
        requests.delete(f"{store}/kv/{key}", timeout=30)

    # '..' would resolve root/kv/.. to the store root itself
    assert requests.put(f"{store}/kv/%2E%2E", data=b"x",
                        timeout=30).status_code == 400
    assert requests.get(f"{store}/kv/%2E%2E", timeout=30).status_code == 400
    assert requests.post(f"{store}/tree/%2E%2E/commit", json={"files": {}},
                         timeout=30).status_code == 400


@pytest.mark.slow
def test_kv_delete_removes_meta_and_tmp_siblings(store, tmp_path):
    """DELETE reaps the .meta and any in-flight .tmp siblings, and is
    idempotent under repeated delete."""
    import requests

    requests.put(f"{store}/kv/del/k", data=b"v",
                 headers={"X-KT-Meta": "{}"}, timeout=30)
    r = requests.delete(f"{store}/kv/del/k", timeout=30)
    assert r.status_code == 200 and r.json()["existed"]
    r = requests.delete(f"{store}/kv/del/k", timeout=30)
    assert r.status_code == 200 and not r.json()["existed"]   # idempotent
    assert requests.get(f"{store}/kv/del/k", timeout=30).status_code == 404
    # a re-created key must not inherit a stale meta: diff says missing
    requests.put(f"{store}/kv/del/k", data=b"v2", timeout=30)
    requests.delete(f"{store}/kv/del/k", timeout=30)
    import hashlib as _h
    h = _h.blake2b(b"v2", digest_size=20).hexdigest()
    r = requests.post(f"{store}/kv/diff", json={"keys": {"del/k": h}},
                      timeout=30)
    assert r.json()["missing"] == ["del/k"]


def test_delete_sweeps_tmp_siblings_on_disk(tmp_path):
    """Unit-level: kv/tree delete unlink in-flight .tmp siblings so killed
    uploads can't accumulate unbounded."""
    import asyncio

    from kubetorch_tpu.data_store import store_server as ss

    st = ss.StoreState(str(tmp_path / "root"))
    kv = st.kv_path("a/b")
    kv.write_bytes(b"v")
    kv.with_name(kv.name + ".meta").write_text("{}")
    kv.with_name(kv.name + ".11112222.tmp").write_bytes(b"partial")
    kv.with_name(kv.name + ".meta.33334444.tmp").write_bytes(b"partial")
    tree = st.tree_path("t/x")
    tree.write_text("{}")
    tree.with_name(tree.name + ".55556666.tmp").write_text("partial")

    class _Req:
        def __init__(self, app, key):
            self.app, self.match_info = app, {"key": key}

    app = {"store": st}
    asyncio.run(ss.kv_delete(_Req(app, "a/b")))
    asyncio.run(ss.tree_delete(_Req(app, "t/x")))
    assert not list((st.root / "kv").iterdir())
    assert not list((st.root / "trees").iterdir())


def test_peer_registry_persists_and_ttl_expires(tmp_path, monkeypatch):
    """/register state survives a store restart via root/peers.json;
    TTL-stale entries are dropped on reload and on lookup."""
    import json as _json
    import time as _time

    from kubetorch_tpu.data_store import scrub
    from kubetorch_tpu.data_store.store_server import StoreState

    root = tmp_path / "root"
    st = StoreState(str(root))
    st.peers["w/step1"] = {"ip": "10.0.0.1", "port": 8873, "ts": _time.time()}
    st.save_peers()

    st2 = StoreState(str(root))                     # "restart"
    assert st2.peers["w/step1"]["ip"] == "10.0.0.1"

    # stale entry (written by a long-dead run) expires on reload
    stale = {"w/old": {"ip": "10.0.0.9", "port": 1, "ts": _time.time() - 10},
             "w/new": {"ip": "10.0.0.2", "port": 2, "ts": _time.time()}}
    (root / scrub.PEERS_FILE).write_text(_json.dumps(stale))
    monkeypatch.setenv("KT_PEER_TTL_S", "5")
    st3 = StoreState(str(root))
    assert set(st3.peers) == {"w/new"}
    # corrupt snapshot degrades to empty, never a crash
    (root / scrub.PEERS_FILE).write_text("not json{")
    assert StoreState(str(root)).peers == {}


@pytest.mark.slow
def test_route_eager_tree_assignment(store):
    """Routing protocol (ISSUE 11 tree shape): first member roots at the
    store (depth 1); later members are assigned the SHALLOWEST member with
    a free child slot EAGERLY (before it completes) — breadth-first fill;
    failed parents are evicted and their children orphaned."""
    import requests

    key = "route/proto"
    r = requests.post(f"{store}/route", json={
        "key": key, "self_url": "http://10.0.0.1:1"}, timeout=10).json()
    assert (r["source"], r["depth"]) == ("store", 1)
    # B arrives while A is still fetching: assigned A (eager rolling join)
    r = requests.post(f"{store}/route", json={
        "key": key, "self_url": "http://10.0.0.2:1"}, timeout=10).json()
    assert (r["source"], r["url"], r["depth"]) == (
        "peer", "http://10.0.0.1:1", 2)
    # C arrives: depth-aware — A (depth 1, free slot) still wins over the
    # deeper B, filling the tree breadth-first
    r = requests.post(f"{store}/route", json={
        "key": key, "self_url": "http://10.0.0.3:1"}, timeout=10).json()
    assert (r["source"], r["url"], r["depth"]) == (
        "peer", "http://10.0.0.1:1", 2)
    # a member is never its own parent
    r = requests.post(f"{store}/route", json={
        "key": key, "self_url": "http://10.0.0.2:1"}, timeout=10).json()
    assert r["url"] != "http://10.0.0.2:1"
    # B reported unreachable → evicted; D re-routes elsewhere
    out = requests.post(f"{store}/route/failed", json={
        "key": key, "url": "http://10.0.0.2:1"}, timeout=10).json()
    assert out["evicted"] is True
    r = requests.post(f"{store}/route", json={
        "key": key, "self_url": "http://10.0.0.4:1"}, timeout=10).json()
    assert r.get("url") != "http://10.0.0.2:1"


@pytest.mark.slow
def test_route_complete_fires_once_under_parallel_fetch(store, tmp_path,
                                                        monkeypatch):
    """However many executor workers a pytree get fans out over, the fetcher
    reports /route/complete exactly once — N reports would inflate this
    pod's routing weight for later joiners."""
    import threading

    import numpy as np

    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.data_store import netpool

    monkeypatch.setenv("POD_IP", "127.0.0.1")
    monkeypatch.setenv("KT_SERVER_PORT", str(free_port()))
    monkeypatch.setenv("KT_DATA_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("KT_STORE_CONCURRENCY", "8")

    tree = {f"w{i}": np.full((32,), i, np.float32) for i in range(16)}
    ds.put("complete/once", tree, store_url=store)

    complete_posts = []

    class _CountingSession:
        def __init__(self, real):
            self._real = real

        def post(self, url, *a, **kw):
            if url.endswith("/route/complete"):
                complete_posts.append(url)
            return self._real.post(url, *a, **kw)

        def __getattr__(self, name):
            return getattr(self._real, name)

    monkeypatch.setattr(ds._RoutedFetcher, "_sess",
                        lambda self: _CountingSession(netpool.session()))

    out = ds.get("complete/once", store_url=store, peer=True)
    np.testing.assert_array_equal(out["w3"], tree["w3"])
    assert len(complete_posts) == 1

    # direct hammer: 8 threads racing complete() on one fetcher → one POST
    complete_posts.clear()
    fetcher = ds._RoutedFetcher(store, "complete/once", peer=True)
    fetcher._fetched = True
    threads = [threading.Thread(target=fetcher.complete) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(complete_posts) == 1
    ds.rm("complete/once", store_url=store)


def _spawn_cache_server(cache_dir, port):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "KT_DATA_CACHE_DIR": str(cache_dir),
                "POD_IP": "127.0.0.1", "LOCAL_IPS": "127.0.0.1"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.serving.http_server",
         "--host", "127.0.0.1", "--port", str(port)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    assert wait_for_port("127.0.0.1", port, timeout=30)
    return proc


@pytest.mark.slow
def test_p2p_get_serves_from_peer_after_store_loss(store, tmp_path, monkeypatch):
    """Pod A fetches a pytree (becoming a parent); pod B's get is routed to
    A and succeeds even after the key is deleted from the central store —
    proof the bytes came from the peer, not the root."""
    import numpy as np

    from kubetorch_tpu.data_store import commands

    key = "p2p/weights"
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones((4,), np.float32)}
    commands.put(key, tree, store_url=store)

    dir_a = tmp_path / "cache-a"
    port_a = free_port()
    proc_a = _spawn_cache_server(dir_a, port_a)
    try:
        # pod A: fetch through the fan-out → caches + registers as parent
        monkeypatch.setenv("POD_IP", "127.0.0.1")
        monkeypatch.setenv("KT_SERVER_PORT", str(port_a))
        monkeypatch.setenv("KT_DATA_CACHE_DIR", str(dir_a))
        got_a = commands.get(key, store_url=store, peer=True)
        np.testing.assert_array_equal(got_a["w"], tree["w"])

        # the store loses the key entirely
        commands.rm(key, store_url=store)

        # pod B (distinct self_url, own cache): routed to A, still succeeds
        monkeypatch.setenv("KT_SERVER_PORT", str(free_port()))
        monkeypatch.setenv("KT_DATA_CACHE_DIR", str(tmp_path / "cache-b"))
        monkeypatch.setenv("KT_PEER_WAIT_S", "5")
        got_b = commands.get(key, store_url=store, peer=True)
        np.testing.assert_array_equal(got_b["w"], tree["w"])
        np.testing.assert_array_equal(got_b["b"], tree["b"])
    finally:
        kill_process_tree(proc_a.pid)

    # pod-local cache reuse (N rank workers sharing one pod cache): with the
    # store empty AND pod A's server dead, a get against A's cache dir is
    # served entirely from local disk
    monkeypatch.setenv("KT_SERVER_PORT", str(port_a))
    monkeypatch.setenv("KT_DATA_CACHE_DIR", str(dir_a))
    got_local = commands.get(key, store_url=store, peer=True)
    np.testing.assert_array_equal(got_local["w"], tree["w"])


@pytest.mark.slow
def test_p2p_rolling_join_waits_for_parent(store, tmp_path, monkeypatch):
    """A child routed to a still-fetching parent polls until the parent's
    cache fills (the reference's block-until-parent-done join) instead of
    falling straight back to the store."""
    import json as _json
    import threading

    import numpy as np
    import requests

    from kubetorch_tpu.data_store import commands, peer_cache

    key = "p2p/rolling"
    arr = np.full((8,), 7, dtype=np.int32)

    dir_a = tmp_path / "cache-a"
    port_a = free_port()
    proc_a = _spawn_cache_server(dir_a, port_a)
    try:
        # register A as an (incomplete) member — it holds nothing yet
        requests.post(f"{store}/route", json={
            "key": key, "self_url": f"http://127.0.0.1:{port_a}"}, timeout=10)

        monkeypatch.setenv("POD_IP", "127.0.0.1")
        monkeypatch.setenv("KT_SERVER_PORT", str(free_port()))
        monkeypatch.setenv("KT_DATA_CACHE_DIR", str(dir_a))
        monkeypatch.setenv("KT_PEER_WAIT_S", "20")

        def fill_parent_cache():
            time.sleep(1.0)
            meta = {"dtype": "int32", "shape": [8], "kind": "array"}
            peer_cache.cache_put(f"{key}/value", arr.tobytes(), meta)
            index = {"leaves": {"value": meta}, "structure": "leaf"}
            peer_cache.cache_put(f"{key}.__kt_index__",
                                 _json.dumps(index).encode(),
                                 {"kind": "index"})

        t = threading.Thread(target=fill_parent_cache)
        t.start()
        # the key is NOT in the store at all: only the rolling wait on A's
        # cache can satisfy this get
        got = commands.get(key, store_url=store, peer=True)
        t.join()
        np.testing.assert_array_equal(got, arr)
    finally:
        kill_process_tree(proc_a.pid)

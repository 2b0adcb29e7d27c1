"""``kt.put / kt.get / kt.ls / kt.rm`` — the data-store public API.

Reference (``data_store/data_store_cmds.py``): put/get auto-detect payload
kind — CUDA tensors routed to NCCL, paths to rsync. TPU redesign: JAX arrays
and pytrees are staged through host memory (no cross-process device handles
on TPU, SURVEY §2.9) and stored as **per-leaf keys** (``ckpt/layers/wq``),
which is what makes *resharding on get* possible: each leaf is fetched once
and ``jax.device_put`` with the target mesh's NamedSharding places exactly
the shards this host needs.

Data-plane hot path (the trainer→inference weight-sync loop):

- Leaves fan out over a shared thread pool (``KT_STORE_CONCURRENCY``,
  default 8; see :mod:`.netpool`), each worker on its own pooled
  ``requests.Session``. On get, decode + ``jax.device_put`` run inside the
  workers, so device placement pipelines behind the wire.
- Every leaf PUT carries a ``blake2b`` content hash in ``X-KT-Meta``; before
  uploading, the client asks ``POST /kv/diff`` which leaves the store
  already holds current, and skips their bytes entirely. A repeated
  identical put (LoRA-only update, re-pushed checkpoint) therefore moves
  only the index — ``put`` returns ``{leaves, bytes, skipped}``.

Directories ride the ktsync tree protocol; single files ride the KV store.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any, Dict, List, Optional

import requests as _requests

from .. import telemetry
from ..exceptions import DataCorruptionError, DataStoreError
from . import netpool, ring
# origin/fleet resolution lives in ring.py (the check_resilience lint
# keeps any other data_store/ module from rebuilding a single-origin URL);
# these aliases preserve the historical commands.* surface tests poke at
from .ring import _REACHABLE_CACHE  # noqa: F401  (test introspection)
from .ring import resolve_origin as _store_url
from .types import BroadcastWindow

# per-blob fetch accounting by source (pod cache / peer / origin store):
# the P2P fan-out's effectiveness as a scrapeable series, and the source
# tag on every store-fetch span in the waterfall
_FETCHES = telemetry.counter(
    "kt_store_fetches_total",
    "Blob/leaf fetches by serving source",
    labels=("source",))

_INDEX_SUFFIX = ".__kt_index__"


def _is_arraylike(obj: Any) -> bool:
    t = type(obj)
    return (t.__module__.startswith(("jax", "jaxlib", "numpy"))
            and hasattr(obj, "dtype") and hasattr(obj, "shape"))


def _is_pytree_of_arrays(obj: Any) -> bool:
    if _is_arraylike(obj):
        return True
    if isinstance(obj, dict) and obj:
        return all(_is_pytree_of_arrays(v) for v in obj.values())
    if isinstance(obj, (list, tuple)) and obj:
        return all(_is_pytree_of_arrays(v) for v in obj)
    return False


# ---------------------------------------------------------------------------
# put
# ---------------------------------------------------------------------------


def put(key: str, src: Any, store_url: Optional[str] = None,
        broadcast: Optional[BroadcastWindow] = None) -> Dict:
    """Store a directory, file, array, or array pytree under ``key``.

    With ``broadcast=BroadcastWindow(world_size=N)`` the put joins the
    store-side quorum barrier for the key's group after storing, blocking
    until all N participants (this producer + N-1 ``get``-side joiners via
    the same window) have arrived — the reference's coordinated
    trainer→inference weight-sync pattern (SURVEY §3.3).
    """
    url = _store_url(store_url)
    if broadcast is not None:
        result = put(key, src, store_url=url)
        join_broadcast(key, broadcast, store_url=url, member="producer")
        return result
    if isinstance(src, (str, os.PathLike)):
        path = os.fspath(src)
        if os.path.isdir(path):
            from .sync import push_tree
            return push_tree(url, key, path)
        if os.path.isfile(path):
            with open(path, "rb") as f:
                return _kv_put(url, key, f.read(), {"kind": "file"})
        raise DataStoreError(f"put: path {path!r} does not exist")
    if _is_pytree_of_arrays(src):
        return _put_pytree(url, key, src)
    raise DataStoreError(
        f"put: unsupported payload type {type(src).__name__}; expected a "
        "path, an array, or a pytree of arrays")


def _leaf_buffer(host):
    """Zero-copy bytes-like view of a leaf's raw bytes. Reinterprets the
    buffer as uint8 first: numpy refuses to export buffers for extension
    dtypes (ml_dtypes bfloat16 raises ``ValueError: cannot include dtype
    in a buffer``), but a uint8 view of the same memory always exports.
    Falls back to a tobytes copy for non-contiguous or otherwise
    unviewable arrays."""
    import numpy as np

    if host.flags["C_CONTIGUOUS"]:
        try:
            return host.reshape(-1).view(np.uint8).data
        except (ValueError, TypeError):
            pass
    return host.tobytes()


def _leaf_hash(host) -> str:
    """blake2b-20 of the leaf's raw bytes — the content address the delta
    protocol diffs on."""
    return hashlib.blake2b(_leaf_buffer(host), digest_size=20).hexdigest()


def tree_fingerprint_of_hashes(leaf_hashes: Dict[str, str]) -> str:
    """Compose per-leaf content hashes into ONE pytree fingerprint:
    blake2b over the sorted (path, leaf-blake2b) pairs. The single
    definition every fingerprint comparer shares — a trainer's
    ``train.checkpoint.tree_fingerprint`` of its live state, a rollout
    manifest's claimed fingerprint, and a serving replica's ledger of
    already-verified leaf hashes (``serve/rollout.py``) are bit-comparable
    *because* they all compose through here."""
    h = hashlib.blake2b(digest_size=20)
    for path in sorted(leaf_hashes):
        h.update(path.encode())
        h.update(leaf_hashes[path].encode())
    return h.hexdigest()


def _response_meta(r) -> Dict:
    try:
        return json.loads(r.headers.get("X-KT-Meta", "{}"))
    except ValueError:
        return {}


def _verify_content(content: bytes, meta: Dict, expect_hash: Optional[str],
                    key: str, source: str) -> None:
    """End-to-end integrity check on fetched bytes. The content address is
    free — the index records each leaf's blake2b and every kv meta carries
    the hash the server verified at PUT — so a GET that hashes differently
    is corruption somewhere between the store's disk and us. Raises
    :class:`DataCorruptionError`; callers repair (evict cache entry / evict
    peer via ``/route/failed``) or surface the typed error."""
    want = expect_hash or (meta or {}).get("blake2b")
    if not want:
        return                       # pre-hash key: unverifiable
    actual = hashlib.blake2b(content, digest_size=20).hexdigest()
    if actual != want:
        raise DataCorruptionError(
            f"content hash mismatch fetching {key!r} from {source}: "
            f"expected {want}, got {actual}",
            key=key, expected=want, actual=actual, source=source)


def _put_pytree(url: str, key: str, tree: Any) -> Dict:
    import numpy as np

    leaves: Dict[str, Any] = {}
    _flatten(tree, "", leaves)
    index: Dict[str, Any] = {"leaves": {}, "structure": _structure_of(tree)}

    def _stage(arr):
        host = np.asarray(arr)
        if not host.flags["C_CONTIGUOUS"]:
            host = np.ascontiguousarray(host)
        return host

    # Content-hash every leaf first: the hashes drive one /kv/diff
    # round-trip that decides which leaves move at all. Host stagings are
    # NOT retained across the pass — leaves that do need uploading are
    # re-staged inside their worker, so peak client RAM stays
    # O(workers × largest leaf) instead of the full checkpoint size.
    for path, arr in leaves.items():
        host = _stage(arr)
        index["leaves"][path] = {"dtype": str(host.dtype),
                                 "shape": list(host.shape),
                                 "kind": "array",
                                 "blake2b": _leaf_hash(host)}

    current = _kv_diff(
        url, {f"{key}/{p}": m["blake2b"] for p, m in index["leaves"].items()})
    to_upload = [p for p in leaves if f"{key}/{p}" not in current]

    def _upload(path: str) -> int:
        host = _stage(leaves[path])
        # zero-copy uint8 view: the body streams from the array's own
        # buffer, no tobytes duplicate per in-flight worker
        _kv_put(url, f"{key}/{path}", _leaf_buffer(host),
                index["leaves"][path])
        return host.nbytes

    total = sum(netpool.map_concurrent(_upload, to_upload))
    # index lands last: a reader that sees the new index sees complete leaves
    index_bytes = json.dumps(index).encode()
    index_hash = hashlib.blake2b(index_bytes, digest_size=20).hexdigest()
    _kv_put(url, f"{key}{_INDEX_SUFFIX}", index_bytes, {"kind": "index"})
    # index_blake2b: the content address of THIS version's index — what a
    # rollout manifest carries so replicas can fetch a re-put-in-place key
    # content-addressed (stale pod caches become clean misses, never wrong
    # bytes; see _RoutedFetcher(content_alias=True))
    return {"leaves": len(leaves), "bytes": total,
            "skipped": len(leaves) - len(to_upload),
            "index_blake2b": index_hash}


def _kv_diff(url: str, hashes: Dict[str, str]) -> set:
    """Ask the store which of ``hashes`` it already holds current; returns
    the set of keys whose bytes can be skipped. Wire shape mirrors
    ``/tree/diff``: ``{keys: {key: blake2b}} → {missing: [key, ...]}``.
    A store without the endpoint (pre-delta build) skips nothing. On a
    fleet any live node answers (the server fans the probe ring-wide).

    Delta bodies compress past ``COMPRESS_MIN_BYTES`` (ISSUE 10): pure
    hash tables shrink 2-3x and this probe precedes every put. Negotiated
    per request — ``Content-Encoding`` on the way out, ``Accept-Encoding``
    for the reply — so either side can be a build without the codec."""
    if not hashes:
        return set()
    try:
        payload = json.dumps({"keys": hashes}).encode()
        headers = {"Content-Type": "application/json",
                   "Accept-Encoding": netpool.offered_codings()}
        coding = netpool.best_coding(netpool.offered_codings())
        if coding and len(payload) >= netpool.COMPRESS_MIN_BYTES:
            payload = netpool.compress_body(payload, coding)
            headers["Content-Encoding"] = coding
        r = ring.ring_for(url).request("POST", "/kv/diff",
                                       data=payload, headers=headers,
                                       timeout=netpool.store_timeout(60))
        if r.status_code != 200:
            return set()
        body = r.content
        resp_coding = (r.headers.get("Content-Encoding") or "").lower()
        if resp_coding in netpool.CODINGS:
            body = netpool.decompress_body(body, resp_coding)
        return set(hashes) - set(json.loads(body)["missing"])
    except (_requests.RequestException, ValueError, KeyError,
            DataStoreError):
        return set()


def _flatten(tree: Any, prefix: str, out: Dict[str, Any]) -> None:
    if _is_arraylike(tree):
        out[prefix or "value"] = tree
        return
    if isinstance(tree, dict):
        for k, v in tree.items():
            _flatten(v, f"{prefix}/{k}" if prefix else str(k), out)
        return
    if isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            _flatten(v, f"{prefix}/{i}" if prefix else str(i), out)
        return
    raise DataStoreError(f"Unsupported leaf {type(tree).__name__} in pytree")


def _structure_of(tree: Any) -> Any:
    if _is_arraylike(tree):
        return "leaf"
    if isinstance(tree, dict):
        return {k: _structure_of(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_structure_of(v) for v in tree]
    raise DataStoreError(f"Unsupported node {type(tree).__name__}")


def _kv_put(url: str, key: str, data, meta: Dict,
            sess: Optional[_requests.Session] = None) -> Dict:
    # data: bytes or a memoryview (requests streams either with a correct
    # Content-Length via super_len). Both are re-sendable buffers, so the
    # resilient wrapper can retry a transient failure safely — the PUT is
    # content-addressed (X-KT-Meta carries the blake2b) and idempotent.
    # Ring routing hashes the RAW key: the PUT lands on the key's primary
    # replica (which forwards to the rest at write-quorum) and fails over
    # along the replica set when that node is down — a mid-push node loss
    # is absorbed here, not surfaced.
    if sess is not None:
        r = sess.put(f"{url}/kv/{netpool.urlkey(key)}", data=data,
                     headers={"X-KT-Meta": json.dumps(meta)},
                     timeout=netpool.store_timeout())
    else:
        r = ring.ring_for(url).request(
            "PUT", f"/kv/{netpool.urlkey(key)}", key=key, data=data,
            headers={"X-KT-Meta": json.dumps(meta)},
            timeout=netpool.store_timeout())
    if r.status_code != 200:
        raise DataStoreError(f"put {key!r} failed: {r.status_code} {r.text[:200]}")
    return r.json()


# ---------------------------------------------------------------------------
# get — with P2P fan-out (the reference's rolling-participation broadcast)
# ---------------------------------------------------------------------------


class _RoutedFetcher:
    """Fetch subkeys of one top-level key through the store-coordinated
    fan-out (reference tree broadcast, data_store_client.py:376-688):

    - ask the store ``/route`` once: either the store itself (root) or a peer
      pod that already completed this key;
    - pull each subkey from the assigned parent's ``/_kt/data/`` cache,
      falling back to the store on any miss and reporting unreachable
      parents (``/route/failed``, reference report_unreachable);
    - cache every fetched subkey locally and report ``/route/complete`` so
      THIS pod becomes a parent for later joiners — rolling participation,
      O(1) store load for N-pod weight sync;
    - RE-PARENT on a dead/corrupt parent (ISSUE 11): after reporting
      ``/route/failed`` the fetcher re-asks the coordinator for a fresh
      parent (up to ``KT_ROUTE_RETRIES`` times) instead of falling all the
      way back to the origin — a mid-broadcast peer SIGKILL moves this
      pod's remaining bytes to a surviving peer, keeping origin egress
      O(delta) through the failure. Per-source byte totals are kept on
      ``bytes_by_source`` (the rollout coordinator's
      ``kt_rollout_bytes_total{source}`` feed).

    Peer mode is automatic inside pods (POD_IP set: the pod server serves
    the cache) and off for laptops, which can't reach pod IPs; ``peer=``
    overrides.

    Thread-safe: ``_get_pytree`` fans leaf fetches over the netpool
    executor, so one fetcher serves many workers. Route resolution happens
    once (under ``_lock``), the peer no-progress window is shared (progress
    by ANY worker re-arms it; one worker's eviction is seen by all), and
    ``/route/complete`` fires at most once.
    """

    def __init__(self, store_url: str, key: str, peer: Optional[bool],
                 sess: Optional[_requests.Session] = None,
                 content_alias: bool = False):
        self.store_url = store_url
        self.key = key
        # content-addressed peer exchange for MUTABLE keys (ISSUE 11): the
        # pod cache and the parent data route are keyed by
        # ``subkey@hash12`` instead of the bare subkey, so a rollout that
        # re-puts ``rollout/svc/weights`` in place every version can still
        # ride the broadcast tree — a parent still holding the PREVIOUS
        # version's bytes is a clean 404 (the rolling-join poll covers
        # it), never a stale serve. Store-directed requests keep the raw
        # subkey (the origin is always current).
        self.content_alias = bool(content_alias)
        self.ring = ring.ring_for(store_url)
        self.sess = sess            # explicit session override (tests);
        #                             None → per-thread pooled session
        self.enabled = (bool(os.environ.get("POD_IP"))
                        if peer is None else bool(peer))
        self.peer_url: Optional[str] = None
        self.peer_blob_url: Optional[str] = None   # parent's ktblobd, if any
        self._resolved = False
        self._fetched = False
        self._deadline: Optional[float] = None
        self._lock = threading.Lock()
        self._complete_sent = False
        # re-parenting budget: how many fresh /route resolutions a failed
        # parent may trigger before this fetcher stops asking and lets the
        # origin cover the rest (cycles/cascades must terminate)
        self._reroutes = 0
        try:
            self._max_reroutes = int(os.environ.get("KT_ROUTE_RETRIES", "2"))
        except ValueError:
            self._max_reroutes = 2
        # per-source byte totals across this fetcher's lifetime — read by
        # serve/rollout.py to attribute a rollout's bytes to origin vs peer
        self.bytes_by_source: Dict[str, int] = {}

    def _sess(self) -> _requests.Session:
        return self.sess if self.sess is not None else netpool.session()

    def _coord_url(self) -> str:
        """The node that coordinates this key's P2P fan-out (``/route``
        family): the key's primary replica, so every pod in the fleet asks
        the SAME coordinator and the broadcast tree stays one tree."""
        if self.sess is not None:
            return self.store_url
        nodes = self.ring.nodes_for(self.key)
        return nodes[0] if nodes else self.store_url

    def _store_request(self, method: str, path: str, subkey: str,
                       timeout: float, verify=None):
        """Store-directed ops ride the resilient wrapper (retries, backoff,
        Retry-After) AND the ring router (replica failover, epoch refresh);
        an explicitly injected session (tests) stays single-shot and
        single-origin so stubs observe exactly one request."""
        if self.sess is not None:
            r = self.sess.request(method, f"{self.store_url}{path}",
                                  timeout=timeout)
            if verify is not None and r.status_code == 200:
                verify(r)
            return r
        return self.ring.request(method, path, key=subkey, timeout=timeout,
                                 verify=verify)

    def head(self, subkey: str) -> bool:
        """Cheap existence probe against the STORE only (metadata-sized, like
        the reference's MDS lookup): decides the key's kind without pulling
        bulk bytes or touching peer wait windows."""
        try:
            r = self._store_request("HEAD",
                                    f"/kv/{netpool.urlkey(subkey)}", subkey,
                                    timeout=netpool.store_timeout(30))
            return r.status_code == 200
        except (_requests.RequestException, DataStoreError):
            return False

    def _self_url(self) -> Optional[str]:
        ip = os.environ.get("POD_IP")
        if not ip:
            return None
        from ..constants import server_port
        return f"http://{ip}:{server_port()}"

    @staticmethod
    def _self_blob_url() -> Optional[str]:
        """This pod's ktblobd address (the pod server spawns the daemon and
        exports KT_BLOBD_PORT for rank workers)."""
        ip = os.environ.get("POD_IP")
        port = os.environ.get("KT_BLOBD_PORT")
        if ip and port:
            return f"http://{ip}:{port}"
        return None

    def _resolve(self) -> None:
        if not self.enabled:
            return
        with self._lock:
            if self._resolved:
                return
            # resolve INSIDE the lock: concurrent workers wait for the one
            # routing verdict instead of racing past an unset peer_url
            # straight to the store
            self._resolved = True
            try:
                r = self._sess().post(
                    f"{self._coord_url()}/route",
                    json={"key": self.key,
                          "self_url": self._self_url(),
                          "self_blob_url": self._self_blob_url()},
                    timeout=10)
                if r.status_code == 200 and r.json().get("source") == "peer":
                    self.peer_url = r.json()["url"]
                    self.peer_blob_url = r.json().get("blob_url")
            except _requests.RequestException:
                self.peer_url = None

    def fetch(self, subkey: str, timeout: Optional[float] = None,
              expect_hash: Optional[str] = None):
        """GET one subkey (traced): opens a ``store.fetch`` span tagged
        with the serving source (``pod-cache`` / ``peer`` / ``store``) and
        byte count, observes the ``store_fetch`` stage histogram, then
        delegates to :meth:`_fetch_inner`."""
        if telemetry.enabled():
            sp = telemetry.span("store.fetch", key=subkey)
        else:
            sp = telemetry.NOOP_SPAN
        with sp:
            r = self._fetch_inner(subkey, timeout, expect_hash, sp)
            if sp:
                sp.set_attr("status", getattr(r, "status_code", None))
                content = getattr(r, "content", None)
                if content is not None:
                    sp.set_attr("bytes", len(content))
        if sp:
            telemetry.observe_stage("store_fetch", sp.end - sp.start)
        return r

    def _fetch_inner(self, subkey: str, timeout: Optional[float],
                     expect_hash: Optional[str], sp):
        """GET one subkey; returns the response (store-shaped: 200 + body +
        X-KT-Meta). Order: pod-local cache (another rank worker may already
        hold it — zero network), then the assigned peer, then the store.

        Every 200 is **hash-verified** against ``expect_hash`` (the index's
        recorded content address) or, failing that, the blake2b the
        response meta carries. Corrupt bytes never escape this method:
        a bad cache entry is evicted and the fetch falls through; a corrupt
        *peer* is treated exactly like a dead one — evicted via
        ``/route/failed`` so later joiners re-route — and the store covers
        the fetch; only bytes the STORE itself serves corrupt surface, as a
        typed :class:`DataCorruptionError` (the scrubber quarantines them
        server-side so the next attempt is a clean 404 → re-upload).

        Parents are assigned eagerly, possibly before they finish their own
        fetch (the reference's rolling join: the child "blocks until parent
        done"). A 404 from the parent therefore means *not yet* — poll until
        the deadline, then fall back. The ``KT_PEER_WAIT_S`` (default 60s)
        budget is a NO-PROGRESS window shared by all workers: each
        successful peer fetch re-arms it, so a healthy parent mid-download
        of a large multi-leaf get is never evicted, while a parent that
        stops producing for one full window is reported failed and
        everything goes to the store. Connection errors evict the parent
        immediately."""
        import time as _time

        if timeout is None:
            timeout = netpool.store_timeout()
        # ck: the peer-exchange key (content-aliased for mutable rollout
        # keys, the bare subkey otherwise); the STORE is always asked for
        # the raw subkey
        ck = self._peer_key(subkey, expect_hash)
        if self.enabled:
            from .peer_cache import cache_evict, cache_get
            hit = cache_get(ck)
            if hit is not None:
                try:
                    _verify_content(hit[0], hit[1], expect_hash, subkey,
                                    "pod-cache")
                    self._fetched = True
                    sp.set_attr("source", "pod-cache")
                    _FETCHES.inc(source="pod-cache")
                    self._account("pod-cache", hit[0])
                    return _CachedResponse(*hit)
                except DataCorruptionError:
                    # self-heal the pod cache: drop the rotten entry and
                    # fetch fresh bytes below (also stops this pod serving
                    # the rot to its own children via /_kt/data)
                    cache_evict(ck)
        while True:
            # resolve INSIDE the loop: an eviction that armed a re-route
            # (_evict_peer) cleared _resolved, so the next pass re-asks the
            # coordinator for a fresh parent — the tree re-parents around a
            # dead interior peer instead of stampeding the origin
            self._resolve()
            with self._lock:
                peer = self.peer_url
                if peer is not None and self._deadline is None:
                    self._deadline = _time.monotonic() + float(
                        os.environ.get("KT_PEER_WAIT_S", "60"))
            if peer is None:
                break
            try:
                r = self._fetch_from_peer(ck, timeout)
            except _requests.RequestException:
                if self._evict_peer(peer):
                    continue
                break
            if r.status_code == 200:
                try:
                    _verify_content(r.content, _response_meta(r),
                                    expect_hash, subkey, "peer")
                except DataCorruptionError:
                    # a corrupt parent is as bad as an unreachable one:
                    # evict (/route/failed) so nobody else is routed there,
                    # then repair from a fresh parent or the origin
                    if self._evict_peer(peer):
                        continue
                    break
                # progress resets the window: a healthy parent slowly
                # serving a large multi-leaf checkpoint must not be
                # evicted mid-download; only a parent that stops
                # producing for a FULL window is reported failed
                with self._lock:
                    if self.peer_url == peer:
                        self._deadline = None
                self._cache(ck, r)
                sp.set_attr("source", "peer")
                _FETCHES.inc(source="peer")
                self._account("peer", r.content)
                return r
            if r.status_code != 404:
                break            # parent errored; store covers this one
            with self._lock:
                expired = (self.peer_url == peer
                           and self._deadline is not None
                           and _time.monotonic() >= self._deadline)
            if expired:
                # the parent's window is spent: evict it so later
                # joiners aren't routed to a cache that never fills
                if self._evict_peer(peer):
                    continue
                break
            _time.sleep(0.25)
        def _verify(resp):
            # a corrupt replica is failed over like a dead one (the ring
            # router tries the key's siblings); only bytes EVERY replica
            # serves corrupt surface, typed — and are never cached (this
            # pod must not become a parent serving rot)
            _verify_content(resp.content, _response_meta(resp), expect_hash,
                            subkey, "store")

        r = self._store_request("GET", f"/kv/{netpool.urlkey(subkey)}",
                                subkey, timeout=timeout, verify=_verify)
        if r.status_code == 200:
            self._cache(ck, r)
            _FETCHES.inc(source="store")
            self._account("store", r.content)
        sp.set_attr("source", "store")
        return r

    def _peer_key(self, subkey: str, expect_hash: Optional[str]) -> str:
        if self.content_alias and expect_hash:
            return f"{subkey}@{expect_hash[:12]}"
        return subkey

    def _account(self, source: str, content) -> None:
        with self._lock:
            self.bytes_by_source[source] = (
                self.bytes_by_source.get(source, 0) + len(content))

    def _evict_peer(self, peer: str) -> bool:
        """Drop ``peer`` as parent (first evictor wins; concurrent workers
        that raced on the same dead parent are no-ops), tell the
        coordinator (``/route/failed``), and — when the ``KT_ROUTE_RETRIES``
        budget allows — arm a fresh ``/route`` resolution so the NEXT fetch
        re-parents onto a surviving peer instead of falling back to the
        origin. Returns True when a re-route was armed (the caller should
        loop); False sends the caller to the store."""
        with self._lock:
            if self.peer_url != peer:
                return False
            self.peer_url = None
            self.peer_blob_url = None
            self._deadline = None
            reroute = self._reroutes < self._max_reroutes
            if reroute:
                self._reroutes += 1
                self._resolved = False
        self._report_failed(peer)
        return reroute

    def _fetch_from_peer(self, subkey: str, timeout: float):
        """One peer attempt. Prefers the parent's ktblobd (native
        epoll+sendfile daemon — bulk bytes never ride the parent's Python
        event loop); the parent's pod-server route is the fallback and the
        compatibility path for pods without the native build. A blobd
        connection error only disables the FAST PATH — the parent itself is
        judged by its pod-server route."""
        # snapshot: a concurrent worker may evict the peer mid-attempt
        peer_url, blob_url = self.peer_url, self.peer_blob_url
        if blob_url is not None:
            from .peer_cache import entry_hash
            h = entry_hash(subkey)
            try:
                # meta FIRST: it is tiny and lands last in cache_put's
                # rename pair, so its presence proves the (possibly
                # multi-GB) .bin is complete — probing .bin first would
                # download the payload just to discard it when the entry
                # turns out half-written
                rm = self._sess().get(f"{blob_url}/blob/{h}.json",
                                      timeout=30)
                if rm.status_code == 200:
                    entry = json.loads(rm.content)
                    if entry.get("key") == subkey:   # collision paranoia
                        rb = self._sess().get(
                            f"{blob_url}/blob/{h}.bin",
                            timeout=timeout)
                        if rb.status_code == 200:
                            return _CachedResponse(rb.content,
                                                   entry.get("meta", {}))
                elif rm.status_code == 404:
                    # same "not yet" semantics as the pod route: the parent
                    # may still be fetching — let the caller's poll window
                    # decide; don't hammer the python route too
                    return rm
            except (_requests.RequestException, ValueError):
                self.peer_blob_url = None   # fast path off; parent still ok
        return self._sess().get(f"{peer_url}/_kt/data/{netpool.urlkey(subkey)}",
                                timeout=timeout)

    def _cache(self, subkey: str, r) -> None:
        if not self.enabled or self._self_url() is None:
            return
        from .peer_cache import cache_put
        meta = {}
        if "X-KT-Meta" in r.headers:
            try:
                meta = json.loads(r.headers["X-KT-Meta"])
            except ValueError:
                meta = {}
        try:
            cache_put(subkey, r.content, meta)
            self._fetched = True
        except OSError:
            pass                    # cache full/unwritable: still a getter

    def _report_failed(self, peer_url: str) -> None:
        try:
            self._sess().post(f"{self._coord_url()}/route/failed",
                              json={"key": self.key, "url": peer_url},
                              timeout=10)
        except _requests.RequestException:
            pass

    def complete(self) -> None:
        """Become a parent for later joiners (only once we hold data).
        Idempotent: exactly one ``/route/complete`` per fetcher, however
        many workers (or repeated callers) land here."""
        self_url = self._self_url()
        if not (self.enabled and self._fetched and self_url):
            return
        with self._lock:
            if self._complete_sent:
                return
            self._complete_sent = True
        try:
            self._sess().post(f"{self._coord_url()}/route/complete",
                              json={"key": self.key, "url": self_url,
                                    "blob_url": self._self_blob_url()},
                              timeout=10)
        except _requests.RequestException:
            pass


class _CachedResponse:
    """Store-response shim for pod-local cache hits (same .status_code /
    .content / .headers surface the fetch() callers read)."""

    status_code = 200

    def __init__(self, content: bytes, meta: Dict):
        self.content = content
        self.headers = {"X-KT-Meta": json.dumps(meta)} if meta else {}


def get(key: str, dest: Optional[str] = None, store_url: Optional[str] = None,
        sharding: Optional[Any] = None, mesh: Optional[Any] = None,
        rules: Optional[Any] = None, peer: Optional[bool] = None) -> Any:
    """Fetch ``key``. Directories need ``dest``; arrays/pytrees are returned,
    optionally placed onto devices:

    - ``sharding=``  a single NamedSharding applied to every leaf, or
    - ``mesh= + rules=``  a :class:`~kubetorch_tpu.parallel.sharding.
      ShardingRules` table resolved per leaf path — the reshard-on-get path
      (load a checkpoint onto a *different* mesh than it was saved from).

    Inside pods, bulk fetches ride the P2P fan-out (see
    :class:`_RoutedFetcher`); ``peer=False`` forces direct store reads,
    ``peer=True`` forces routing. The key's KIND is decided by cheap HEAD
    probes against the store first, so a file or directory get never burns a
    peer wait window polling for a pytree index that cannot exist.
    """
    url = _store_url(store_url)
    fetcher = _RoutedFetcher(url, key, peer)

    if fetcher.head(f"{key}{_INDEX_SUFFIX}"):
        r = fetcher.fetch(f"{key}{_INDEX_SUFFIX}", timeout=60)
        index = json.loads(r.content)
        tree = _get_pytree(key, index, fetcher, sharding, mesh, rules)
        fetcher.complete()
        return tree

    if fetcher.head(key):
        r = fetcher.fetch(key)
        if r.status_code == 200:
            return _finish_raw(r, dest, sharding, fetcher)

    r = ring.ring_for(url).request(
        "GET", f"/tree/{netpool.urlkey(key)}/manifest", key=key,
        timeout=netpool.store_timeout(60))
    if r.status_code == 200:
        if not dest:
            raise DataStoreError(f"get: {key!r} is a directory tree; pass dest=")
        from .sync import pull_tree
        return pull_tree(url, key, dest)

    # The store has nothing, but peers may (key evicted from the store after
    # the first wave fetched it — the rolling-broadcast tail): probe the
    # fan-out for the index, then the raw key, sharing one wait window.
    if fetcher.enabled:
        r = fetcher.fetch(f"{key}{_INDEX_SUFFIX}", timeout=60)
        if r.status_code == 200:
            index = json.loads(r.content)
            tree = _get_pytree(key, index, fetcher, sharding, mesh, rules)
            fetcher.complete()
            return tree
        r = fetcher.fetch(key)
        if r.status_code == 200:
            return _finish_raw(r, dest, sharding, fetcher)

    raise DataStoreError(f"get: no such key {key!r}")


def _finish_raw(r, dest, sharding, fetcher: "_RoutedFetcher") -> Any:
    meta = json.loads(r.headers.get("X-KT-Meta", "{}"))
    fetcher.complete()
    if meta.get("kind") == "array":
        return _decode_array(r.content, meta, sharding)
    if dest:
        with open(dest, "wb") as f:
            f.write(r.content)
        return dest
    return r.content


def _get_pytree(key, index, fetcher: _RoutedFetcher, sharding, mesh, rules) -> Any:
    def _one(item):
        path, meta = item
        # the index's recorded blake2b is the leaf's content address —
        # fetch() verifies every source (cache/peer/store) against it
        r = fetcher.fetch(f"{key}/{path}", expect_hash=meta.get("blake2b"))
        if r.status_code != 200:
            raise DataStoreError(f"get: missing leaf {key}/{path}")
        leaf_sharding = sharding
        if leaf_sharding is None and mesh is not None and rules is not None:
            from jax.sharding import NamedSharding
            leaf_sharding = NamedSharding(mesh, rules.spec_for(path, mesh))
        # decode + device_put inside the worker: placement of leaf k
        # pipelines behind the wire transfer of leaf k+1
        return path, _decode_array(r.content, meta, leaf_sharding)

    pairs = netpool.map_concurrent(_one, index["leaves"].items())
    return _unflatten(index["structure"], "", dict(pairs))


def _np_dtype(dtype: str):
    import numpy as np

    if dtype == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dtype)


def _decode_array(data: bytes, meta: Dict, sharding: Optional[Any]) -> Any:
    import numpy as np

    # decode into a preallocated writable buffer: frombuffer(...).copy()
    # would materialize a second full-size array while the wire bytes are
    # still alive (2× peak per leaf)
    arr = np.empty(meta["shape"], dtype=_np_dtype(meta["dtype"]))
    view = arr.reshape(-1).view(np.uint8)
    if view.nbytes != len(data):
        raise DataStoreError(
            f"leaf byte-size mismatch: body is {len(data)}B, meta "
            f"{meta['dtype']}{meta['shape']} needs {view.nbytes}B")
    view[:] = np.frombuffer(data, dtype=np.uint8)
    if sharding is not None:
        import jax
        return jax.device_put(arr, sharding)
    return arr


def _unflatten(structure: Any, prefix: str, leaves: Dict[str, Any]) -> Any:
    if structure == "leaf":
        return leaves[prefix or "value"]
    if isinstance(structure, dict):
        return {k: _unflatten(v, f"{prefix}/{k}" if prefix else str(k), leaves)
                for k, v in structure.items()}
    if isinstance(structure, list):
        return [_unflatten(v, f"{prefix}/{i}" if prefix else str(i), leaves)
                for i, v in enumerate(structure)]
    raise DataStoreError("corrupt pytree index")


def join_broadcast(key: str, window: BroadcastWindow,
                   store_url: Optional[str] = None,
                   member: Optional[str] = None) -> List[str]:
    """Join the quorum barrier for ``key``; returns the member list once all
    ``window.world_size`` participants have arrived."""
    import socket
    import uuid

    url = _store_url(store_url)
    member = member or f"{socket.gethostname()}-{uuid.uuid4().hex[:6]}"
    # joining is idempotent (member names are unique per joiner and re-adds
    # are set-inserts), so transport errors retry; a 408 quorum timeout is a
    # real verdict and passes straight through. The barrier group lives on
    # ONE node — the key's ring primary — so every participant joins the
    # same quorum whatever seed URL it was configured with.
    r = ring.ring_for(url).request("POST", "/barrier", key=key, json={
        "group": window.group_id or f"bcast/{key}",
        "world_size": window.world_size,
        "member": member,
        "timeout": window.timeout,
    }, timeout=window.timeout + 10)
    if r.status_code == 408:
        data = r.json()
        raise DataStoreError(
            f"Broadcast window for {key!r} timed out: "
            f"{len(data['joined'])}/{data['world_size']} joined")
    if r.status_code != 200:
        raise DataStoreError(f"barrier join failed: {r.status_code}")
    return r.json()["members"]


def get_broadcast(key: str, window: BroadcastWindow,
                  store_url: Optional[str] = None, **get_kwargs) -> Any:
    """Consumer side of a coordinated broadcast: join the window, then fetch
    (reshard kwargs pass through to :func:`get`)."""
    join_broadcast(key, window, store_url=store_url)
    return get(key, store_url=store_url, **get_kwargs)


# ---------------------------------------------------------------------------
# ls / rm
# ---------------------------------------------------------------------------


def ls(prefix: str = "", store_url: Optional[str] = None) -> List[Dict]:
    url = _store_url(store_url)
    # any live node answers for the whole ring (the server merges its
    # siblings' namespaces before responding)
    r = ring.ring_for(url).request("GET", "/keys", params={"prefix": prefix},
                                   timeout=netpool.store_timeout(60))
    if r.status_code != 200:
        raise DataStoreError(f"ls failed: {r.status_code}")
    # hide internal index keys
    return [k for k in r.json()["keys"] if not k["key"].endswith(_INDEX_SUFFIX)]


def rm(key: str, store_url: Optional[str] = None) -> bool:
    url = _store_url(store_url)
    rg = ring.ring_for(url)
    timeout = netpool.store_timeout(60)
    existed = False
    index_key = f"{key}{_INDEX_SUFFIX}"
    r = rg.request("GET", f"/kv/{netpool.urlkey(index_key)}", key=index_key,
                   timeout=timeout)
    if r.status_code == 200:
        index = json.loads(r.content)
        netpool.map_concurrent(
            lambda path: rg.request(
                "DELETE", f"/kv/{netpool.urlkey(key + '/' + path)}",
                key=f"{key}/{path}", timeout=netpool.store_timeout(60)),
            index["leaves"])
        rg.request("DELETE", f"/kv/{netpool.urlkey(index_key)}",
                   key=index_key, timeout=timeout)
        existed = True
    rd = rg.request("DELETE", f"/kv/{netpool.urlkey(key)}", key=key,
                    timeout=timeout)
    existed = existed or (rd.status_code == 200 and rd.json().get("existed"))
    rt = rg.request("DELETE", f"/tree/{netpool.urlkey(key)}", key=key,
                    timeout=timeout)
    existed = existed or (rt.status_code == 200 and rt.json().get("existed"))
    return existed


# ---------------------------------------------------------------------------
# Small mutable JSON values (checkpoint markers) — single-key, quorum-read
# ---------------------------------------------------------------------------


def put_json(key: str, obj: Any, store_url: Optional[str] = None) -> Dict:
    """Store a small JSON document as ONE kv key (no index/leaf fan-out).

    Built for *mutable* control values — checkpoint commit markers, slot
    pointers — that are deliberately re-put in place: single-key writes
    ride the ring's write-quorum forward, and :func:`get_json` can read
    them back at quorum, so node loss never resurrects a stale marker."""
    url = _store_url(store_url)
    data = json.dumps(obj).encode()
    meta = {"kind": "json",
            "blake2b": hashlib.blake2b(data, digest_size=20).hexdigest()}
    return _kv_put(url, key, data, meta)


def get_json(key: str, store_url: Optional[str] = None,
             quorum: bool = False, default: Any = None) -> Any:
    """Fetch a :func:`put_json` value.

    ``quorum=True`` reads the key from EVERY member of its replica set
    (strictly-local reads, no proxying) and returns the newest copy by
    the server-stamped ``stored_at`` — the read side of the write-quorum
    contract: with W=2 and one node lost, at least one surviving replica
    holds the latest marker, and a revived stale replica can never win.
    Missing key → ``default``."""
    url = _store_url(store_url)
    rg = ring.ring_for(url)
    path = f"/kv/{netpool.urlkey(key)}"
    best: Optional[tuple] = None
    if quorum and rg.size > 1:
        for base in rg.nodes_for(key)[:ring.replication_factor()]:
            try:
                r = netpool.request(
                    "GET", f"{base}{path}",
                    headers={ring.REPLICATED_HEADER: "1"},
                    timeout=netpool.store_timeout(30))
            except (_requests.RequestException, DataStoreError):
                rg.record_failure(base)
                continue
            if r.status_code != 200:
                continue
            meta = _response_meta(r)
            try:
                _verify_content(r.content, meta, None, key, "store")
            except DataCorruptionError:
                continue
            at = float(meta.get("stored_at") or 0.0)
            if best is None or at > best[0]:
                best = (at, r.content)
        if best is not None:
            return json.loads(best[1])
        return default
    try:
        r = rg.request("GET", path, key=key,
                       timeout=netpool.store_timeout(30))
    except DataStoreError:
        return default
    if r.status_code != 200:
        return default
    try:
        return json.loads(r.content)
    except ValueError:
        return default

"""ktsync store server: content-addressed blob store + tree manifests + KV.

The rebuild of the reference's closed-source data-store pod
(``ghcr.io/run-house/kubetorch-data-store``: rsyncd + MDS, SURVEY §2.7) as a
single aiohttp service:

- ``/blob/{hash}``                 GET/PUT content-addressed blobs (CAS)
- ``/tree/{key}/diff|commit|manifest``  delta-sync protocol (see sync.py)
- ``/kv/{key}``                    GET/PUT/DELETE raw values (tensor leaves)
- ``/kv/diff``                     content-hash delta for KV keys: which of
                                   ``{keys: {key: blake2b}}`` are already
                                   current (see commands._kv_diff)
- ``/keys?prefix=``                listing for `kt ls`
- ``/register``                    peer registry (MDS role): which pod holds
                                   which locale="local" key, for P2P gets
- ``/scrub/status`` / ``/scrub/run``  background integrity scrubber
- ``/gc``                          refcounted GC of tree-unreferenced blobs
- ``/ring``                        GET: this node's ring view (epoch,
                                   members, capacity); POST: adopt a newer
                                   membership view (controller/test-fed)

Uploads stream: blob/KV PUT bodies are chunked straight to the ``.tmp``
file with an incremental blake2b, so server memory stays ``O(chunk)``
however large the checkpoint.

Replication (ISSUE 7): with ``KT_STORE_NODES`` (+ ``KT_STORE_SELF_URL``)
set, this node is one member of a consistent-hash ring (``ring.py`` owns
placement). A client PUT commits locally, is forwarded synchronously to
ring successors until write-quorum W acks exist (local commit counts as
one), and repairs the rest of the R-way replica set asynchronously; a
dead successor is skipped in favor of the next live node (ownership
handoff) so a single node loss never fails the write. GETs and diffs
answer ring-wide — a node that lacks the bytes proxies its siblings — so
any node can serve any key. Internal store↔store traffic carries
``X-KT-Replicated`` and is strictly local (no forwarding loops, no chaos,
no epoch checks). Stale client routers are rejected with 409 + typed
``RingEpochMismatch`` before any disk is touched.

Crash consistency (ISSUE 4): every commit rename rides
``durability.durable_replace`` (data fsync + parent-dir fsync,
``KT_STORE_FSYNC``), startup runs ``scrub.recover_store`` (orphan-tmp
sweep + re-verification of objects younger than the last clean-shutdown
marker), the peer registry persists to ``root/peers.json`` with TTL
expiry, mid-stream ENOSPC surfaces as HTTP 507 + typed ``StoreFullError``,
and a rate-limited scrubber quarantines rotted objects to
``root/quarantine/`` so clients see 404 (re-upload/re-route), never
wrong bytes. You can ``kill -9`` this process at any byte offset and
trust the store after restart.

Run: ``python -m kubetorch_tpu.data_store.store_server --port 8873 --root DIR``
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import json
import os
import shutil
import threading
import time
import uuid
from pathlib import Path
from typing import Dict, List, Optional, Tuple
from aiohttp import web

from .. import telemetry
from ..exceptions import (RingEpochMismatch, StoreFullError,
                          package_exception)
from . import durability, scrub
from . import ring as ring_mod
from .ring import REPLICATED_HEADER, RING_EPOCH_HEADER

MAX_BODY = 10 * 1024 ** 3
UPLOAD_CHUNK = 1 << 20          # streaming read granularity for PUT bodies

# untraced plumbing: probes and the observability surface itself must not
# fill the span ring at scrape cadence
_TRACE_EXEMPT = ("/health", "/metrics", "/debug/traces", "/scrub/status",
                 "/ring")

_STORE_REQS = telemetry.counter(
    "kt_store_requests_total",
    "Store-server requests by route class and method",
    labels=("route", "method"))
_STORE_BYTES = telemetry.counter(
    "kt_store_transfer_bytes_total",
    "Bytes served (GET) / accepted (PUT) by the store server",
    labels=("direction",))
_REPLICATION = telemetry.counter(
    "kt_store_replication_total",
    "Replica-forwarded commits by outcome (sync=quorum path, async=repair)",
    labels=("mode", "result"))
_PROXY_FETCHES = telemetry.counter(
    "kt_store_proxy_fetches_total",
    "GETs served by proxying a sibling store node (local miss)",
    labels=("kind",))
_EPOCH_REJECTS = telemetry.counter(
    "kt_store_epoch_rejections_total",
    "Requests rejected because the client's ring epoch was stale")

_INTERNAL_TIMEOUT_S = 60.0      # store↔store forwards/probes


def _internal(request: web.Request) -> bool:
    """True for store↔store traffic (replication forwards, ring-wide
    probes): strictly local semantics — never re-forward, never proxy."""
    return request.headers.get(REPLICATED_HEADER) is not None


class RingState:
    """This node's view of the store ring: membership + epoch + the
    liveness book the forwarding path and the scrubber's re-replication
    sweep share. ``down`` records *when* a sibling first failed — the
    watchdog-style taxonomy one level up: a node inside the TTL window is
    ``Unreachable`` (skip, retry later), one past it is ``Dead`` (its keys
    are re-replicated onto the survivors, ownership handed off)."""

    def __init__(self, self_url: Optional[str], nodes: Optional[List[str]],
                 epoch: Optional[int] = None,
                 replication: Optional[int] = None,
                 quorum: Optional[int] = None,
                 ttl_s: Optional[float] = None):
        self.self_url = (self_url or "").rstrip("/")
        members = [n for n in (nodes or []) if n]
        if self.self_url and self.self_url not in members:
            members.append(self.self_url)
        self._hash = ring_mod.HashRing(members)
        self.epoch = epoch
        self.replication = (replication if replication
                            else ring_mod.replication_factor())
        self.write_quorum = quorum if quorum else ring_mod.write_quorum()
        self.ttl_s = ttl_s if ttl_s is not None else ring_mod.node_ttl_s()
        self._lock = threading.Lock()
        self.down: Dict[str, float] = {}      # url → first-failure wall time

    @property
    def nodes(self) -> List[str]:
        return list(self._hash.nodes)

    @property
    def multi(self) -> bool:
        return len(self._hash.nodes) > 1

    def adopt(self, nodes: List[str], epoch: Optional[int]) -> bool:
        """Adopt a newer membership view; stale/equal epochs are refused
        (last-writer-wins needs a total order, and the epoch is it)."""
        with self._lock:
            if (self.epoch is not None and epoch is not None
                    and epoch <= self.epoch):
                return False
            members = list(nodes)
            if self.self_url and self.self_url not in members:
                members.append(self.self_url)
            self._hash = ring_mod.HashRing(members)
            self.epoch = epoch
            self.down = {u: t for u, t in self.down.items()
                         if u in self._hash.nodes}
            return True

    def mark_down(self, url: str) -> None:
        with self._lock:
            self.down.setdefault(url.rstrip("/"), time.time())

    def mark_up(self, url: str) -> None:
        with self._lock:
            self.down.pop(url.rstrip("/"), None)

    def down_since(self, url: str) -> Optional[float]:
        with self._lock:
            return self.down.get(url.rstrip("/"))

    def dead_past_ttl(self, url: str) -> bool:
        ts = self.down_since(url)
        return ts is not None and time.time() - ts >= self.ttl_s

    def walk(self, key: str) -> List[str]:
        return self._hash.walk(key)

    def siblings(self) -> List[str]:
        return [u for u in self._hash.nodes if u != self.self_url]

    def live_replicas(self, key: str) -> List[str]:
        """Where ``key`` SHOULD live right now: the first R nodes on its
        walk that are not dead past the TTL — the ownership-handoff view
        the re-replication sweep converges the disk state toward."""
        out: List[str] = []
        for u in self.walk(key):
            if not self.dead_past_ttl(u):
                out.append(u)
            if len(out) >= self.replication:
                break
        return out

    def status(self) -> Dict:
        with self._lock:
            down = dict(self.down)
        now = time.time()
        return {
            "epoch": self.epoch,
            "self": self.self_url or None,
            "nodes": self.nodes,
            "replication": self.replication,
            "write_quorum": self.write_quorum,
            "node_ttl_s": self.ttl_s,
            "down": {u: {"down_for_s": round(now - ts, 3),
                         "cause": "Dead" if now - ts >= self.ttl_s
                         else "Unreachable"}
                     for u, ts in down.items()},
        }


def _ring_from_env() -> RingState:
    """Ring view from the deployment env: ``KT_STORE_NODES`` (comma-
    separated members incl. this node) + ``KT_STORE_SELF_URL`` +
    ``KT_STORE_RING_EPOCH`` (default 1 for multi-node rings). Unset →
    degenerate single-node ring; every ring feature is a no-op."""
    raw = os.environ.get("KT_STORE_NODES", "")
    nodes = [u.strip().rstrip("/") for u in raw.split(",") if u.strip()]
    self_url = os.environ.get("KT_STORE_SELF_URL", "").strip()
    epoch: Optional[int] = None
    if nodes:
        try:
            epoch = int(os.environ.get("KT_STORE_RING_EPOCH", "1"))
        except ValueError:
            epoch = 1
    return RingState(self_url, nodes, epoch=epoch)


@web.middleware
async def store_trace_middleware(request: web.Request, handler):
    """Per-request span continuing the client's ``X-KT-Trace`` context —
    every blob/kv/tree transfer shows up in the waterfall with its byte
    count, and injected chaos faults annotate the active span."""
    if request.path.startswith(_TRACE_EXEMPT):
        return await handler(request)
    route = request.path.split("/", 2)[1] if "/" in request.path else ""
    _STORE_REQS.inc(route=route, method=request.method)
    ctx = telemetry.extract(request.headers)
    with telemetry.span("store.server", parent=ctx, path=request.path[:120],
                        method=request.method) as sp:
        try:
            resp = await handler(request)
        except web.HTTPException as e:
            sp.set_attr("status", e.status)
            raise
        if sp:
            sp.set_attr("status", resp.status)
            # GET: the response body IS the transfer; for PUTs the handler
            # already recorded the accepted byte count (a PUT's tiny JSON
            # ack must not overwrite it)
            size = getattr(resp, "content_length", None)
            if size and request.method == "GET":
                sp.set_attr("bytes", size)
                _STORE_BYTES.inc(size, direction="out")
        return resp


class StoreState:
    def __init__(self, root: str, ring: Optional[RingState] = None):
        self.root = Path(root)
        (self.root / "blobs").mkdir(parents=True, exist_ok=True)
        (self.root / "trees").mkdir(parents=True, exist_ok=True)
        (self.root / "kv").mkdir(parents=True, exist_ok=True)
        # ring membership (env-fed by default; create_store_app can inject
        # an explicit view for in-process fleets)
        self.ring = ring if ring is not None else _ring_from_env()
        # crash recovery BEFORE the first request: sweep orphan tmps,
        # re-verify anything the last run may have torn, reload peers
        self.recovery = scrub.recover_store(self.root)
        self.peers: Dict[str, Dict] = scrub.load_peers(self.root)

    @staticmethod
    def _safe(key: str) -> str:
        try:
            return durability.escape_key(durability.validate_key(key))
        except ValueError:
            raise web.HTTPBadRequest(text="bad key")

    def blob_path(self, h: str) -> Path:
        if not h.isalnum():
            raise web.HTTPBadRequest(text="bad hash")
        return self.root / "blobs" / h[:2] / h

    def tree_path(self, key: str) -> Path:
        return self.root / "trees" / f"{self._safe(key)}.json"

    def kv_path(self, key: str) -> Path:
        return self.root / "kv" / self._safe(key)

    def path_for_request(self, http_path: str) -> Optional[Path]:
        """On-disk file behind a ``/blob/..`` or ``/kv/..`` request path —
        the hook the chaos verbs (``corrupt-blob``, ``torn-write``) use to
        fault real stored state deterministically."""
        try:
            if http_path.startswith("/blob/"):
                return self.blob_path(http_path[len("/blob/"):])
            if http_path.startswith("/kv/") and http_path != "/kv/diff":
                return self.kv_path(http_path[len("/kv/"):])
        except web.HTTPBadRequest:
            return None
        return None

    def save_peers(self) -> None:
        scrub.save_peers(self.root, self.peers)

    def mark_clean_shutdown(self) -> None:
        self.save_peers()
        scrub.mark_clean_shutdown(self.root)


def _state(request: web.Request) -> StoreState:
    return request.app["store"]


def _tmp_siblings(path: Path):
    """In-flight ``.tmp`` files for ``path`` (the unique-suffix scheme of
    ``_stream_to_tmp`` / durable_write_bytes)."""
    return path.parent.glob(f"{path.name}.*.tmp") if path.parent.is_dir() \
        else ()


# -- blobs -------------------------------------------------------------------


async def _stream_to_tmp(request: web.Request, path: Path) -> Tuple[Path, str, int]:
    """Stream the request body to a uniquely-named ``.tmp`` sibling of
    ``path`` in ``UPLOAD_CHUNK`` pieces, hashing as it lands. Memory stays
    O(chunk) regardless of body size (``await request.read()`` would buffer
    a whole multi-GB checkpoint in server RAM). The unique tmp name keeps
    concurrent PUTs of the same key from interleaving writes; the commit
    rename stays last-wins-atomic. A full disk mid-stream surfaces as 507 +
    typed ``StoreFullError``, not a retry-forever 500. Returns
    ``(tmp, blake2b_hex, size)``."""
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.name}.{uuid.uuid4().hex[:8]}.tmp")
    hasher = hashlib.blake2b(digest_size=20)
    size = 0
    try:
        with tmp.open("wb") as f:
            async for chunk in request.content.iter_chunked(UPLOAD_CHUNK):
                f.write(chunk)
                hasher.update(chunk)
                size += len(chunk)
    except Exception as e:
        tmp.unlink(missing_ok=True)
        if durability.is_disk_full(e):
            raise web.HTTPInsufficientStorage(
                text=json.dumps(package_exception(StoreFullError(
                    f"store out of space writing {path.name}",
                    path=str(path)))),
                content_type="application/json")
        raise
    _STORE_BYTES.inc(size, direction="in")
    cur = telemetry.current_span()
    if cur is not None:
        cur.set_attr("bytes", size)
    return tmp, hasher.hexdigest(), size


def _commit(tmp: Path, path: Path) -> None:
    """Durable commit rename; ENOSPC during the fsync/rename is still a 507
    (dirty pages can hit the wall at fsync time, not write time)."""
    try:
        durability.durable_replace(tmp, path)
    except OSError as e:
        tmp.unlink(missing_ok=True)
        if durability.is_disk_full(e):
            raise web.HTTPInsufficientStorage(
                text=json.dumps(package_exception(StoreFullError(
                    f"store out of space committing {path.name}",
                    path=str(path)))),
                content_type="application/json")
        raise


# -- ring plumbing: epoch validation, replication forwards, proxy reads ------


@web.middleware
async def ring_epoch_middleware(request: web.Request, handler):
    """Reject data-plane requests routed with a stale ring epoch BEFORE
    they touch disk: a stale router may have hashed the key onto the wrong
    replica set, and a typed 409 is cheaper to absorb (refresh + re-route)
    than a misplaced object is to find. Internal store↔store traffic and
    the ring/probe surface are exempt."""
    st = request.app.get("store")
    ring = getattr(st, "ring", None)
    claimed = request.headers.get(RING_EPOCH_HEADER)
    if (ring is not None and ring.multi and ring.epoch is not None
            and claimed is not None and not _internal(request)
            and not request.path.startswith(("/ring",) + _TRACE_EXEMPT)):
        try:
            actual = int(claimed)
        except ValueError:
            actual = None
        if actual is not None and actual != ring.epoch:
            _EPOCH_REJECTS.inc()
            return web.json_response(package_exception(RingEpochMismatch(
                f"client routed with ring epoch {actual}, this node is at "
                f"{ring.epoch}", expected=ring.epoch, actual=actual)),
                status=409)
    return await handler(request)


def _file_streamer(path: Path):
    """Async chunk generator over a committed file — replica forwards move
    O(chunk) per in-flight body, same budget as the upload path."""
    async def gen():
        loop = asyncio.get_event_loop()
        with path.open("rb") as f:
            while True:
                chunk = await loop.run_in_executor(None, f.read, UPLOAD_CHUNK)
                if not chunk:
                    break
                yield chunk
    return gen()


async def _forward(app: web.Application, base: str, method: str, path: str,
                   file_path: Optional[Path] = None,
                   headers: Optional[Dict[str, str]] = None,
                   json_body: Optional[dict] = None) -> bool:
    """One internal store→store request; False on any failure (the caller
    decides between handoff and async repair). Marks liveness both ways."""
    import aiohttp

    st: StoreState = app["store"]
    hdrs = {REPLICATED_HEADER: "1", **(headers or {})}
    try:
        kwargs: Dict = {"headers": hdrs,
                        "timeout": aiohttp.ClientTimeout(
                            total=_INTERNAL_TIMEOUT_S, connect=3)}
        if file_path is not None:
            kwargs["data"] = _file_streamer(file_path)
        if json_body is not None:
            kwargs["json"] = json_body
        async with app["ring_http"].request(
                method, f"{base}{path}", **kwargs) as r:
            ok = r.status == 200
    except Exception:
        st.ring.mark_down(base)
        return False
    if ok:
        st.ring.mark_up(base)
    return ok


async def _replicate_object(app: web.Application, key: str, path: str,
                            file_path: Path,
                            headers: Optional[Dict[str, str]] = None) -> None:
    """Fan a freshly-committed object out to its replica set.

    The local commit is ack #1; ring successors are forwarded to
    synchronously until ``min(W, R)`` acks exist, skipping recently-failed
    nodes and walking past dead ones to the next live successor (ownership
    handoff — a single node loss mid-push must not fail the write). The
    remaining members of the R-way set repair asynchronously. Quorum
    shortfall on a fully-degraded ring degrades to ack-1 rather than
    failing the client; the scrubber's re-replication sweep restores R.
    """
    st: StoreState = app["store"]
    ring = st.ring
    need_sync = min(ring.write_quorum, ring.replication) - 1
    want_total = ring.replication - 1
    acks = 0
    async_targets: List[str] = []
    for base in [u for u in ring.walk(key) if u != ring.self_url]:
        if acks >= need_sync and acks + len(async_targets) >= want_total:
            break
        if ring.dead_past_ttl(base):
            continue
        if acks >= need_sync:
            async_targets.append(base)
            continue
        if await _forward(app, base, "PUT", path, file_path=file_path,
                          headers=headers):
            acks += 1
            _REPLICATION.inc(mode="sync", result="ok")
        else:
            _REPLICATION.inc(mode="sync", result="failed")
    for base in async_targets:
        async def _repair(b=base):
            ok = await _forward(app, b, "PUT", path, file_path=file_path,
                                headers=headers)
            _REPLICATION.inc(mode="async", result="ok" if ok else "failed")
        asyncio.ensure_future(_repair())
    if acks < need_sync:
        telemetry.add_event("store.quorum_degraded", key=key,
                            acks=acks + 1, want=need_sync + 1)


PROXY_CHUNK = 1 << 20           # streamed proxy-relay granularity


async def _proxy_fetch(request: web.Request, key: str, path: str,
                       kind: str) -> Optional[web.StreamResponse]:
    """Local miss on a multi-node ring: answer from whichever sibling
    holds the object — any node can serve any key. Internal requests never
    proxy (that is how the recursion terminates).

    The relay STREAMS (ISSUE 10): each upstream chunk is written to the
    client as it arrives, so a ring-wide proxy read of a multi-GB blob
    holds O(chunk) RSS on this node — the same discipline streaming PUTs
    have had since ISSUE 1 — instead of buffering the whole body. A
    sibling that dies mid-stream can no longer be papered over (bytes
    already left for the client); the truncated body fails the client's
    blake2b verification and its routed retry lands on a live replica.
    """
    import aiohttp

    st = _state(request)
    ring = st.ring
    if not ring.multi or _internal(request):
        return None
    for base in [u for u in ring.walk(key) if u != ring.self_url]:
        resp: Optional[web.StreamResponse] = None
        try:
            async with request.app["ring_http"].request(
                    request.method, f"{base}{path}",
                    headers={REPLICATED_HEADER: "1"},
                    timeout=aiohttp.ClientTimeout(
                        total=_INTERNAL_TIMEOUT_S, connect=3)) as r:
                if r.status != 200:
                    continue
                ring.mark_up(base)
                _PROXY_FETCHES.inc(kind=kind)
                headers = {}
                if "X-KT-Meta" in r.headers:
                    headers["X-KT-Meta"] = r.headers["X-KT-Meta"]
                ctype = r.headers.get("Content-Type",
                                      "application/octet-stream")
                if request.method == "HEAD":
                    return web.Response(headers=headers, content_type=ctype)
                resp = web.StreamResponse()
                resp.content_type = ctype
                for k, v in headers.items():
                    resp.headers[k] = v
                if r.content_length is not None:
                    resp.content_length = r.content_length
                await resp.prepare(request)
                async for chunk in r.content.iter_chunked(PROXY_CHUNK):
                    await resp.write(chunk)
                await resp.write_eof()
                return resp
        except Exception:
            ring.mark_down(base)
            if resp is not None and resp.prepared:
                # bytes already left for the client: abort THIS response
                # (truncation the client's hash check converts into a
                # routed retry) rather than silently trying a sibling
                raise
    return None


async def _blobs_missing_ringwide(app: web.Application, hashes) -> set:
    """Which of ``hashes`` exist on NO live ring member — the availability
    check ``/tree/diff`` and ``/tree/commit`` answer with, since a blob's
    replica set rarely includes the node coordinating the tree."""
    st: StoreState = app["store"]
    missing = {h for h in hashes if not st.blob_path(h).is_file()}
    if not missing or not st.ring.multi:
        return missing
    import aiohttp

    for base in st.ring.siblings():
        if not missing:
            break
        try:
            async with app["ring_http"].post(
                    f"{base}/tree/__probe__/diff",
                    json={"files": {h: {"hash": h} for h in missing}},
                    headers={REPLICATED_HEADER: "1"},
                    timeout=aiohttp.ClientTimeout(
                        total=_INTERNAL_TIMEOUT_S, connect=3)) as r:
                if r.status == 200:
                    remote_missing = set((await r.json())["missing"])
                    missing &= remote_missing
                    st.ring.mark_up(base)
        except Exception:
            st.ring.mark_down(base)
    return missing


async def ring_get(request: web.Request) -> web.Response:
    st = _state(request)
    try:
        du = shutil.disk_usage(st.root)
        capacity = {"total_bytes": du.total, "used_bytes": du.used,
                    "free_bytes": du.free}
    except OSError:
        capacity = {}
    return web.json_response({**st.ring.status(), "capacity": capacity})


async def ring_post(request: web.Request) -> web.Response:
    """Adopt a newer membership view (controller-fed, or a test driving a
    deterministic membership change). Body: ``{epoch, nodes}``."""
    st = _state(request)
    try:
        body = await request.json()
        nodes = [str(u).rstrip("/") for u in body["nodes"]]
        epoch = int(body["epoch"])
    except (ValueError, KeyError, TypeError):
        return web.json_response({"error": "bad ring view"}, status=400)
    adopted = st.ring.adopt(nodes, epoch)
    return web.json_response({"ok": True, "adopted": adopted,
                              "epoch": st.ring.epoch})


# -- blobs (continued) --------------------------------------------------------


async def put_blob(request: web.Request) -> web.Response:
    st = _state(request)
    h = request.match_info["hash"]
    path = st.blob_path(h)
    tmp, actual, size = await _stream_to_tmp(request, path)
    if actual != h:
        tmp.unlink(missing_ok=True)
        return web.json_response({"error": f"hash mismatch: {actual}"},
                                 status=400)
    _commit(tmp, path)
    if st.ring.multi and not _internal(request):
        await _replicate_object(request.app, h, f"/blob/{h}", path)
    return web.json_response({"ok": True, "size": size})


async def get_blob(request: web.Request) -> web.Response:
    st = _state(request)
    h = request.match_info["hash"]
    path = st.blob_path(h)
    if not path.is_file():
        proxied = await _proxy_fetch(request, h, f"/blob/{h}", kind="blob")
        if proxied is not None:
            return proxied
        return web.json_response({"error": "no such blob"}, status=404)
    return web.FileResponse(path)


# -- trees -------------------------------------------------------------------


async def tree_diff(request: web.Request) -> web.Response:
    st = _state(request)
    body = await request.json()
    files: Dict[str, Dict] = body.get("files", {})
    hashes = {info["hash"] for info in files.values()}
    if _internal(request):
        # ring-wide probe from a sibling: answer for THIS disk only
        missing = {h for h in hashes if not st.blob_path(h).is_file()}
    else:
        missing = await _blobs_missing_ringwide(request.app, hashes)
    return web.json_response({"missing": sorted(missing)})


async def tree_commit(request: web.Request) -> web.Response:
    st = _state(request)
    key = request.match_info["key"]
    body = await request.json()
    files: Dict[str, Dict] = body.get("files", {})
    if _internal(request):
        # replicated manifest: the origin node already proved availability
        still_missing = []
    else:
        still_missing = sorted(await _blobs_missing_ringwide(
            request.app, {info["hash"] for info in files.values()}))
    if still_missing:
        return web.json_response(
            {"error": "missing blobs", "missing": still_missing}, status=409)
    path = st.tree_path(key)
    tmp = path.with_name(f"{path.name}.{uuid.uuid4().hex[:8]}.tmp")
    try:
        tmp.write_text(json.dumps({"files": files,
                                   "committed_at": time.time()}))
    except OSError as e:
        tmp.unlink(missing_ok=True)
        if durability.is_disk_full(e):
            raise web.HTTPInsufficientStorage(
                text=json.dumps(package_exception(StoreFullError(
                    f"store out of space writing manifest {key!r}"))),
                content_type="application/json")
        raise
    _commit(tmp, path)
    if st.ring.multi and not _internal(request):
        # manifests ride the same quorum protocol as the blobs they index
        await _replicate_manifest(request.app, key, files)
    return web.json_response({"ok": True, "files": len(files)})


async def _replicate_manifest(app: web.Application, key: str,
                              files: Dict[str, Dict]) -> None:
    st: StoreState = app["store"]
    ring = st.ring
    acks, need = 0, min(ring.write_quorum, ring.replication) - 1
    for base in [u for u in ring.walk(key) if u != ring.self_url]:
        if acks >= need:
            break
        if ring.dead_past_ttl(base):
            continue
        from urllib.parse import quote
        ok = await _forward(app, base, "POST",
                            f"/tree/{quote(key, safe='/')}/commit",
                            json_body={"files": files})
        _REPLICATION.inc(mode="sync", result="ok" if ok else "failed")
        if ok:
            acks += 1


async def tree_manifest(request: web.Request) -> web.Response:
    st = _state(request)
    key = request.match_info["key"]
    path = st.tree_path(key)
    if not path.is_file():
        from urllib.parse import quote
        proxied = await _proxy_fetch(
            request, key, f"/tree/{quote(key, safe='/')}/manifest",
            kind="manifest")
        if proxied is not None:
            return proxied
        return web.json_response({"error": "no such tree"}, status=404)
    return web.Response(body=path.read_bytes(), content_type="application/json")


async def _fanout_delete(request: web.Request, path: str) -> bool:
    """Deletes must reach every replica (and any handoff stray), or the
    key resurrects from a sibling on the next proxied GET. Best-effort
    fan-out to ALL live siblings; returns True if any reported existed."""
    st = _state(request)
    if not st.ring.multi or _internal(request):
        return False
    import aiohttp

    existed = False
    for base in st.ring.siblings():
        try:
            async with request.app["ring_http"].delete(
                    f"{base}{path}", headers={REPLICATED_HEADER: "1"},
                    timeout=aiohttp.ClientTimeout(
                        total=_INTERNAL_TIMEOUT_S, connect=3)) as r:
                if r.status == 200:
                    st.ring.mark_up(base)
                    with contextlib.suppress(Exception):
                        existed = existed or (await r.json()).get("existed",
                                                                  False)
        except Exception:
            st.ring.mark_down(base)
    return existed


async def tree_delete(request: web.Request) -> web.Response:
    st = _state(request)
    key = request.match_info["key"]
    path = st.tree_path(key)
    existed = path.is_file()
    from urllib.parse import quote
    existed = await _fanout_delete(
        request, f"/tree/{quote(key, safe='/')}") or existed
    # idempotent under concurrent delete (missing_ok), and in-flight .tmp
    # siblings from a racing commit go too — an orphan would resurrect as
    # garbage on the next recovery-less scan
    with contextlib.suppress(OSError):
        path.unlink(missing_ok=True)
    for tmp in _tmp_siblings(path):
        with contextlib.suppress(OSError):
            tmp.unlink(missing_ok=True)
    return web.json_response({"ok": True, "existed": existed})


# -- KV (tensor leaves / small objects) --------------------------------------


async def kv_put(request: web.Request) -> web.Response:
    st = _state(request)
    path = st.kv_path(request.match_info["key"])
    meta = {}
    if "X-KT-Meta" in request.headers:
        try:
            meta = json.loads(request.headers["X-KT-Meta"])
        except ValueError:
            return web.json_response({"error": "bad X-KT-Meta"}, status=400)
    tmp, actual, size = await _stream_to_tmp(request, path)
    claimed = meta.get("blake2b")
    if claimed is not None and claimed != actual:
        # the client addressed content it didn't send — reject before the
        # bad bytes become the delta-skip baseline for every later put
        tmp.unlink(missing_ok=True)
        return web.json_response(
            {"error": f"content hash mismatch: body is {actual}"}, status=400)
    meta["blake2b"] = actual
    meta["size"] = size
    # receive time, preserved verbatim on replica forwards: the ordering
    # fact quorum reads of mutable keys (checkpoint markers) resolve on
    meta.setdefault("stored_at", round(time.time(), 6))
    if os.environ.get("KT_SOAK_BREAK") == "ack-before-commit":
        # DELIBERATELY BROKEN build, reachable only via this env flag: ack
        # the write before the durable commit, deferring both renames (and
        # the quorum forward) to a delayed task. A kill landing inside the
        # window loses an ACKNOWLEDGED write — the soak's durability
        # invariant must catch exactly this, and the shrinker must reduce
        # the schedule to the kill that did it. Never set outside tests.
        async def _commit_later(app=request.app, st=st, path=path, tmp=tmp,
                                meta=dict(meta),
                                internal=_internal(request),
                                key=request.match_info["key"]):
            await asyncio.sleep(float(
                os.environ.get("KT_SOAK_BREAK_DELAY_S", "0.3")))
            _commit(tmp, path)
            meta_tmp = path.with_name(
                f"{path.name}.meta.{uuid.uuid4().hex[:8]}.tmp")
            meta_tmp.write_text(json.dumps(meta))
            _commit(meta_tmp, path.with_name(path.name + ".meta"))
            if st.ring.multi and not internal:
                from urllib.parse import quote
                await _replicate_object(
                    app, key, f"/kv/{quote(key, safe='/')}", path,
                    headers={"X-KT-Meta": json.dumps(meta)})
        asyncio.get_running_loop().create_task(_commit_later())
        return web.json_response({"ok": True, "size": size})
    # data renames first: if we crash before the meta lands, the stale
    # meta makes /kv/diff report the key missing (hash or size mismatch)
    # — a wasted re-upload, not a lost update. The rename pair itself is
    # atomic w.r.t. other requests only within this event loop (no await
    # between them); concurrent conflicting puts to one key are last-wins
    # racy regardless, and kv_diff's size check narrows the stale-meta
    # window it could otherwise misjudge.
    _commit(tmp, path)
    meta_tmp = path.with_name(f"{path.name}.meta.{uuid.uuid4().hex[:8]}.tmp")
    try:
        meta_tmp.write_text(json.dumps(meta))
    except OSError as e:
        meta_tmp.unlink(missing_ok=True)
        if durability.is_disk_full(e):
            # data landed but the meta didn't: /kv/diff reports the key
            # missing (stale/absent meta), so the eventual retry after
            # freeing space re-uploads cleanly — report the truth now
            raise web.HTTPInsufficientStorage(
                text=json.dumps(package_exception(StoreFullError(
                    f"store out of space writing meta for {path.name}",
                    path=str(path)))),
                content_type="application/json")
        raise
    _commit(meta_tmp, path.with_name(path.name + ".meta"))
    if st.ring.multi and not _internal(request):
        key = request.match_info["key"]
        from urllib.parse import quote
        await _replicate_object(
            request.app, key, f"/kv/{quote(key, safe='/')}", path,
            headers={"X-KT-Meta": json.dumps(meta)})
    return web.json_response({"ok": True, "size": size})


async def kv_diff(request: web.Request) -> web.Response:
    """Delta probe for KV keys (mirrors ``/tree/diff``): body
    ``{keys: {key: blake2b}}`` → ``{missing: [key, ...]}`` listing the keys
    whose stored content does NOT match — those are the only ones the
    client must upload. Unknown keys and keys stored before hashes were
    recorded count as missing (re-upload is always safe). On a multi-node
    ring a key counts current when ANY live member holds it current (the
    re-replication sweep restores R-way placement; claiming missing here
    would re-move bytes the ring already has).

    Delta bodies compress (ISSUE 10): both directions are pure hash
    tables that shrink 2-3x, negotiated via ``Content-Encoding`` (request)
    and ``Accept-Encoding`` (response) with the private ``kt-zstd``/``zlib``
    tokens from :mod:`..data_store.netpool` — an old client that sends neither
    header gets the exact pre-compression wire behavior."""
    from . import netpool

    st = _state(request)
    raw = await request.read()
    coding = (request.headers.get("Content-Encoding") or "").lower() or None
    if coding in netpool.CODINGS:
        try:
            raw = netpool.decompress_body(raw, coding)
        except Exception as e:  # noqa: BLE001 — any codec error is a 400
            return web.json_response(
                {"error": f"bad {coding} body: {e}"}, status=400)
    _STORE_BYTES.inc(len(raw), direction="in")
    try:
        body = json.loads(raw) if raw else {}
    except ValueError:
        return web.json_response({"error": "bad json"}, status=400)
    keys: Dict[str, str] = body.get("keys", {})
    missing = []
    for key, want in keys.items():
        try:
            path = st.kv_path(key)
        except web.HTTPBadRequest:
            missing.append(key)
            continue
        meta_path = path.with_name(path.name + ".meta")
        have, meta_size = None, None
        if path.is_file() and meta_path.is_file():
            try:
                stored = json.loads(meta_path.read_text())
                have, meta_size = stored.get("blake2b"), stored.get("size")
            except (ValueError, OSError):
                have = None
        if have is None or have != want:
            missing.append(key)
            continue
        # the meta hash only vouches for the data file it was written
        # alongside; if the data's size no longer matches (meta from an
        # older put, or a concurrent put mid-rename), don't claim current
        try:
            if meta_size is None or os.path.getsize(path) != meta_size:
                missing.append(key)
        except OSError:
            missing.append(key)
    if missing and st.ring.multi and not _internal(request):
        missing = await _kv_missing_ringwide(request.app, missing, keys)
    payload = json.dumps({"missing": sorted(missing)}).encode()
    out_coding = netpool.best_coding(request.headers.get("Accept-Encoding"))
    if out_coding and len(payload) >= netpool.COMPRESS_MIN_BYTES:
        payload = netpool.compress_body(payload, out_coding)
        _STORE_BYTES.inc(len(payload), direction="out")
        return web.Response(body=payload, content_type="application/json",
                            headers={"Content-Encoding": out_coding})
    _STORE_BYTES.inc(len(payload), direction="out")
    return web.Response(body=payload, content_type="application/json")


async def _kv_missing_ringwide(app: web.Application, missing: List[str],
                               wanted: Dict[str, str]) -> List[str]:
    """Narrow a local /kv/diff miss list by asking the live siblings: a
    key some other member already holds current needs no bytes from the
    client."""
    import aiohttp

    st: StoreState = app["store"]
    unresolved = set(missing)
    for base in st.ring.siblings():
        if not unresolved:
            break
        try:
            async with app["ring_http"].post(
                    f"{base}/kv/diff",
                    json={"keys": {k: wanted[k] for k in unresolved}},
                    headers={REPLICATED_HEADER: "1"},
                    timeout=aiohttp.ClientTimeout(
                        total=_INTERNAL_TIMEOUT_S, connect=3)) as r:
                if r.status == 200:
                    unresolved &= set((await r.json())["missing"])
                    st.ring.mark_up(base)
        except Exception:
            st.ring.mark_down(base)
    return sorted(unresolved)


async def kv_get(request: web.Request) -> web.Response:
    st = _state(request)
    key = request.match_info["key"]
    path = st.kv_path(key)
    if not path.is_file():
        from urllib.parse import quote
        proxied = await _proxy_fetch(request, key,
                                     f"/kv/{quote(key, safe='/')}", kind="kv")
        if proxied is not None:
            return proxied
        return web.json_response({"error": "no such key"}, status=404)
    headers = {}
    meta = path.with_name(path.name + ".meta")
    if meta.is_file():
        headers["X-KT-Meta"] = meta.read_text()
    return web.FileResponse(path, headers=headers)


async def kv_delete(request: web.Request) -> web.Response:
    st = _state(request)
    key = request.match_info["key"]
    path = st.kv_path(key)
    existed = path.is_file()
    from urllib.parse import quote
    existed = await _fanout_delete(
        request, f"/kv/{quote(key, safe='/')}") or existed
    meta = path.with_name(path.name + ".meta")
    # each unlink is independent and missing_ok: the meta must go even if
    # the data unlink races a concurrent delete, or a stale meta would
    # make /kv/diff claim a re-uploaded key current against old bytes
    with contextlib.suppress(OSError):
        path.unlink(missing_ok=True)
    with contextlib.suppress(OSError):
        meta.unlink(missing_ok=True)
    for tmp in list(_tmp_siblings(path)) + list(_tmp_siblings(meta)):
        with contextlib.suppress(OSError):
            tmp.unlink(missing_ok=True)
    return web.json_response({"ok": True, "existed": existed})


async def list_keys(request: web.Request) -> web.Response:
    st = _state(request)
    prefix = request.query.get("prefix", "")
    out = []
    for p in (st.root / "kv").iterdir():
        if p.name.endswith((".tmp", ".meta")):
            continue
        key = durability.unescape_key(p.name)
        if key.startswith(prefix):
            out.append({"key": key, "size": p.stat().st_size, "kind": "kv"})
    for p in (st.root / "trees").glob("*.json"):
        if p.name.endswith(".tmp"):
            continue
        key = durability.unescape_key(p.stem)
        if key.startswith(prefix):
            out.append({"key": key, "kind": "tree"})
    if st.ring.multi and not _internal(request):
        # `kt ls` against any node must see the whole ring's namespace
        import aiohttp

        seen = {(k["key"], k["kind"]) for k in out}
        for base in st.ring.siblings():
            try:
                async with request.app["ring_http"].get(
                        f"{base}/keys", params={"prefix": prefix},
                        headers={REPLICATED_HEADER: "1"},
                        timeout=aiohttp.ClientTimeout(
                            total=_INTERNAL_TIMEOUT_S, connect=3)) as r:
                    if r.status != 200:
                        continue
                    st.ring.mark_up(base)
                    for k in (await r.json()).get("keys", []):
                        ident = (k.get("key"), k.get("kind"))
                        if ident not in seen:
                            seen.add(ident)
                            out.append(k)
            except Exception:
                st.ring.mark_down(base)
    return web.json_response({"keys": sorted(out, key=lambda x: x["key"])})


# -- integrity: scrub / gc ----------------------------------------------------


async def scrub_status(request: web.Request) -> web.Response:
    return web.json_response(request.app["scrubber"].status())


async def scrub_run(request: web.Request) -> web.Response:
    """Force one full sweep and return its report — the deterministic hook
    the chaos tests (and operators after an incident) use instead of
    waiting out ``KT_SCRUB_INTERVAL_S``."""
    report = await request.app["scrubber"].sweep()
    return web.json_response({"ok": True, **report})


async def gc_run(request: web.Request) -> web.Response:
    """Refcounted blob GC: body ``{"grace_s": N}`` optionally overrides the
    in-flight-upload grace window (default 1h / ``KT_GC_GRACE_S``)."""
    grace_s = None
    if request.can_read_body:
        try:
            body = await request.json()
            if isinstance(body, dict) and "grace_s" in body:
                grace_s = max(0.0, float(body["grace_s"]))
        except (ValueError, TypeError):
            return web.json_response({"error": "bad grace_s"}, status=400)
    st = _state(request)
    report = await asyncio.get_event_loop().run_in_executor(
        None, scrub.gc_blobs, st.root, grace_s)
    return web.json_response({"ok": True, **report})


# -- broadcast barriers (MDS quorum role, reference WS /ws/gpu-broadcast) -----


async def barrier_join(request: web.Request) -> web.Response:
    """Long-poll quorum barrier: returns once ``world_size`` distinct members
    have joined ``group`` (or 408 on timeout). Used to coordinate N-party
    weight broadcast: the producer puts, everyone joins, getters fetch."""
    st = _state(request)
    body = await request.json()
    group = body["group"]
    world_size = int(body["world_size"])
    member = body["member"]
    timeout = float(body.get("timeout", 600.0))

    barriers = getattr(st, "barriers", None)
    if barriers is None:
        barriers = st.barriers = {}
    entry = barriers.setdefault(group, {"members": set(),
                                        "event": asyncio.Event(),
                                        "world_size": world_size})
    entry["members"].add(member)
    if len(entry["members"]) >= entry["world_size"]:
        entry["event"].set()
    try:
        await asyncio.wait_for(entry["event"].wait(), timeout)
    except asyncio.TimeoutError:
        return web.json_response(
            {"error": "barrier timeout",
             "joined": sorted(entry["members"]),
             "world_size": entry["world_size"]}, status=408)
    # last joiner garbage-collects the group after a grace period
    if len(entry["members"]) >= entry["world_size"]:
        async def _gc():
            await asyncio.sleep(60)
            barriers.pop(group, None)
        asyncio.ensure_future(_gc())
    return web.json_response({"ok": True, "members": sorted(entry["members"])})


# -- P2P fan-out routing (MDS broadcast-coordination role) --------------------
#
# The reference's rolling-participation tree broadcast (design.md, client
# :376-688), finished into a REAL fan-out tree (ISSUE 11): N pods fetching
# one key produce O(1) store load AND bounded per-NIC load. Each getter
# asks /route for a source; the store answers "store" (tree root, depth 0)
# or a peer assigned EAGERLY in arrival order, which may still be fetching
# — the child polls the parent's cache until it fills (the reference's
# "block until parent done" rolling join). Parent assignment is
# depth-aware and out-degree-bounded: the shallowest member with a free
# child slot wins, so the tree fills breadth-first and a multi-GB rollout
# push leaves the origin's NIC exactly once per fanout'd child while every
# interior node serves at most ``KT_ROUTE_FANOUT`` children. Pods also
# register on completion so late joiners fan out from finished holders,
# and /route/failed evicts unreachable parents, frees their slot on THEIR
# parent, and orphans their children — who re-route on the next /route
# call (client-side re-parenting in commands._RoutedFetcher).

ROUTE_STALE_S = 3600.0     # forget members after an hour
_DEFAULT_ROUTE_FANOUT = 4  # children per parent (tensor-tree shape: every
#                            hop is a full-bandwidth transfer, so a small
#                            out-degree keeps each NIC O(fanout × delta)
#                            and depth O(log_fanout N))


def route_fanout() -> int:
    """Max children per broadcast-tree member (``KT_ROUTE_FANOUT``)."""
    try:
        return max(1, int(os.environ.get("KT_ROUTE_FANOUT",
                                         str(_DEFAULT_ROUTE_FANOUT))))
    except ValueError:
        return _DEFAULT_ROUTE_FANOUT


_ROUTE_EVENTS = telemetry.counter(
    "kt_store_route_events_total",
    "Broadcast-tree membership events (evict: parent reported failed; "
    "orphan: child of an evicted parent, re-routes on next /route; "
    "reparent: a previously-orphaned/evicted member re-assigned)",
    labels=("event",))


class _RouteGroup:
    # url → {ts, children, depth, parent, blob_url, complete}
    def __init__(self):
        self.members: Dict[str, Dict] = {}


def _route_groups(st: StoreState) -> Dict[str, _RouteGroup]:
    groups = getattr(st, "route_groups", None)
    if groups is None:
        groups = st.route_groups = {}
    return groups


def _gc_route_groups(groups: Dict[str, _RouteGroup]) -> None:
    """Drop groups whose members have all gone stale — per-iteration weight
    -sync keys ('weights/step-0001', ...) must not accumulate forever in a
    long-lived store. O(total members) per call; route traffic is control
    -plane-rare, so sweeping on every route/complete is cheap."""
    now = time.time()
    for key in [k for k, g in groups.items()
                if all(now - m["ts"] > ROUTE_STALE_S
                       for m in g.members.values()) or not g.members]:
        del groups[key]


def _is_ancestor(group: _RouteGroup, candidate: str, url: str) -> bool:
    """True when ``url`` appears on ``candidate``'s parent chain — a
    re-routing member must never be handed one of its own descendants
    (A→B→A would deadlock both until the peer-wait window expires)."""
    seen = set()
    cur: Optional[str] = candidate
    while cur is not None and cur not in seen:
        if cur == url:
            return True
        seen.add(cur)
        member = group.members.get(cur)
        cur = member.get("parent") if member else None
    return False


def _free_parent_slot(group: _RouteGroup, url: str) -> None:
    member = group.members.get(url)
    parent = member.get("parent") if member else None
    if parent:
        p = group.members.get(parent)
        if p is not None:
            p["children"] = max(0, p.get("children", 0) - 1)


async def route_get(request: web.Request) -> web.Response:
    st = _state(request)
    body = await request.json()
    key = body["key"]
    self_url = body.get("self_url")
    groups = _route_groups(st)
    _gc_route_groups(groups)
    group = groups.setdefault(key, _RouteGroup())
    now = time.time()
    for url in [u for u, m in group.members.items()
                if now - m["ts"] > ROUTE_STALE_S]:
        del group.members[url]
    fanout = route_fanout()
    existing = group.members.get(self_url) if self_url else None
    if existing is not None and existing.get("parent"):
        # a RE-route replaces the caller's edge: free the old parent's
        # child slot first, or re-routing members double-book the fanout
        _free_parent_slot(group, self_url)
        existing["parent"] = None
    # shallowest member with a free child slot wins (ties: fewest children,
    # then url for determinism) — breadth-first tree fill, so depth stays
    # O(log_fanout N) and no member ever serves more than ``fanout``
    # children. Assigned before the caller registers, so it can never be
    # its own parent; on RE-route (caller already registered) its own
    # descendants are excluded too, or the tree would cycle.
    candidates = [(m.get("depth", 1), m.get("children", 0), url)
                  for url, m in group.members.items()
                  if m.get("children", 0) < fanout and url != self_url
                  and not (self_url and _is_ancestor(group, url, self_url))]
    chosen: Optional[str] = None
    if candidates:
        _, _, chosen = min(candidates)
    depth = (group.members[chosen].get("depth", 1) + 1) if chosen else 1
    if self_url:
        member = group.members.setdefault(self_url, {"children": 0})
        member["ts"] = now
        member["depth"] = depth
        member["parent"] = chosen
        if body.get("self_blob_url"):
            # ktblobd address: children stream bulk bytes from the native
            # daemon when the parent runs one
            member["blob_url"] = body.get("self_blob_url")
        else:
            member.setdefault("blob_url", None)
        if existing is not None:
            # a re-route: this member had (or lost) a parent before
            _ROUTE_EVENTS.inc(event="reparent")
    if chosen:
        member = group.members[chosen]
        member["children"] = member.get("children", 0) + 1
        return web.json_response({"source": "peer", "url": chosen,
                                  "blob_url": member.get("blob_url"),
                                  "depth": depth})
    return web.json_response({"source": "store", "depth": depth})


async def route_complete(request: web.Request) -> web.Response:
    """A pod finished fetching ``key`` (it can now serve every subkey):
    (re-)register it fresh so late joiners fan out from finished holders."""
    st = _state(request)
    body = await request.json()
    groups = _route_groups(st)
    group = groups.setdefault(body["key"], _RouteGroup())
    member = group.members.setdefault(body["url"], {"children": 0})
    member["ts"] = time.time()
    member["complete"] = True
    member.setdefault("depth", 1)
    if body.get("blob_url"):
        member["blob_url"] = body["blob_url"]
    _gc_route_groups(groups)
    return web.json_response({"ok": True, "members": len(group.members)})


async def route_failed(request: web.Request) -> web.Response:
    """A getter reports its assigned parent unreachable or corrupt
    (reference report_unreachable): evict so nobody else is routed there,
    free the evicted member's slot on ITS parent, and orphan its children
    — each child re-parents itself on its next /route call (the
    re-parenting half lives in commands._RoutedFetcher, which re-resolves
    after reporting). Returns how many children were orphaned so tests and
    ``kt rollout status`` can see the tree heal."""
    st = _state(request)
    body = await request.json()
    group = _route_groups(st).get(body["key"])
    evicted = False
    orphans = 0
    if group is not None:
        url = body["url"]
        member = group.members.get(url)
        if member is not None:
            _free_parent_slot(group, url)
            del group.members[url]
            evicted = True
            _ROUTE_EVENTS.inc(event="evict")
            for child in group.members.values():
                if child.get("parent") == url:
                    child["parent"] = None
                    orphans += 1
            if orphans:
                _ROUTE_EVENTS.inc(orphans, event="orphan")
    return web.json_response({"ok": True, "evicted": evicted,
                              "orphans": orphans})


# -- peer registry (MDS role) -------------------------------------------------


async def register_peer(request: web.Request) -> web.Response:
    st = _state(request)
    body = await request.json()
    st.peers[body["key"]] = {"ip": body["ip"], "port": body.get("port", 8873),
                             "ts": time.time()}
    # write-through snapshot: /register is control-plane-rare, and without
    # it every store restart silently degrades P2P gets to origin fetches
    st.save_peers()
    return web.json_response({"ok": True})


async def lookup_peer(request: web.Request) -> web.Response:
    st = _state(request)
    key = request.match_info["key"]
    peer = st.peers.get(key)
    if peer is not None:
        ttl = scrub._env_float("KT_PEER_TTL_S", "peer_ttl_s",
                               scrub.DEFAULT_PEER_TTL_S)
        if time.time() - float(peer.get("ts", 0)) > ttl:
            st.peers.pop(key, None)
            peer = None
    if peer is None:
        return web.json_response({"error": "no peer"}, status=404)
    return web.json_response(peer)


async def health(request: web.Request) -> web.Response:
    return web.json_response({"status": "ok"})


async def metrics(request: web.Request) -> web.Response:
    """Prometheus exposition off the shared registry: request/transfer
    counters above plus whatever the scrubber/chaos/resilience layers
    recorded in this process — the store side of the unified metrics
    plane (deploy/metrics.yaml scrapes it like any pod)."""
    st = _state(request)
    telemetry.gauge("kt_store_uptime_seconds",
                    "Seconds since this store process started").set(
        time.time() - request.app["started_at"])
    telemetry.gauge("kt_store_peers", "Registered P2P peers").set(
        len(st.peers))
    telemetry.gauge("kt_store_ring_nodes",
                    "Store-ring members in this node's view").set(
        len(st.ring.nodes))
    if st.ring.epoch is not None:
        telemetry.gauge("kt_store_ring_epoch",
                        "This node's ring membership epoch").set(
            st.ring.epoch)
    return web.Response(body=telemetry.REGISTRY.render().encode(),
                        content_type="text/plain")


async def debug_traces(request: web.Request) -> web.Response:
    """Same flight-recorder surface as the pod server: the store's span
    ring, queryable by trace id or request id."""
    limit = None
    try:
        if request.query.get("limit"):
            limit = max(1, int(request.query["limit"]))
    except ValueError:
        return web.json_response({"error": "bad limit"}, status=400)
    return web.json_response(telemetry.debug_traces_payload(
        request.query.get("q") or request.query.get("request_id"),
        limit=limit))


def create_store_app(root: str,
                     ring: Optional[RingState] = None) -> web.Application:
    # fault injection (KT_CHAOS, see kubetorch_tpu.chaos): lets tests prove
    # the data plane's retry/Retry-After behavior against a real store
    from ..chaos import maybe_chaos_middleware
    chaos_mw, chaos_engine = maybe_chaos_middleware()
    # trace middleware outermost so injected chaos faults annotate the
    # request's span (faults model the network, so chaos stays in front of
    # all store logic); the epoch check sits behind chaos — a stale router
    # must be rejected by the same node a fault-injected one would be
    middlewares = [store_trace_middleware]
    if chaos_mw:
        middlewares.append(chaos_mw)
    middlewares.append(ring_epoch_middleware)
    app = web.Application(client_max_size=MAX_BODY, middlewares=middlewares)
    app["chaos"] = chaos_engine
    app["store"] = StoreState(root, ring=ring)
    app["started_at"] = time.time()
    app["scrubber"] = scrub.Scrubber(
        app["store"].root, ring=app["store"].ring,
        http=lambda: app.get("ring_http"))

    async def _ring_client(app: web.Application):
        # one pooled client session for all store↔store traffic
        # (replication forwards, proxy reads, ring-wide diffs, re-repl)
        import aiohttp

        app["ring_http"] = aiohttp.ClientSession()
        yield
        await app["ring_http"].close()

    app.cleanup_ctx.append(_ring_client)

    async def _scrub_loop(app: web.Application):
        task = None
        if app["scrubber"].interval_s > 0:
            task = asyncio.get_event_loop().create_task(
                app["scrubber"].run_forever())
        yield
        if task is not None:
            task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await task

    async def _on_shutdown(app: web.Application):
        # graceful stop: persist peers + stamp the clean-shutdown marker so
        # the next startup only re-verifies objects written after it
        app["store"].mark_clean_shutdown()

    app.cleanup_ctx.append(_scrub_loop)
    app.on_shutdown.append(_on_shutdown)
    r = app.router
    r.add_get("/health", health)
    r.add_get("/metrics", metrics)
    r.add_get("/debug/traces", debug_traces)
    r.add_get("/ring", ring_get)
    r.add_post("/ring", ring_post)
    r.add_put("/blob/{hash}", put_blob)
    r.add_get("/blob/{hash}", get_blob)
    r.add_post("/tree/{key:.+}/diff", tree_diff)
    r.add_post("/tree/{key:.+}/commit", tree_commit)
    r.add_get("/tree/{key:.+}/manifest", tree_manifest)
    r.add_delete("/tree/{key:.+}", tree_delete)
    r.add_post("/kv/diff", kv_diff)
    r.add_put("/kv/{key:.+}", kv_put)
    r.add_get("/kv/{key:.+}", kv_get)
    r.add_delete("/kv/{key:.+}", kv_delete)
    r.add_get("/keys", list_keys)
    r.add_get("/scrub/status", scrub_status)
    r.add_post("/scrub/run", scrub_run)
    r.add_post("/gc", gc_run)
    r.add_post("/register", register_peer)
    r.add_get("/peer/{key:.+}", lookup_peer)
    r.add_post("/barrier", barrier_join)
    r.add_post("/route", route_get)
    r.add_post("/route/complete", route_complete)
    r.add_post("/route/failed", route_failed)
    return app


def main(argv=None) -> None:
    import argparse

    p = argparse.ArgumentParser(description="kubetorch-tpu data store")
    p.add_argument("--port", type=int, default=8873)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--root", default=os.environ.get("KT_STORE_ROOT", "/data"))
    p.add_argument("--nodes", default=None,
                   help="comma-separated ring member URLs (default: "
                        "KT_STORE_NODES)")
    p.add_argument("--self-url", default=None,
                   help="this node's base URL within --nodes (default: "
                        "KT_STORE_SELF_URL)")
    args = p.parse_args(argv)
    # flags win over env, then _ring_from_env reads the merged view
    if args.nodes is not None:
        os.environ["KT_STORE_NODES"] = args.nodes
    if args.self_url is not None:
        os.environ["KT_STORE_SELF_URL"] = args.self_url
    # flight recorder (ISSUE 20): armed only when KT_OBS_SPOOL is set —
    # a chaos kill-store-node then leaves a readable black box
    from ..obs import maybe_start_recorder
    maybe_start_recorder("store")
    web.run_app(create_store_app(args.root), host=args.host, port=args.port,
                print=lambda *_: None)


if __name__ == "__main__":
    # delegate to the canonical module: running via ``-m`` makes this
    # file ``__main__``, and module-level singletons must not be split
    # from the copies the rest of the package imports
    from kubetorch_tpu.data_store.store_server import main as _canonical_main

    _canonical_main()

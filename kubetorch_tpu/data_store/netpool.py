"""Client-side network pool for the data-plane hot path.

The put/get/sync commands move multi-GB pytrees as many independent HTTP
requests (one per leaf / blob). This module owns the three pieces that make
that path fast and tunable:

- ``store_concurrency()``  — fan-out width, ``KT_STORE_CONCURRENCY`` (def. 8)
- ``store_timeout()``      — per-request timeout, ``KT_STORE_TIMEOUT_S``
- ``session()``            — a **per-thread** pooled ``requests.Session``
  (Session objects are not thread-safe; thread-locals give each executor
  worker its own keep-alive connection pool)
- ``map_concurrent(fn, items)`` — run ``fn`` over ``items`` on a shared
  ``ThreadPoolExecutor``; degrades to a plain serial loop when the
  concurrency knob is 1 (the benchmark baseline) or there is nothing to
  overlap.

The executor is module-level and lazily built so worker threads — and their
thread-local sessions, and therefore their warm connections — survive across
puts/gets instead of being torn down per call.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, List, Optional, TypeVar

import requests as _requests
from requests.adapters import HTTPAdapter

from .. import telemetry

T = TypeVar("T")
R = TypeVar("R")

DEFAULT_CONCURRENCY = 8
DEFAULT_TIMEOUT_S = 600.0


# -- delta-body compression (ISSUE 10) ---------------------------------------
# /kv/diff bodies are pure hash tables ({key: blake2b} in, {missing} out):
# thousands of hex strings compress 2-3x, and at fleet scale the diff probe
# runs before EVERY put. Negotiated via Accept-Encoding/Content-Encoding with
# tokens no transport layer interprets — "kt-zstd" when the optional
# zstandard module exists, stdlib "zlib" otherwise — so urllib3/aiohttp never
# decode behind our back and both sides stay symmetric. The registered "zstd"
# token is NOT one of them: aiohttp and urllib3 treat it as a transport
# coding and decode (or reject) the body before this code sees it.

COMPRESS_MIN_BYTES = 1024
ZSTD = "kt-zstd"
CODINGS = (ZSTD, "zlib")


def _zstd():
    try:
        import zstandard
        return zstandard
    except ImportError:
        return None


def offered_codings() -> str:
    """The ``Accept-Encoding`` value this client offers."""
    return f"{ZSTD}, zlib" if _zstd() is not None else "zlib"


def best_coding(accept: Optional[str]) -> Optional[str]:
    """Pick the best body coding both sides speak, or None."""
    tokens = {t.split(";")[0].strip().lower()
              for t in (accept or "").split(",")}
    if ZSTD in tokens and _zstd() is not None:
        return ZSTD
    if "zlib" in tokens:
        return "zlib"
    return None


def compress_body(data: bytes, coding: str) -> bytes:
    if coding == ZSTD:
        return _zstd().ZstdCompressor().compress(data)
    if coding == "zlib":
        import zlib
        return zlib.compress(data, level=3)
    raise ValueError(f"unknown body coding {coding!r}")


def decompress_body(data: bytes, coding: Optional[str]) -> bytes:
    if not coding:
        return data
    if coding == ZSTD:
        z = _zstd()
        if z is None:
            raise ValueError("zstd body but no zstandard module")
        return z.ZstdDecompressor().decompress(data)
    if coding == "zlib":
        import zlib
        return zlib.decompress(data)
    raise ValueError(f"unknown body coding {coding!r}")


def urlkey(key: str) -> str:
    """Percent-encode a store key for a URL path, keeping ``/`` as the
    separator. The server decodes exactly once (aiohttp), so a key with a
    literal ``%`` or space round-trips instead of being mis-decoded —
    identity for ordinary ``ckpt/run/leaf`` keys."""
    from urllib.parse import quote
    return quote(key, safe="/")


def _host_cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return os.cpu_count() or 1


def store_concurrency() -> int:
    """Data-plane fan-out width. ``KT_STORE_CONCURRENCY`` wins outright;
    unset, the default is 8 capped at the host's CPU count — on a
    single-core host 8 compute-bound workers only thrash the GIL, while
    any real pod gets the full fan-out."""
    raw = os.environ.get("KT_STORE_CONCURRENCY")
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    return min(DEFAULT_CONCURRENCY, _host_cpus())


def store_timeout(default: float = DEFAULT_TIMEOUT_S) -> float:
    """Per-request timeout. ``KT_STORE_TIMEOUT_S`` overrides every hardcoded
    default uniformly (bulk transfers pass 600, control calls pass 60)."""
    try:
        return float(os.environ.get("KT_STORE_TIMEOUT_S", default))
    except (TypeError, ValueError):
        return default


_TLS = threading.local()


def _fleet_size() -> int:
    """Distinct store origins this client talks to (``KT_STORE_NODES``);
    1 for a single-origin deployment."""
    raw = os.environ.get("KT_STORE_NODES", "")
    return max(1, len([u for u in raw.split(",") if u.strip()]))


def session() -> _requests.Session:
    """This thread's pooled Session (created on first use, reused after).

    Multi-origin aware: ``pool_connections`` is the number of per-HOST
    keep-alive pools urllib3 caches, so it must cover every ring replica
    plus peer fetches — sized below the smaller cap, a 3-node fleet would
    silently evict and re-open TCP connections on every replica
    failover. ``pool_maxsize`` bounds sockets per host (the fan-out
    width)."""
    sess = getattr(_TLS, "session", None)
    if sess is None:
        sess = _requests.Session()
        per_host = max(store_concurrency(), 10)
        hosts = max(_fleet_size() + 4, 10)     # replicas + peers + slack
        adapter = HTTPAdapter(pool_connections=hosts, pool_maxsize=per_host)
        sess.mount("http://", adapter)
        sess.mount("https://", adapter)
        _TLS.session = sess
    return sess


_EXEC: ThreadPoolExecutor | None = None
_EXEC_SIZE = 0
_EXEC_LOCK = threading.Lock()


def _executor(size: int) -> ThreadPoolExecutor:
    global _EXEC, _EXEC_SIZE
    with _EXEC_LOCK:
        if _EXEC is None or _EXEC_SIZE != size:
            if _EXEC is not None:
                _EXEC.shutdown(wait=False)
            _EXEC = ThreadPoolExecutor(max_workers=size,
                                       thread_name_prefix="kt-store")
            _EXEC_SIZE = size
        return _EXEC


# ---------------------------------------------------------------------------
# Resilient request wrapper — the data-plane choke point every store op rides
# ---------------------------------------------------------------------------

# per-netloc circuit breakers (opt-in: KT_STORE_BREAKER_THRESHOLD > 0). Off
# by default because a breaker converts "slow store" into fast CircuitOpen
# failures — right for production weight-sync loops, wrong for ad-hoc CLIs.
# Strictly per-NETLOC state: on a multi-origin ring each replica trips (and
# cools down) independently, and the ring router treats one replica's open
# breaker as a failover signal, never as a verdict on its siblings.
_BREAKERS: dict = {}
_BREAKERS_LOCK = threading.Lock()


def _breaker_for(url: str):
    from ..resilience import CircuitBreaker

    threshold = 0
    try:
        threshold = int(os.environ.get("KT_STORE_BREAKER_THRESHOLD", "0"))
    except ValueError:
        pass
    if threshold <= 0:
        return None
    from urllib.parse import urlsplit
    netloc = urlsplit(url).netloc
    with _BREAKERS_LOCK:
        br = _BREAKERS.get(netloc)
        if br is None or br.failure_threshold != threshold:
            br = _BREAKERS[netloc] = CircuitBreaker(
                failure_threshold=threshold,
                cooldown_s=float(os.environ.get("KT_STORE_BREAKER_COOLDOWN_S",
                                                "5")))
        return br


def reset_breakers() -> None:
    with _BREAKERS_LOCK:
        _BREAKERS.clear()


def request(method: str, url: str, *, timeout: Optional[float] = None,
            policy=None, retry_statuses: Optional[frozenset] = None,
            data_factory: Optional[Callable[[], object]] = None,
            record: Optional[List[float]] = None, **kwargs):
    """``session().request`` with the store retry policy applied.

    Every store op is content-addressed (puts are keyed by hash, gets/
    deletes are idempotent by nature), so transient failures — connection
    errors, timeouts, truncated bodies, 502/503/504 — retry by default with
    exponential backoff + full jitter, honoring ``Retry-After`` on 503s.
    Non-retryable statuses (404, 400, 409...) return immediately; callers
    keep their existing status handling.

    ``data_factory`` re-creates a streaming body per attempt (an open file
    object is consumed by the failed attempt and cannot be re-sent).

    A 507 response (store disk full) is NOT retryable — it raises a typed
    :class:`~kubetorch_tpu.exceptions.StoreFullError` (rehydrated from the
    server's packaged body when present) so every call site surfaces the
    capacity verdict instead of hammering a full disk.
    """
    from ..resilience import (ESTABLISHED_TRANSIENT_EXCS, RETRYABLE_STATUSES,
                              retry_after_seconds, store_policy)

    # the partition chaos verb (ISSUE 13) black-holes cross-region
    # requests HERE — before the retry policy, so a provably-dark link
    # surfaces as one immediate connection error the caller's failover
    # (ring sibling, geo spill, anti-entropy lag accounting) absorbs
    # instead of a full backoff budget. No-op unless KT_CHAOS arms it.
    if os.environ.get("KT_CHAOS"):
        from .. import chaos
        chaos.maybe_partition(url)

    policy = policy or store_policy()
    statuses = RETRYABLE_STATUSES if retry_statuses is None else retry_statuses
    breaker = _breaker_for(url)

    def _attempt(info):
        t = timeout if timeout is not None else store_timeout()
        if info.timeout is not None:
            t = min(t, info.timeout)
        if data_factory is not None:
            kwargs["data"] = data_factory()
        return session().request(method, url, timeout=t, **kwargs)

    def _resp_retry(resp):
        if resp.status_code not in statuses:
            return None
        ra = retry_after_seconds(resp)
        return ra if ra is not None else True

    # span per store op, continuing the caller's trace over the wire (the
    # store server parents onto X-KT-Trace) — retry/backoff events from the
    # policy land on it. Disabled tracing → NOOP_SPAN taken without even
    # building the attrs dict: this is the hot path the bench-trace regime
    # holds to ~0% disabled overhead.
    if telemetry.enabled():
        sp = telemetry.span("store.request", method=method,
                            path=url.split("/", 3)[-1][:120])
    else:
        sp = telemetry.NOOP_SPAN
    with sp:
        if sp:
            hdrs = dict(kwargs.get("headers") or {})
            telemetry.inject(hdrs)
            kwargs["headers"] = hdrs
        resp = policy.run(
            _attempt,
            retryable_exc=lambda e: isinstance(e, ESTABLISHED_TRANSIENT_EXCS),
            response_retry_delay=_resp_retry,
            breaker=breaker,
            record=record)
        if sp:
            sp.set_attr("status", resp.status_code)
            clen = resp.headers.get("Content-Length")
            if clen is not None:
                sp.set_attr("bytes", clen)
    if getattr(resp, "status_code", None) == 507:
        raise _store_full_error(resp, url)
    return resp


def _store_full_error(resp, url: str):
    """Typed 507 mapping: rehydrate the server's packaged StoreFullError
    when the body carries one; otherwise synthesize."""
    from ..exceptions import StoreFullError, rehydrate_exception

    exc = None
    try:
        data = resp.json()
        if isinstance(data, dict) and data.get("error_type"):
            exc = rehydrate_exception(data)
    except ValueError:
        pass
    if not isinstance(exc, StoreFullError):
        exc = StoreFullError(f"store at {url} is out of disk space (507)")
    exc.status_code = 507        # transport fact, matching other rehydrations
    return exc


def map_concurrent(fn: Callable[[T], R], items: Iterable[T]) -> List[R]:
    """``[fn(x) for x in items]``, fanned out over the shared executor.

    Result order matches input order. The first worker exception propagates
    (remaining futures are left to finish — they hold no external state
    beyond idempotent HTTP calls). With ``KT_STORE_CONCURRENCY=1`` or a
    single item this is a plain serial loop, which is both the benchmark
    baseline and the re-entrancy escape hatch.
    """
    todo = list(items)
    width = store_concurrency()
    if width <= 1 or len(todo) <= 1:
        return [fn(x) for x in todo]
    futures = [_executor(width).submit(fn, x) for x in todo]
    return [f.result() for f in futures]

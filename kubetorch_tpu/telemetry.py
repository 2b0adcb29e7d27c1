"""Dependency-free tracing + metrics core: the flight recorder every other
layer emits into.

PRs 2–4 built retries, deadlines, a watchdog, and a self-healing store —
none of it observable end-to-end. This module is the one place telemetry
semantics live (ISSUE 5):

**Tracing** — contextvar-propagated spans carrying
``trace_id``/``span_id``/``parent_id`` across process and network
boundaries:

- in-process: :func:`span` opens a child of the current span and binds it
  to the task/thread via a ``ContextVar`` (async tasks and
  ``copy_context``-run executor threads both inherit it);
- across HTTP: :func:`current_header` / :func:`inject` put the active
  context on the wire as ``X-KT-Trace: <trace_id>-<span_id>``;
  :func:`parse_trace` / :func:`extract` reopen it server-side;
- across the process-pool boundary: the call envelope carries the same
  header string, and finished worker spans ship back over the response
  queue into the parent's ring via :func:`ingest_span`.

Finished spans land in a bounded, deduplicating per-process ring
(:data:`RING`) that backs the servers' ``/debug/traces`` endpoints and the
``kt trace <request_id>`` waterfall (:func:`format_waterfall`).

**Metrics** — a Prometheus-exposition registry (:data:`REGISTRY`):
counters, gauges, and histograms with proper label escaping and
``# HELP``/``# TYPE`` headers, plus the per-stage latency histogram
(``kt_stage_seconds``: deserialize, queue_wait, execute, device_transfer,
store_fetch, retry_sleep, shm_copy) every hot-path layer observes into. It backs the
pod and store ``/metrics`` scrape endpoints and ``MetricsPusher``.

**Overhead budget** — tracing defaults on; ``KT_TRACE=0`` disables it and
the disabled fast path is allocation-free: :func:`span` returns a shared
no-op singleton and every event/inject helper short-circuits on one env
lookup. ``make bench-trace`` tracks the enabled-vs-disabled put/get
overhead so later perf PRs inherit an enforced budget, not a guess.

Dependency-free by design (stdlib only, no package imports): every layer —
client, resilience, chaos, netpool, store, watchdog — can import it
without cycles.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
import uuid
from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Tuple

TRACE_HEADER = "X-KT-Trace"
TIMING_HEADER = "X-KT-Timing"
TRACE_ENV = "KT_TRACE"
RING_ENV = "KT_TRACE_RING"

_FALSY = ("0", "false", "off", "no", "")


def enabled() -> bool:
    """Tracing switch: ``KT_TRACE`` env, default on. Read per call (tests
    and the bench toggle it at runtime); a dict lookup on ``os.environ``
    costs nanoseconds and allocates nothing."""
    raw = os.environ.get(TRACE_ENV)
    if raw is None:
        return True
    return raw.strip().lower() not in _FALSY


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


class TraceContext:
    """A remote parent: what crossed the wire in ``X-KT-Trace``."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def header_value(self) -> str:
        return f"{self.trace_id}-{self.span_id}"


def parse_trace(value: Optional[str]) -> Optional[TraceContext]:
    """``"<trace_id>-<span_id>"`` → :class:`TraceContext`; None on absent or
    malformed input (a bad header must never fail a request)."""
    if not value:
        return None
    trace_id, sep, span_id = value.partition("-")
    if not sep or not trace_id or not span_id:
        return None
    return TraceContext(trace_id.strip(), span_id.strip())


def extract(headers) -> Optional[TraceContext]:
    """Parse the trace header off any mapping-like headers object."""
    try:
        return parse_trace(headers.get(TRACE_HEADER))
    except Exception:  # noqa: BLE001 — telemetry must never fail a request
        return None


# In-flight span registry (ISSUE 20): the flight recorder's crash black
# box must capture what a process was DOING when it died, not just what it
# had finished — a SIGKILL mid-call leaves the interesting span open, and
# the ring only ever sees closed ones. Keyed by id(span); entering
# registers, exiting removes. One dict op per span on top of the
# allocation the span already paid; the disabled fast path (NOOP_SPAN)
# never touches it.
_ACTIVE_SPANS: Dict[int, "Span"] = {}
_ACTIVE_LOCK = threading.Lock()


def active_spans() -> List[Dict]:
    """Dicts for every span currently open in this process, oldest first.
    The crash-forensics input: ``obs/`` persists these with each snapshot
    so ``kt blackbox`` can show the in-flight work of a dead process."""
    with _ACTIVE_LOCK:
        spans = list(_ACTIVE_SPANS.values())
    out = []
    for s in spans:
        d = s.to_dict()
        if s.end is None:
            d["end"] = d["end_mono"] = None   # still open — to_dict
            #                                   stamps "now"
        out.append(d)
    return sorted(out, key=lambda d: d.get("start", 0.0))


class Span:
    """One timed operation. Context-manager: entering binds it as the
    current span, exiting records the end time and ships it to the ring."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start", "end",
                 "start_mono", "end_mono", "status", "attrs", "events",
                 "_token")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], attrs: Dict[str, Any]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        # two stamps: wall time for display and cross-host ordering, and
        # the monotonic clock the engine, the rank pool and every caller on
        # this host measure with — on one host spans line up with
        # ``_Request.submitted_at``, ``at_batch_boundary`` readings and a
        # benchmark window without a clock-offset guess
        self.start = time.time()
        self.start_mono = time.monotonic()
        self.end: Optional[float] = None
        self.end_mono: Optional[float] = None
        self.status = "ok"
        self.attrs = attrs
        self.events: List[Tuple[float, float, str, Dict[str, Any]]] = []
        self._token = None

    def __bool__(self) -> bool:
        return True

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def set_status(self, status: str) -> None:
        """For call sites that swallow the exception themselves (the worker
        loop packages errors instead of raising through ``__exit__``)."""
        self.status = status

    def add_event(self, name: str, **attrs: Any) -> None:
        self.events.append((time.time(), time.monotonic(), name, attrs))

    def seconds(self) -> float:
        """Monotonic duration so far (to the end once closed)."""
        end = self.end_mono if self.end_mono is not None \
            else time.monotonic()
        return end - self.start_mono

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "end": self.end if self.end is not None else time.time(),
            "start_mono": self.start_mono,
            "end_mono": (self.end_mono if self.end_mono is not None
                         else time.monotonic()),
            "status": self.status,
            "attrs": dict(self.attrs),
            "events": [{"ts": ts, "mono": mono, "name": n, "attrs": a}
                       for ts, mono, n, a in self.events],
        }

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        with _ACTIVE_LOCK:
            _ACTIVE_SPANS[id(self)] = self
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end = time.time()
        self.end_mono = time.monotonic()
        if exc is not None:
            self.status = "error"
            self.attrs.setdefault("error", type(exc).__name__)
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        with _ACTIVE_LOCK:
            _ACTIVE_SPANS.pop(id(self), None)
        RING.add(self.to_dict())


class _NoopSpan:
    """Shared do-nothing span for the tracing-disabled fast path: a single
    module-level instance, so ``with span(...)`` allocates nothing."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def set_attr(self, key: str, value: Any) -> None:
        pass

    def set_status(self, status: str) -> None:
        pass

    def add_event(self, name: str, **attrs: Any) -> None:
        pass

    def seconds(self) -> float:
        return 0.0

    def to_dict(self) -> None:
        return None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


NOOP_SPAN = _NoopSpan()

_current: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "kt_current_span", default=None)


def _new_id(nbytes: int) -> str:
    return uuid.uuid4().hex[: nbytes * 2]


def span(name: str, parent: Optional[TraceContext] = None, **attrs: Any):
    """Open a span. ``parent`` (a remote :class:`TraceContext`) continues a
    wire-propagated trace; otherwise the current in-process span is the
    parent; otherwise this is a fresh root. Returns :data:`NOOP_SPAN` when
    tracing is disabled."""
    if not enabled():
        return NOOP_SPAN
    if parent is not None:
        trace_id, parent_id = parent.trace_id, parent.span_id
    else:
        cur = _current.get()
        if cur is not None:
            trace_id, parent_id = cur.trace_id, cur.span_id
        else:
            trace_id, parent_id = _new_id(8), None
    return Span(name, trace_id, _new_id(4), parent_id, attrs)


def current_span() -> Optional[Span]:
    return _current.get()


def current_trace_id() -> Optional[str]:
    cur = _current.get()
    return cur.trace_id if cur is not None else None


def current_header() -> Optional[str]:
    """The active context's wire value, or None (disabled / no span)."""
    cur = _current.get()
    if cur is None or not enabled():
        return None
    return f"{cur.trace_id}-{cur.span_id}"


def inject(headers: Dict[str, str]) -> None:
    """Put the active trace context on an outgoing request's headers."""
    value = current_header()
    if value is not None:
        headers[TRACE_HEADER] = value


def add_event(name: str, **attrs: Any) -> None:
    """Record an event on the active span; silent no-op without one — call
    sites (retry loops, chaos) never need to know whether they run inside
    a traced request."""
    cur = _current.get()
    if cur is not None:
        cur.add_event(name, **attrs)


# ---------------------------------------------------------------------------
# Trace ring buffer
# ---------------------------------------------------------------------------


class TraceRing:
    """Bounded, deduplicating store of finished spans, newest-last.

    Keyed by ``(trace_id, span_id)`` so a worker re-shipping a trace's
    spans over the response queue upserts rather than duplicates. Capacity
    from ``KT_TRACE_RING`` (default 2048 spans); oldest evict first.
    """

    def __init__(self, capacity: Optional[int] = None):
        self._cap_override = capacity
        self._spans: "OrderedDict[Tuple[str, str], Dict]" = OrderedDict()
        self._lock = threading.Lock()

    @property
    def capacity(self) -> int:
        if self._cap_override is not None:
            return self._cap_override
        try:
            return max(16, int(os.environ.get(RING_ENV, "2048")))
        except ValueError:
            return 2048

    def add(self, span_dict: Optional[Dict]) -> bool:
        """Upsert; True when this ``(trace_id, span_id)`` was NOT already
        in the ring — the gate for observe-once metric derivation from
        re-shipped span prefixes."""
        if not span_dict:
            return False
        key = (span_dict.get("trace_id", ""), span_dict.get("span_id", ""))
        with self._lock:
            fresh = key not in self._spans
            self._spans[key] = span_dict
            self._spans.move_to_end(key)
            cap = self.capacity
            while len(self._spans) > cap:
                self._spans.popitem(last=False)
        return fresh

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def snapshot(self, limit: Optional[int] = None) -> List[Dict]:
        with self._lock:
            spans = list(self._spans.values())
        return spans[-limit:] if limit else spans

    def find(self, query: str) -> List[Dict]:
        """Spans whose ``trace_id`` — or ``request_id`` attr — equals
        ``query``, oldest first. ``request_id`` lookup resolves to the
        owning trace(s) first, so the whole waterfall comes back even when
        only one span carries the request-id label."""
        with self._lock:
            spans = list(self._spans.values())
        trace_ids = {s["trace_id"] for s in spans
                     if s["trace_id"] == query
                     or s.get("attrs", {}).get("request_id") == query}
        return sorted((s for s in spans if s["trace_id"] in trace_ids),
                      key=lambda s: s.get("start", 0.0))


RING = TraceRing()


def ingest_span(span_dict: Optional[Dict]) -> bool:
    """Feed a span finished in ANOTHER process (rank worker) into this
    process's ring, so one ``/debug/traces`` query sees the whole request.
    Returns True when the span was new to the ring (workers re-ship trace
    prefixes; derive metrics from a span only on its first arrival)."""
    return RING.add(span_dict)


# ---------------------------------------------------------------------------
# Waterfall rendering (kt trace / debug tooling)
# ---------------------------------------------------------------------------


# attribute families a caller's span carries back from the other side: the
# waterfall prints each as a line of its own under the span
_TIMELINE_GROUPS = ("server.", "rank.", "pod.", "engine.", "boot.")


def format_waterfall(spans: Iterable[Dict], width: int = 40) -> str:
    """ASCII waterfall for one trace's spans: tree-indented by parentage,
    each line showing offset+duration bars relative to the earliest start,
    span events (retries, chaos faults, breaker trips) nested beneath."""
    spans = [s for s in spans if s]
    if not spans:
        return "(no spans)"
    spans.sort(key=lambda s: s.get("start", 0.0))
    t0 = spans[0]["start"]
    t1 = max(s.get("end") or s["start"] for s in spans)
    total = max(t1 - t0, 1e-9)
    by_id = {s["span_id"]: s for s in spans}
    children: Dict[Optional[str], List[Dict]] = {}
    for s in spans:
        parent = s.get("parent_id")
        if parent not in by_id:
            parent = None        # orphan (parent evicted/remote): root it
        children.setdefault(parent, []).append(s)

    lines = [f"trace {spans[0]['trace_id']}  "
             f"({len(spans)} spans, {total * 1000:.1f}ms)"]

    def _attrs(s: Dict) -> str:
        keep = {k: v for k, v in s.get("attrs", {}).items()
                if not k.startswith(_TIMELINE_GROUPS)}
        return " ".join(f"{k}={v}" for k, v in sorted(keep.items()))

    def _timeline(s: Dict, depth: int) -> None:
        # what came back from the other side of the call or the deploy
        # (X-KT-Timing, the /ready boot body): durations, one line a group
        attrs = s.get("attrs", {})
        for group in _TIMELINE_GROUPS:
            parts = []
            for k, v in attrs.items():
                if not k.startswith(group):
                    continue
                name = k[len(group):]
                if name.endswith("_ms") and isinstance(v, (int, float)):
                    parts.append(f"{name[:-3]}={v:.2f}ms")
                elif name.endswith("_s") and isinstance(v, (int, float)):
                    parts.append(f"{name[:-2]}={v:.3f}s")
                else:
                    parts.append(f"{name}={v}")
            if parts:
                lines.append(f"   {' ' * width} {'  ' * depth}  ◦ "
                             f"{group[:-1]}: {' '.join(parts)}")

    def _bar(s: Dict) -> str:
        off = (s["start"] - t0) / total
        dur = ((s.get("end") or s["start"]) - s["start"]) / total
        lo = min(int(off * width), width - 1)
        hi = min(max(int((off + dur) * width), lo + 1), width)
        return "·" * lo + "█" * (hi - lo) + "·" * (width - hi)

    def _emit(s: Dict, depth: int) -> None:
        start_ms = (s["start"] - t0) * 1000
        dur_ms = ((s.get("end") or s["start"]) - s["start"]) * 1000
        mark = " !" if s.get("status") == "error" else ""
        lines.append(f"  [{_bar(s)}] {'  ' * depth}{s['name']}{mark}  "
                     f"+{start_ms:.1f}ms {dur_ms:.1f}ms  {_attrs(s)}".rstrip())
        _timeline(s, depth)
        for ev in s.get("events", []):
            ev_ms = (ev["ts"] - t0) * 1000
            ev_attrs = " ".join(f"{k}={v}" for k, v in
                                sorted(ev.get("attrs", {}).items()))
            lines.append(f"   {' ' * width} {'  ' * depth}  • {ev['name']} "
                         f"+{ev_ms:.1f}ms  {ev_attrs}".rstrip())
        for child in children.get(s["span_id"], []):
            _emit(child, depth + 1)

    for root in children.get(None, []):
        _emit(root, 0)
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Metrics: Prometheus-exposition registry
# ---------------------------------------------------------------------------


def escape_label_value(value: Any) -> str:
    """Prometheus exposition label-value escaping: backslash, double-quote,
    and newline (the three the format defines)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _format_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(v) if isinstance(v, float) else str(v)


def _label_str(labelnames: Tuple[str, ...], labelvalues: Tuple[str, ...],
               extra: str = "") -> str:
    parts = [f'{k}="{escape_label_value(v)}"'
             for k, v in zip(labelnames, labelvalues)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Tuple[str, ...]):
        self.name = name
        self.help = help
        self.labelnames = labelnames
        self._lock = threading.Lock()
        self._values: Dict[Tuple[str, ...], Any] = {}

    def _key(self, labels: Dict[str, Any]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: got labels {sorted(labels)}, "
                f"declared {sorted(self.labelnames)}")
        return tuple(str(labels[k]) for k in self.labelnames)

    def header(self) -> List[str]:
        out = []
        if self.help:
            out.append(f"# HELP {self.name} {self.help}")
        out.append(f"# TYPE {self.name} {self.kind}")
        return out


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: Any) -> float:
        with self._lock:
            return self._values.get(self._key(labels), 0.0)

    def render(self) -> List[str]:
        out = self.header()
        with self._lock:
            items = sorted(self._values.items())
        if not items and not self.labelnames:
            items = [((), 0.0)]
        for key, v in items:
            out.append(f"{self.name}{_label_str(self.labelnames, key)} "
                       f"{_format_value(v)}")
        return out


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: Any) -> float:
        with self._lock:
            return self._values.get(self._key(labels), 0.0)

    def render(self) -> List[str]:
        out = self.header()
        with self._lock:
            items = sorted(self._values.items())
        if not items and not self.labelnames:
            items = [((), 0.0)]
        for key, v in items:
            out.append(f"{self.name}{_label_str(self.labelnames, key)} "
                       f"{_format_value(v)}")
        return out


# Default latency buckets: sub-ms (header parse), request-scale, and the
# multi-second tail a cold jit compile or multi-GB fetch actually produces.
DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str, labelnames: Tuple[str, ...],
                 buckets: Tuple[float, ...] = DEFAULT_BUCKETS):
        super().__init__(name, help, labelnames)
        self.buckets = tuple(sorted(buckets))

    def observe(self, value: float, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            entry = self._values.get(key)
            if entry is None:
                entry = self._values[key] = {
                    "buckets": [0] * len(self.buckets), "sum": 0.0,
                    "count": 0}
            for i, le in enumerate(self.buckets):
                if value <= le:
                    entry["buckets"][i] += 1
            entry["sum"] += value
            entry["count"] += 1

    def count(self, **labels: Any) -> int:
        with self._lock:
            entry = self._values.get(self._key(labels))
            return entry["count"] if entry else 0

    def render(self) -> List[str]:
        out = self.header()
        with self._lock:
            items = sorted((k, {"buckets": list(v["buckets"]),
                                "sum": v["sum"], "count": v["count"]})
                           for k, v in self._values.items())
        for key, entry in items:
            for le, n in zip(self.buckets, entry["buckets"]):
                lbl = _label_str(self.labelnames, key,
                                 extra=f'le="{_format_value(le)}"')
                out.append(f"{self.name}_bucket{lbl} {n}")
            lbl = _label_str(self.labelnames, key, extra='le="+Inf"')
            out.append(f"{self.name}_bucket{lbl} {entry['count']}")
            base = _label_str(self.labelnames, key)
            out.append(f"{self.name}_sum{base} "
                       f"{_format_value(entry['sum'])}")
            out.append(f"{self.name}_count{base} {entry['count']}")
        return out


class MetricsRegistry:
    """Named metric registry with get-or-create semantics (call sites
    declare inline; the first declaration wins, a kind mismatch raises)."""

    def __init__(self):
        self._metrics: "OrderedDict[str, _Metric]" = OrderedDict()
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name: str, help: str,
                       labels: Iterable[str], **kwargs) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as {m.kind}")
                return m
            m = cls(name, help, tuple(labels), **kwargs)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Iterable[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Iterable[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Iterable[str] = (),
                  buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def render(self) -> str:
        if self is REGISTRY:
            # every /metrics endpoint renders the global registry, so the
            # build-identity gauge (ISSUE 20) rides along by construction —
            # a future endpoint cannot forget to export it
            build_info_metrics()
        with self._lock:
            metrics = list(self._metrics.values())
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + ("\n" if lines else "")

    def snapshot(self) -> Dict[str, Dict]:
        """JSON-safe structural dump of every registered metric: the flight
        recorder's (ISSUE 20) input. Label tuples become ``\\x1f``-joined
        string keys (label values never contain the unit separator);
        histogram entries keep their cumulative bucket lists so a reader
        can diff two snapshots bucket-by-bucket."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: Dict[str, Dict] = {}
        for m in metrics:
            with m._lock:
                items = list(m._values.items())
            entry: Dict[str, Any] = {"kind": m.kind,
                                     "labels": list(m.labelnames)}
            if isinstance(m, Histogram):
                entry["le"] = [_format_value(b) for b in m.buckets]
                entry["values"] = {
                    "\x1f".join(k): {"buckets": list(v["buckets"]),
                                     "sum": v["sum"], "count": v["count"]}
                    for k, v in items}
            else:
                entry["values"] = {"\x1f".join(k): v for k, v in items}
            out[m.name] = entry
        return out

    def catalog(self) -> List[Tuple[str, str, str]]:
        """``(series, type, labels)`` rows for every registered metric,
        registration order — the source the observability docs' metrics
        table is generated from (and drift-tested against)."""
        with self._lock:
            metrics = list(self._metrics.values())
        return [(m.name, m.kind, ", ".join(m.labelnames) or "—")
                for m in metrics]


REGISTRY = MetricsRegistry()


def counter(name: str, help: str = "", labels: Iterable[str] = ()) -> Counter:
    return REGISTRY.counter(name, help, labels)


def gauge(name: str, help: str = "", labels: Iterable[str] = ()) -> Gauge:
    return REGISTRY.gauge(name, help, labels)


def histogram(name: str, help: str = "", labels: Iterable[str] = (),
              buckets: Tuple[float, ...] = DEFAULT_BUCKETS) -> Histogram:
    return REGISTRY.histogram(name, help, labels, buckets=buckets)


def render_untyped_gauges(lines: Dict[str, Any]) -> str:
    """Exposition text for ad-hoc gauge lines whose keys may already carry
    a ``{label="..."}`` suffix (the TPU HBM series, ``kt_user_*`` merges):
    one ``# TYPE <base> gauge`` header per base metric name, values as-is.
    The one sanctioned alternative to hand-rolled ``"{k} {v}"`` joins
    (``scripts/check_resilience.py`` lints for those)."""
    out: List[str] = []
    seen = set()
    for key, value in lines.items():
        base = key.split("{", 1)[0]
        if base not in seen:
            seen.add(base)
            out.append(f"# TYPE {base} gauge")
        out.append(f"{key} {value}")
    return "\n".join(out) + ("\n" if out else "")


# ---------------------------------------------------------------------------
# Per-stage latency instrumentation
# ---------------------------------------------------------------------------

# The stage taxonomy every later perf PR measures against (docs/
# observability.md "Span taxonomy"). Free-form stages are allowed; these
# are the named hot-path phases of one request.
STAGES = ("deserialize", "queue_wait", "execute", "device_transfer",
          "store_fetch", "retry_sleep", "shm_copy", "rollout_apply")

_STAGE_HIST: Optional[Histogram] = None


def stage_histogram() -> Histogram:
    global _STAGE_HIST
    if _STAGE_HIST is None:
        _STAGE_HIST = histogram(
            "kt_stage_seconds",
            "Per-stage request latency (deserialize, queue_wait, execute, "
            "device_transfer, store_fetch, retry_sleep, shm_copy, "
            "rollout_apply)",
            labels=("stage",))
    return _STAGE_HIST


def observe_stage(stage_name: str, seconds: float) -> None:
    stage_histogram().observe(seconds, stage=stage_name)


class _StageTimer:
    """``with stage("deserialize"):`` — a span (when tracing is on) plus a
    ``kt_stage_seconds`` observation (always; one dict op, no allocation
    churn on the disabled path)."""

    __slots__ = ("stage", "attrs", "seconds", "_span", "_t0")

    def __init__(self, stage_name: str, attrs: Dict[str, Any]):
        self.stage = stage_name
        self.attrs = attrs
        self.seconds = 0.0           # readable once the block has exited

    def __enter__(self):
        self._span = span(f"stage.{self.stage}", **self.attrs)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self._span

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self._t0
        observe_stage(self.stage, self.seconds)
        note_timing(self.stage, self.seconds)
        self._span.__exit__(exc_type, exc, tb)


def stage(stage_name: str, **attrs: Any) -> _StageTimer:
    return _StageTimer(stage_name, attrs)


class _HistTimer:
    """``with timed(hist, phase="compute"):`` — observe wall-clock into an
    arbitrary histogram. The span-free sibling of :func:`stage` for
    per-iteration hot loops (a train step fires thousands of times; a Span
    per step would churn the ring for no diagnostic value)."""

    __slots__ = ("hist", "labels", "_t0")

    def __init__(self, hist: Histogram, labels: Dict[str, Any]):
        self.hist = hist
        self.labels = labels

    def __enter__(self) -> "_HistTimer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.hist.observe(time.perf_counter() - self._t0, **self.labels)


def timed(hist: Histogram, **labels: Any) -> _HistTimer:
    """Time a block into ``hist`` (no span). The sanctioned way for code
    outside this module to measure latency when a ``kt_stage_seconds``
    stage is the wrong shape (e.g. phase-labelled step anatomy)."""
    return _HistTimer(hist, labels)


# ---------------------------------------------------------------------------
# One call's server-side timeline, handed back to the caller (ISSUE 26)
# ---------------------------------------------------------------------------

# The pod answers a call with ``X-KT-Timing``, a ``Server-Timing``-style
# list of ``name;dur=<ms>`` pairs (``;n=<count>`` for the one count) built
# from what it observed for THAT request, and the client writes them onto
# its ``client.call`` span. Durations only, so the header holds across
# hosts whose clocks disagree. The caller's ring alone then shows where the
# call's time went, with the pod long gone.
#
# Collected through a ContextVar holding one mutable dict per request: every
# ``stage(...)`` that exits inside the request's context adds its seconds
# (tasks and ``copy_context`` threads share the dict, not a copy). Nothing
# is collected, formatted or sent when tracing is disabled.

_call_timing: contextvars.ContextVar[Optional[Dict[str, Any]]] = \
    contextvars.ContextVar("kt_call_timing", default=None)

# what the rank reports of its own side of a call (``rank.execute``) and of
# an engine request the call produced (``engine.*``)
RANK_EXECUTE = "rank.execute"


def begin_call_timing() -> Optional[Dict[str, Any]]:
    """Open this request's timing collector and bind it to the context;
    None (and nothing bound) when tracing is disabled."""
    if not enabled():
        return None
    timing: Dict[str, Any] = {}
    _call_timing.set(timing)
    return timing


def current_call_timing() -> Optional[Dict[str, Any]]:
    return _call_timing.get()


def add_timing(timing: Optional[Dict[str, Any]], name: str,
               seconds: float) -> None:
    """Add a server stage's seconds to a collector (a stage that runs
    twice, as ``shm_copy`` does, sums)."""
    if timing is not None:
        key = "server." + name
        timing[key] = timing.get(key, 0.0) + seconds


def note_timing(name: str, seconds: float) -> None:
    """:func:`add_timing` to the current request's collector, if any."""
    add_timing(_call_timing.get(), name, seconds)


def merge_timing(timing: Optional[Dict[str, Any]],
                 rank: Optional[Dict[str, Any]]) -> None:
    """Hand a rank's reply-side timing to the request's collector. A call
    fanned out over several ranks keeps the slowest rank's."""
    if timing is None or not rank:
        return
    have = timing.get("_rank")
    if have is None or \
            rank.get(RANK_EXECUTE, 0.0) > have.get(RANK_EXECUTE, 0.0):
        timing["_rank"] = rank


def finish_call_timing(timing: Dict[str, Any]) -> Dict[str, Any]:
    """The request's collector as one flat mapping for the header: the
    pod's own stages plus the (slowest) rank's, a stage seen on both sides
    summed. ``server.respond`` is what is left of ``server.execute`` after
    the wait for the rank and the rank's own time: the reply's way back
    through the pool."""
    out = {k: v for k, v in timing.items() if k != "_rank"}
    for key, value in (timing.get("_rank") or {}).items():
        if key.startswith("server."):
            out[key] = out.get(key, 0.0) + value
        else:
            out[key] = value
    if "server.execute" in out and RANK_EXECUTE in out:
        respond = (out["server.execute"] - out[RANK_EXECUTE]
                   - out.get("server.queue_wait", 0.0))
        if respond >= 0.0:
            out["server.respond"] = respond
    return out


def engine_timing(life: Dict[str, Any]) -> Dict[str, Any]:
    """An ``engine.request`` event's numbers under the header's names."""
    out: Dict[str, Any] = {}
    for key in ("queue", "prefill", "decode", "host", "wait"):
        if life.get(key + "_s") is not None:
            out["engine." + key] = life[key + "_s"]
    for key in ("blocks", "blocks_ahead"):
        if life.get(key) is not None:
            out["engine." + key] = int(life[key])
    for key, value in life.items():
        if key.startswith(("host.", "wait.")):
            out["engine." + key[:-2]] = value       # strip the ``_s``
    return out


def format_timing(timing: Dict[str, Any]) -> str:
    """``{"server.execute": 0.0123, "engine.blocks": 4}`` →
    ``server.execute;dur=12.3, engine.blocks;n=4``."""
    parts = []
    for name, value in timing.items():
        if isinstance(value, int):
            parts.append(f"{name};n={value}")
        else:
            parts.append(f"{name};dur={1e3 * value:.3f}")
    return ", ".join(parts)


def parse_timing(value: Optional[str]) -> Dict[str, Any]:
    """The header as span attributes: ``<name>_ms`` (float milliseconds)
    for a ``dur``, ``<name>`` (int) for an ``n``. Entries that do not parse
    are skipped; absent or garbage input gives {} and never raises."""
    out: Dict[str, Any] = {}
    if not value or not isinstance(value, str):
        return out
    for entry in value.split(","):
        name, _, param = entry.strip().partition(";")
        key, _, raw = param.partition("=")
        if not name or " " in name:
            continue
        try:
            if key == "dur":
                out[name + "_ms"] = float(raw)
            elif key == "n":
                out[name] = int(raw)
        except ValueError:
            continue
    return out


def apply_timing(sp, header_value: Optional[str]) -> None:
    """Write a response's ``X-KT-Timing`` onto the caller's span."""
    if not sp:
        return
    for key, value in parse_timing(header_value).items():
        sp.set_attr(key, value)


# ---------------------------------------------------------------------------
# Phase clocks: a hot loop's anatomy, and a one-shot sequence's laps
# ---------------------------------------------------------------------------


class _Phase:
    __slots__ = ("clock", "name", "_note")

    def __init__(self, clock: "PhaseClock", name: str):
        self.clock = clock
        self.name = name

    def __enter__(self) -> "_Phase":
        # the clock's own stamps are the outermost: what the annotation
        # costs is inside the phase it names
        clock = self.clock
        clock._open = (self.name, time.monotonic())
        self._note = clock.annotate(clock.prefix + self.name) \
            if clock.annotate is not None else None
        if self._note is not None:
            self._note.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        clock = self.clock
        name, t0 = clock._open
        clock._open = None
        dt = time.monotonic() - t0
        clock.seconds[name] = clock.seconds.get(name, 0.0) + dt
        clock._block[name] = clock._block.get(name, 0.0) + dt


class PhaseClock:
    """Where one thread's loop spends its time, phase by phase.

    ``with clock.phase("fetch"):`` adds the block's seconds to the
    cumulative ``seconds["fetch"]`` and, at the next :meth:`end_block`, to
    one observation of ``hist{phase="fetch"}`` per phase per block (a phase
    entered several times in a block observes their sum). ``annotate``, a
    callable ``name -> context manager`` such as
    ``jax.profiler.TraceAnnotation``, puts the same phases on a profiler's
    host timeline as ``<prefix><phase>`` whenever anyone traces (this
    module stays free of jax). Phases do not nest. Single-threaded by
    contract: the owning loop is the only writer; :meth:`snapshot` may be
    called from that thread at any point, also inside a phase, whose
    seconds so far it counts."""

    def __init__(self, hist: Optional[Histogram] = None, annotate=None,
                 prefix: str = "", wait: Iterable[str] = ()):
        self.hist = hist
        self.annotate = annotate
        self.prefix = prefix
        self.wait = frozenset(wait)     # phases spent waiting on the device
        self.seconds: Dict[str, float] = {}
        self.blocks = 0
        self._block: Dict[str, float] = {}
        self._open: Optional[Tuple[str, float]] = None

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def end_block(self) -> None:
        """Observe what this pass of the loop spent in each phase."""
        if self.hist is not None:
            for name, dt in self._block.items():
                self.hist.observe(dt, phase=name)
        self._block = {}

    def snapshot(self) -> Dict[str, float]:
        """Cumulative seconds per phase as of now."""
        out = dict(self.seconds)
        if self._open is not None:
            name, t0 = self._open
            out[name] = out.get(name, 0.0) + time.monotonic() - t0
        return out

    def since(self, earlier: Dict[str, float]) -> Dict[str, float]:
        """Seconds per phase between an earlier :meth:`snapshot` and now,
        with the totals ``host_s`` (phases that are the host's own work)
        and ``wait_s`` (phases spent waiting on the device)."""
        out: Dict[str, float] = {"host_s": 0.0, "wait_s": 0.0}
        for name, total in self.snapshot().items():
            dt = total - earlier.get(name, 0.0)
            if dt <= 0.0:
                continue
            kind = "wait" if name in self.wait else "host"
            out[f"{kind}.{name}_s"] = dt
            out[kind + "_s"] += dt
        return out


class Laps:
    """Consecutive phases of a one-shot sequence (a boot), each lap from
    the end of the last: ``laps.lap("rank_import_s")`` after the import."""

    __slots__ = ("seconds", "_t")

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self._t = time.monotonic()

    def lap(self, name: str) -> None:
        now = time.monotonic()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._t
        self._t = now


# ---------------------------------------------------------------------------
# Engine step anatomy metrics (ISSUE 26)
# ---------------------------------------------------------------------------

# One decode block of the generation engine, from the host's side. The
# first six are the host's own work between two dispatches; the last two
# are spent waiting on the device.
ENGINE_HOST_PHASES = ("hooks", "admit.setup", "admit.seat", "upload",
                      "dispatch", "emit")
ENGINE_WAIT_PHASES = ("admit.prefill", "fetch")

# a block is 1-300 ms and a phase of it can be microseconds
ENGINE_PHASE_BUCKETS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                        0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

_ENGINE_METRICS: Optional[Dict[str, _Metric]] = None


def engine_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the generation engine's step anatomy (ISSUE 26):
    ``kt_engine_phase_seconds{phase=...}``, one observation per phase per
    decode block on the stepping thread (``serve/engine.py``): ``hooks``
    (boundary hooks, reaping cancelled slots), ``admit.setup``,
    ``admit.prefill``, ``admit.seat``, ``upload``, ``dispatch``, ``fetch``,
    ``emit``. ``admit.prefill`` and ``fetch`` wait on the device; the rest
    is the host's own work between two dispatches."""
    global _ENGINE_METRICS
    if _ENGINE_METRICS is None:
        _ENGINE_METRICS = {
            "phase_seconds": histogram(
                "kt_engine_phase_seconds",
                "Generation-engine step anatomy per decode block (phase: "
                "hooks, admit.setup, admit.prefill, admit.seat, upload, "
                "dispatch, fetch, emit)",
                labels=("phase",), buckets=ENGINE_PHASE_BUCKETS),
        }
    return _ENGINE_METRICS


# ---------------------------------------------------------------------------
# Train-step anatomy metrics (ISSUE 12)
# ---------------------------------------------------------------------------

_TRAIN_METRICS: Optional[Dict[str, _Metric]] = None


def train_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the step-time anatomy family (ISSUE 12):

    - ``kt_train_step_seconds{phase=...}`` — where one training step's
      wall-clock goes. Phases: ``compute`` (the jitted step call, observed
      by ``make_train_step``'s wrapper — dispatch-to-return; on an async
      backend this is dispatch cost unless the caller syncs), ``grad_sync``
      (host-visible wait for the step's metrics/grads to materialize,
      observed by loops/benches that fetch them), ``snapshot_stall`` (the
      inline portion of ``Checkpointer.maybe_save`` — the time the step
      loop is actually blocked by a checkpoint snapshot).
    - ``kt_train_mfu`` — achieved model-FLOPs utilization, set by the
      bench/train loops that know the model's FLOPs-per-token.
    """
    global _TRAIN_METRICS
    if _TRAIN_METRICS is None:
        _TRAIN_METRICS = {
            "step_seconds": histogram(
                "kt_train_step_seconds",
                "Train-step wall-clock anatomy (phase: compute, grad_sync, "
                "snapshot_stall)",
                labels=("phase",)),
            "mfu": gauge(
                "kt_train_mfu",
                "Achieved model-FLOPs utilization of the training step"),
        }
    return _TRAIN_METRICS


# ---------------------------------------------------------------------------
# Speculative-decode adaptation metrics (ISSUE 12 satellite)
# ---------------------------------------------------------------------------

_SPEC_METRICS: Optional[Dict[str, _Metric]] = None


def spec_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the speculative-decode gauges the adaptive draft
    length controller (``serve/spec_engine.py``) exports: the acceptance
    EWMA it steers by and the draft length it chose."""
    global _SPEC_METRICS
    if _SPEC_METRICS is None:
        _SPEC_METRICS = {
            "accept_rate": gauge(
                "kt_spec_accept_rate",
                "EWMA of the speculative-decode acceptance rate "
                "(accepted/proposed per round)"),
            "draft_len": gauge(
                "kt_spec_draft_len",
                "Current speculative draft length k (adaptive within "
                "KT_SPEC_K_MIN..KT_SPEC_K_MAX)"),
        }
    return _SPEC_METRICS


# ---------------------------------------------------------------------------
# Serving front-door metrics (ISSUE 9)
# ---------------------------------------------------------------------------

# Replica-packing depth buckets: how full the chosen replica's decode batch
# was at dispatch (1 = the request opened a fresh batch; higher = it joined
# a partially-full one — the continuous-batching win, measured).
BATCH_DEPTH_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0,
                       32.0, 48.0, 64.0)

_SERVE_METRICS: Optional[Dict[str, _Metric]] = None


def serve_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the ``kt_serve_*`` family the inference front door
    (``serving/router.py``) emits into: admission/shed accounting, affinity
    routing outcomes, replica batch-packing depth, and the health-probe
    cache's savings. One place so the series names, labels, and HELP text
    stay consistent between the router, ``/metrics``, ``kt serve status``,
    and the bench/gate tooling that parses them."""
    global _SERVE_METRICS
    if _SERVE_METRICS is None:
        _SERVE_METRICS = {
            "admitted": counter(
                "kt_serve_admitted_total",
                "Requests admitted through the serving front door",
                labels=("tier",)),
            "shed": counter(
                "kt_serve_shed_total",
                "Requests shed at the front door before any prefill "
                "compute (reason: deadline_expired, doomed, queue_full)",
                labels=("reason", "tier")),
            "affinity": counter(
                "kt_serve_affinity_total",
                "Affinity routing outcomes (hit = routed to the replica "
                "where the session's prefix KV / adapter is resident, "
                "miss = consistent-hash cold placement, none = keyless)",
                labels=("result",)),
            "batch_depth": histogram(
                "kt_serve_batch_depth",
                "In-flight depth of the chosen replica's decode batch at "
                "dispatch (continuous batching across replicas)",
                labels=(), buckets=BATCH_DEPTH_BUCKETS),
            "queue_depth": gauge(
                "kt_serve_queue_depth",
                "Requests waiting in the front door's admission queue"),
            "probes": counter(
                "kt_serve_health_probes_total",
                "Health probes actually sent by the router"),
            "probes_avoided": counter(
                "kt_serve_health_probes_avoided_total",
                "Health probes skipped thanks to the TTL cache "
                "(the per-dispatch RTT the old supervisor paid)"),
        }
    return _SERVE_METRICS


# ---------------------------------------------------------------------------
# Fleet cold-start metrics (ISSUE 16)
# ---------------------------------------------------------------------------

# Replica-boot phase taxonomy: where the 0→N seconds go. ``import`` =
# python/module import, ``weight_fetch`` = pulling weights over the
# broadcast tree, ``weight_attach`` = shm attach + device_put,
# ``compile_or_cache`` = AOT cache probe + (on miss) trace/compile,
# ``engine_init`` = engine construction end to end, ``first_token`` =
# submit→first sampled token on the fresh replica.
COLD_START_PHASES = ("import", "weight_fetch", "weight_attach",
                     "compile_or_cache", "engine_init", "first_token")

_COLD_START_METRICS: Optional[Dict[str, _Metric]] = None


def cold_start_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the replica cold-start family (ISSUE 16): the
    per-phase boot anatomy (``kt_cold_start_seconds{phase=...}``, phases
    in :data:`COLD_START_PHASES`), the last full boot as a gauge the
    controller's aggressive-autoscale gate scrapes, the AOT compile
    cache's hit/miss/corrupt accounting, template fork outcomes, and the
    router's readiness-fence decisions. One place so the bench, the perf
    gate, the autoscaler scrape, and the docs stay on the same names."""
    global _COLD_START_METRICS
    if _COLD_START_METRICS is None:
        _COLD_START_METRICS = {
            "phase_seconds": histogram(
                "kt_cold_start_seconds",
                "Replica cold-start anatomy by phase (import, weight_fetch, "
                "weight_attach, compile_or_cache, engine_init, first_token)",
                labels=("phase",)),
            "total": gauge(
                "kt_cold_start_total_seconds",
                "Wall-clock of this replica's last full cold start "
                "(0 until one has been measured) — the signal the "
                "controller's fast-scale gate reads"),
            "boot_ts": gauge(
                "kt_cold_start_timestamp_seconds",
                "Unix time this replica last completed a measured cold "
                "start — the recency the fast-scale gate ranks "
                "measurements by (the newest boot is the evidence, not "
                "the fastest-ever one)"),
            "aot": counter(
                "kt_aot_cache_total",
                "AOT compile-cache lookups by result (hit, miss, "
                "incompatible, corrupt, publish, store_hit, "
                "store_publish, store_corrupt)",
                labels=("result",)),
            "forks": counter(
                "kt_template_forks_total",
                "Template-process fork requests by outcome (ok, error, "
                "template_dead)",
                labels=("outcome",)),
            "fence": counter(
                "kt_serve_readiness_fence_total",
                "Router readiness-fence decisions for still-warming "
                "replicas (admitted = fence passed and cleared, blocked = "
                "probe refused, expired = stale warming mark aged out, "
                "departed = warming ip left the membership)",
                labels=("result",)),
        }
    return _COLD_START_METRICS


_SOAK_METRICS: Optional[Dict[str, _Metric]] = None


def soak_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the ``kt_soak_*`` family the chaos conductor
    (``soak/conductor.py``) emits into: schedule events delivered,
    workload ops by outcome, invariant violations, and run verdicts. One
    place so ``kt soak run --json`` output and the CI smoke gate read the
    same series."""
    global _SOAK_METRICS
    if _SOAK_METRICS is None:
        _SOAK_METRICS = {
            "events": counter(
                "kt_soak_events_total",
                "Fault-schedule events delivered by the conductor",
                labels=("action",)),
            "ops": counter(
                "kt_soak_ops_total",
                "Soak workload operations by outcome (ok, typed-error, "
                "raw-error)",
                labels=("op", "outcome")),
            "violations": counter(
                "kt_soak_violations_total",
                "Invariant violations found when checking the history",
                labels=("invariant",)),
            "runs": counter(
                "kt_soak_runs_total",
                "Completed soak runs by verdict",
                labels=("outcome",)),
        }
    return _SOAK_METRICS


_PIPELINE_METRICS: Optional[Dict[str, _Metric]] = None


def pipeline_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the ``kt_pipeline_*`` family (ISSUE 17): elastic
    pipeline-parallel health. ``parallel/pipeline_elastic.py`` (the only
    stage-membership site) sets the gauges and counts re-groups; the
    pipeline supervisor observes re-group stall wall-clock. One place so
    ``/health``, ``/metrics``, and ``bench.py --pipeline`` read the same
    series."""
    global _PIPELINE_METRICS
    if _PIPELINE_METRICS is None:
        _PIPELINE_METRICS = {
            "regroups": counter(
                "kt_pipeline_regroups_total",
                "Pipeline stage re-groups by watchdog-classified cause "
                "(Crashed, Killed, OOMKilled, Preempted, Evicted, Slow)",
                labels=("cause",)),
            "stale": counter(
                "kt_pipeline_stale_epoch_total",
                "Zombie-stage confirms/publishes refused with "
                "StaleStageEpochError",),
            "epoch": gauge(
                "kt_pipeline_stage_epoch",
                "Current stage-membership epoch (bumped on every re-group)"),
            "stages": gauge(
                "kt_pipeline_stages",
                "Live pipeline stages in the current membership"),
            "bubble": gauge(
                "kt_pipeline_bubble_fraction",
                "Pipeline bubble fraction of the current schedule, "
                "slowdown-adjusted for nonuniform stage widths"),
            "regroup_seconds": histogram(
                "kt_pipeline_regroup_seconds",
                "Stage loss detected -> first post-re-group step committed",
                buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120)),
        }
    return _PIPELINE_METRICS


_FLYWHEEL_METRICS: Optional[Dict[str, _Metric]] = None


def flywheel_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the ``kt_flywheel_*`` family (ISSUE 19): the
    continuous-learning loop. ``flywheel/ledger.py`` (the only
    feedback-append site) counts appends/consumes/dedups, the harvester
    phase-times its cycle, and the promoter (the only
    publish/canary caller) counts gate verdicts and sets per-stage lag.
    One place so ``kt flywheel status``, ``/metrics``, and
    ``bench_serve.py --flywheel`` read the same series."""
    global _FLYWHEEL_METRICS
    if _FLYWHEEL_METRICS is None:
        _FLYWHEEL_METRICS = {
            "appended": counter(
                "kt_flywheel_appended_total",
                "Feedback records durably acked into the ledger "
                "(counted only after the segment's quorum write)",
                labels=("service",)),
            "consumed": counter(
                "kt_flywheel_consumed_total",
                "Fresh feedback records handed to the trainer by the "
                "cursor (post-dedup)",
                labels=("service",)),
            "deduped": counter(
                "kt_flywheel_deduped_total",
                "Duplicate records dropped by the cursor's hash dedup "
                "(at-least-once retries, re-polled segments)",
                labels=("service",)),
            "gate": counter(
                "kt_flywheel_gate_total",
                "Promotion-gate verdicts (promoted, rolled_back, "
                "gate_rejected)",
                labels=("verdict",)),
            "harvest": histogram(
                "kt_flywheel_harvest_seconds",
                "Harvester wall-clock by phase (harvest = one training "
                "step on harvested capacity, vacate = flush-and-yield, "
                "idle = waiting for SLO headroom)",
                labels=("phase",),
                buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 2.5, 5,
                         10, 30)),
            "lag": gauge(
                "kt_flywheel_lag_seconds",
                "Freshness of each flywheel stage (collect = newest "
                "acked append, train = newest committed cursor state, "
                "publish = newest rollout manifest, promote = newest "
                "fleet-phase promotion)",
                labels=("stage",)),
        }
    return _FLYWHEEL_METRICS


# ---------------------------------------------------------------------------
# Build identity (ISSUE 20 satellite)
# ---------------------------------------------------------------------------

_BUILD_INFO: Optional[Dict[str, str]] = None
_BUILD_INFO_METRICS: Optional[Dict[str, _Metric]] = None


def build_info() -> Dict[str, str]:
    """What code this process runs: package version, jax/jaxlib versions,
    backend, host. Computed once (importlib.metadata walks the filesystem);
    never imports jax — the backend comes from ``JAX_PLATFORMS``/
    ``jax.default_backend()`` only if jax is ALREADY loaded, so the
    dependency-free contract of this module holds."""
    global _BUILD_INFO
    if _BUILD_INFO is None:
        import socket
        import sys as _sys

        def _dist_version(name: str) -> str:
            try:
                from importlib import metadata
                return metadata.version(name)
            except Exception:  # noqa: BLE001 — absent/unmetadata'd dist
                return "unknown"

        try:
            from . import __version__ as pkg_version
        except Exception:  # noqa: BLE001
            pkg_version = "unknown"
        backend = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
        jax_mod = _sys.modules.get("jax")
        if not backend and jax_mod is not None:
            try:
                backend = jax_mod.default_backend()
            except Exception:  # noqa: BLE001 — no devices yet
                backend = ""
        _BUILD_INFO = {
            "version": str(pkg_version),
            "jax": _dist_version("jax"),
            "jaxlib": _dist_version("jaxlib"),
            "backend": backend or "unknown",
            "host": socket.gethostname(),
        }
    return _BUILD_INFO


def build_info_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create ``kt_build_info`` — the constant-1 identity gauge
    every ``/metrics`` endpoint exports (``MetricsRegistry.render`` ensures
    it on the global registry), so fleet rollups and bench JSON can key
    scraped numbers by the build that produced them."""
    global _BUILD_INFO_METRICS
    if _BUILD_INFO_METRICS is None:
        info = build_info()
        g = gauge(
            "kt_build_info",
            "Build identity of this process (constant 1; the labels are "
            "the payload: package/jax/jaxlib versions, backend, host)",
            labels=("version", "jax", "jaxlib", "backend", "host"))
        g.set(1, **info)
        _BUILD_INFO_METRICS = {"build_info": g}
    return _BUILD_INFO_METRICS


# ---------------------------------------------------------------------------
# Fleet rollup + flight-recorder metrics (ISSUE 20)
# ---------------------------------------------------------------------------

# Multi-window burn-rate taxonomy (SRE workbook): the fast window catches
# a cliff within minutes, the slow window keeps a smolder from paging
# forever. Window lengths are config (obs_slo_*); these are the labels.
SLO_WINDOWS = ("fast", "slow")

_FLEET_METRICS: Optional[Dict[str, _Metric]] = None


def fleet_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the ``kt_fleet_*`` family the controller-side fleet
    aggregator (``obs/fleet.py``, the only histogram-merge site) emits
    into: scrape outcomes, counter-reset epochs detected, per-stage SLO
    burn rates by window, and alert counts. The merged per-stage rollup
    histograms themselves are rendered by the aggregator (they are
    re-aggregated scrapes, not process-local observations — observing
    them into this registry would double-count on self-scrape)."""
    global _FLEET_METRICS
    if _FLEET_METRICS is None:
        _FLEET_METRICS = {
            "scrapes": counter(
                "kt_fleet_scrapes_total",
                "Fleet aggregator scrape attempts by outcome (ok, error)",
                labels=("outcome",)),
            "resets": counter(
                "kt_fleet_counter_resets_total",
                "Per-pod counter resets detected while merging (a scraped "
                "cumulative value went DOWN ⇒ the pod restarted ⇒ new "
                "epoch, never a negative delta)"),
            "pods": gauge(
                "kt_fleet_pods",
                "Pods in the fleet aggregator's last scrape round",
                labels=("state",)),
            "slo_burn": gauge(
                "kt_fleet_slo_burn",
                "Multi-window SLO burn rate per stage (1.0 = burning the "
                "error budget exactly at the sustainable rate; window: "
                "fast, slow)",
                labels=("stage", "window")),
            "alerts": counter(
                "kt_fleet_alerts_total",
                "SloBurnAlert records emitted by the fleet aggregator",
                labels=("stage", "window")),
        }
    return _FLEET_METRICS


_OBS_METRICS: Optional[Dict[str, _Metric]] = None


def obs_metrics() -> Dict[str, "_Metric"]:
    """Get-or-create the flight recorder's own accounting (``obs/``, the
    only telemetry-persistence site): snapshots appended by kind, spool
    rotations, and the spool's current on-disk size — the boundedness the
    soak asserts."""
    global _OBS_METRICS
    if _OBS_METRICS is None:
        _OBS_METRICS = {
            "snapshots": counter(
                "kt_obs_snapshots_total",
                "Flight-recorder records appended to the spool by kind "
                "(snapshot, final, event)",
                labels=("kind",)),
            "rotations": counter(
                "kt_obs_rotations_total",
                "Spool segment rotations (size- or age-capped)"),
            "spool_bytes": gauge(
                "kt_obs_spool_bytes",
                "Current on-disk size of this process's spool directory"),
        }
    return _OBS_METRICS


# ---------------------------------------------------------------------------
# Debug endpoint helper (shared by pod + store servers)
# ---------------------------------------------------------------------------


def debug_traces_payload(query: Optional[str],
                         limit: Optional[int] = None) -> Dict[str, Any]:
    """Body for ``GET /debug/traces[?q=<request_id|trace_id>][&limit=N]``."""
    if query:
        spans = RING.find(query)
    else:
        spans = RING.snapshot(limit=limit or 256)
    return {"spans": spans, "count": len(spans),
            "ring_size": len(RING), "enabled": enabled()}

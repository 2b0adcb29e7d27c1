"""The per-process flight recorder: always-on telemetry history with a
crash black box.

A background thread appends one record per interval to a local spool
directory (``<KT_OBS_SPOOL>/<name>-<pid>/segment-NNNNNN.jsonl``). Each
record carries a delta-encoded snapshot of the metrics registry, the
spans that completed since the previous record, and — crucially — the
spans still OPEN right now (:func:`telemetry.active_spans`): a SIGKILL
leaves the interesting span in flight, so every periodic record persists
the in-flight state, not just the final one. The loss window after a
hard kill is therefore one interval, never the whole history.

Durability and verifiability:

- every flush APPENDS one record line and pushes it to the kernel page
  cache — commit cost is O(one record), never O(segment), which is what
  keeps the perf gate's ``recorder_overhead`` ratio inside its <3%
  budget. PROCESS death (SIGKILL, OOM — the black box's threat model)
  loses nothing already appended; fsync happens at segment close and on
  event/final records, so MACHINE death costs at most the open
  segment's tail. A kill mid-append can tear only the very last line;
  the reader treats a torn final line of the final segment as the
  expected crash artifact (every earlier record was committed whole)
  and anything else as corruption;
- records are hash-chained per segment (blake2b over the previous hash +
  the record's canonical JSON), restarting at ``""`` on rotation so each
  retained segment verifies independently after older ones are deleted;
- ``seq`` increments across the whole spool, so the reader can prove no
  retained record is missing;
- spans are capped per record (``_SPAN_PER_RECORD_CAP`` newest win, the
  drop count stamped into the record) so a span storm inflates neither
  the flush nor the spool.

Boundedness: segments rotate at ``max_bytes/4`` and the spool deletes
oldest segments beyond ``max_bytes`` total or ``max_age_s`` old — the
soak's ``check_blackbox`` invariant and the perf gate's
``recorder_overhead`` stage hold this module to its budget.

Crash hooks: ``atexit`` always; SIGTERM/SIGINT only when the process had
no handler installed (the recorder never steals a server's shutdown
path); watchdog deaths arrive via :func:`note_death`.
"""

from __future__ import annotations

import atexit
import json
import os
import re
import signal
import threading
import time
from collections import OrderedDict
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from .. import telemetry
from ..data_store.durability import blake2b_bytes

RECORD_VERSION = 1
SEGMENT_GLOB = "segment-*.jsonl"

# finished-span dedup memory: larger than the trace ring's default
# capacity (2048), so a span evicted from this set has almost certainly
# left the ring too and cannot be re-recorded
_SPAN_DEDUP_CAP = 4096

# newest completed spans one record may carry: under a span storm the
# black box's value is the LAST interval, not a complete span archive —
# the overflow is counted into the record, never silently dropped. 128
# keeps the per-flush serialize+fsync cost well inside the <3% overhead
# budget the perf gate pins (recon keeps 512 across records anyway)
_SPAN_PER_RECORD_CAP = 128

# seconds between spool-cap sweeps (glob + stat of every segment): cap
# enforcement also runs on every rotation, so the sweep interval only
# bounds how stale the spool_bytes gauge can get
_CAPS_SWEEP_S = 2.0


def _canonical(record: Dict[str, Any]) -> bytes:
    return json.dumps(record, sort_keys=True,
                      separators=(",", ":")).encode("utf-8")


def chain_hash(prev: str, record: Dict[str, Any]) -> str:
    """Hash-chain link for one spool record: blake2b over the previous
    record's hash plus this record's canonical JSON (minus its own
    ``h`` field). The chain restarts at ``""`` at every segment boundary
    so each segment stays independently verifiable after rotation has
    deleted its predecessors."""
    body = {k: v for k, v in record.items() if k != "h"}
    return blake2b_bytes(prev.encode("ascii") + _canonical(body))


def snapshot_delta(prev: Dict[str, Dict],
                   cur: Dict[str, Dict]) -> Dict[str, Dict]:
    """Changed-series-only encoding of ``cur`` relative to ``prev`` (both
    in the :meth:`MetricsRegistry.snapshot` shape). A series appears when
    any of its label combinations changed value or the series is new;
    histogram entries are replaced wholesale — their bucket lists are
    cumulative, so intra-entry diffing buys nothing."""
    delta: Dict[str, Dict] = {}
    for series, entry in cur.items():
        base = prev.get(series)
        if (base is None or base.get("kind") != entry.get("kind")
                or base.get("labels") != entry.get("labels")
                or base.get("le") != entry.get("le")):
            delta[series] = entry
            continue
        changed = {lkey: lval for lkey, lval in entry["values"].items()
                   if base["values"].get(lkey) != lval}
        if changed:
            slim = {field: fval for field, fval in entry.items()
                    if field != "values"}
            slim["values"] = changed
            delta[series] = slim
    return delta


def apply_delta(base: Dict[str, Dict], payload: Dict[str, Dict],
                full: bool = False) -> Dict[str, Dict]:
    """Fold one record's ``metrics`` payload into a running snapshot —
    the reader-side inverse of :func:`snapshot_delta`. Deep-copies via
    the JSON round trip the payload already survived, so the running
    state never aliases record internals."""
    copied = json.loads(json.dumps(payload))
    if full:
        return copied
    for series, entry in copied.items():
        have = base.get(series)
        if have is None or have.get("kind") != entry.get("kind"):
            base[series] = entry
            continue
        for field, fval in entry.items():
            if field != "values":
                have[field] = fval
        have.setdefault("values", {}).update(entry.get("values", {}))
    return base


class FlightRecorder:
    """One process's always-on telemetry history (see module docstring).

    ``start()`` writes a synchronous full snapshot before the thread even
    exists, so a process killed instants after boot still leaves a
    readable black box. ``flush()`` is safe from any thread (RLock) —
    the periodic thread, signal handlers, atexit, and watchdog hooks all
    funnel through it.
    """

    def __init__(self, spool_root: str, name: str = "proc",
                 interval_s: float = 1.0,
                 max_bytes: int = 8 * 1024 * 1024,
                 max_age_s: float = 3600.0,
                 registry: Optional[telemetry.MetricsRegistry] = None):
        safe = re.sub(r"[^A-Za-z0-9_.]+", "-", str(name)).strip("-") or "proc"
        self.dir = Path(spool_root) / f"{safe}-{os.getpid()}"
        self.name = safe
        self.interval_s = max(0.01, float(interval_s))
        self.max_bytes = max(64 * 1024, int(max_bytes))
        self.max_age_s = float(max_age_s)
        self.segment_bytes = max(16 * 1024, self.max_bytes // 4)
        self.registry = registry if registry is not None else telemetry.REGISTRY
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._seq = 0
        self._seg_index = 0
        self._file: Optional[Any] = None
        self._seg_bytes = 0
        self._last_caps = 0.0
        self._prev_hash = ""
        self._prev_snapshot: Dict[str, Dict] = {}
        self._seen_spans: "OrderedDict[Tuple[str, str], None]" = OrderedDict()
        self._finalized = False
        self._prev_handlers: Dict[int, Any] = {}

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "FlightRecorder":
        self.dir.mkdir(parents=True, exist_ok=True)
        self.flush()
        self._thread = threading.Thread(
            target=self._run, name="kt-flight-recorder", daemon=True)
        self._thread.start()
        atexit.register(self._atexit)
        self._install_signal_hooks()
        return self

    def stop(self, final: bool = True) -> None:
        """Orderly shutdown (tests, clean exits): stop the thread, then
        append the terminal record. Crash paths never get here — they go
        through the atexit/signal hooks or lose at most one interval."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if final:
            self._finalize("stop")
        with self._lock:
            self._close_segment()
        try:
            atexit.unregister(self._atexit)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.flush()
            except Exception:  # noqa: BLE001 — forensics must never kill the host
                pass

    # -- record append -------------------------------------------------

    def flush(self, kind: str = "snapshot",
              note: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Append one record to the current segment and push it to the
        kernel. ``kind`` is ``snapshot`` (periodic), ``event``
        (out-of-band, e.g. a watchdog death), or ``final`` (terminal).

        Durability is tiered by what kills the process: the buffered
        write is flushed to the kernel page cache before this method
        returns, so PROCESS death (SIGKILL, OOM) loses nothing already
        appended — the black box's actual threat model. fsync (MACHINE
        death) happens at segment close and terminal records; a node
        crash costs at most the open segment's tail, and paying ~1ms of
        fsync per record bought nothing for the crash class the spool
        exists to survive."""
        with self._lock:
            now = time.time()
            cur = self.registry.snapshot()
            f = self._open_segment()
            full = self._seg_bytes == 0
            spans, dropped = self._drain_new_spans()
            record: Dict[str, Any] = {
                "v": RECORD_VERSION,
                "seq": self._seq,
                "ts": now,
                # this host's monotonic clock, the one spans' start_mono /
                # end_mono and the engine's stamps are on
                "mono": time.monotonic(),
                "kind": kind,
                "full": full,
                "metrics": (cur if full
                            else snapshot_delta(self._prev_snapshot, cur)),
                "spans": spans,
                "inflight": telemetry.active_spans(),
            }
            if dropped:
                record["dropped_spans"] = dropped
            if note:
                record["note"] = note
            # serialize the body ONCE: the chain hash covers these exact
            # canonical bytes, and the committed line is the same bytes
            # with the hash spliced in. The reader re-canonicalizes the
            # parsed record minus ``h`` — Python's JSON float/str round
            # trip is stable, so the bytes (and the hash) agree.
            body = _canonical(record)
            record["h"] = blake2b_bytes(
                self._prev_hash.encode("ascii") + body)
            line = body[:-1] + (',"h":"%s"}\n' % record["h"]).encode("ascii")
            f.write(line)
            f.flush()
            if kind != "snapshot":
                try:
                    os.fsync(f.fileno())
                except OSError:
                    pass
            self._seg_bytes += len(line)
            self._seq += 1
            self._prev_hash = record["h"]
            self._prev_snapshot = cur
            family = telemetry.obs_metrics()
            family["snapshots"].inc(kind=kind)
            rotated = self._seg_bytes >= self.segment_bytes
            if rotated:
                self._close_segment()
                self._seg_index += 1
                self._prev_hash = ""
                family["rotations"].inc()
            if rotated or now - self._last_caps >= _CAPS_SWEEP_S:
                self._last_caps = now
                family["spool_bytes"].set(self._enforce_caps(now))
            return record

    def _open_segment(self):
        if self._file is None:
            path = self.dir / f"segment-{self._seg_index:06d}.jsonl"
            self._file = open(path, "ab")
            self._seg_bytes = self._file.tell()
        return self._file

    def _close_segment(self) -> None:
        if self._file is not None:
            try:
                self._file.flush()
                os.fsync(self._file.fileno())
            except OSError:
                pass
            try:
                self._file.close()
            except OSError:
                pass
            self._file = None
            self._seg_bytes = 0

    def note_event(self, event: str, **attrs: Any) -> None:
        """Append an out-of-band event record and commit immediately —
        the watchdog's death hook rides this, so a rank's demise is on
        disk even if the supervisor dies next. Never raises."""
        try:
            self.flush(kind="event", note={"event": event, **attrs})
        except Exception:  # noqa: BLE001
            pass

    def _drain_new_spans(self) -> Tuple[List[Dict], int]:
        """(newest completed spans since the last record, drop count).

        Drains from a bounded ring slice (2x the record cap): under a
        span storm the ring is already evicting silently, so scanning
        its full depth buys nothing but GIL time — the drop count is a
        floor, not an exact census."""
        fresh = []
        for span_dict in telemetry.RING.snapshot(
                limit=2 * _SPAN_PER_RECORD_CAP):
            dedup = (span_dict.get("trace_id", ""),
                     span_dict.get("span_id", ""))
            if dedup in self._seen_spans:
                continue
            self._seen_spans[dedup] = None
            fresh.append(span_dict)
        while len(self._seen_spans) > _SPAN_DEDUP_CAP:
            self._seen_spans.popitem(last=False)
        dropped = 0
        if len(fresh) > _SPAN_PER_RECORD_CAP:
            dropped = len(fresh) - _SPAN_PER_RECORD_CAP
            fresh = fresh[-_SPAN_PER_RECORD_CAP:]
        return fresh, dropped

    def _enforce_caps(self, now: float) -> int:
        """Delete oldest non-current segments beyond the size cap and any
        past the age cap; returns the spool's resulting byte size."""
        current = self.dir / f"segment-{self._seg_index:06d}.jsonl"
        sizes: "OrderedDict[Path, int]" = OrderedDict()
        for seg in sorted(self.dir.glob(SEGMENT_GLOB)):
            try:
                sizes[seg] = seg.stat().st_size
            except OSError:
                continue
        total = sum(sizes.values())
        for seg, size in sizes.items():
            if seg == current:
                continue
            try:
                expired = (now - seg.stat().st_mtime) > self.max_age_s
            except OSError:
                expired = True
            if total > self.max_bytes or expired:
                try:
                    seg.unlink()
                    total -= size
                except OSError:
                    pass
        return total

    # -- crash hooks ---------------------------------------------------

    def _finalize(self, reason: str, **attrs: Any) -> None:
        with self._lock:
            if self._finalized:
                return
            self._finalized = True
        self._stop.set()
        try:
            self.flush(kind="final", note={"reason": reason, **attrs})
        except Exception:  # noqa: BLE001 — last gasp is best-effort
            pass

    def _atexit(self) -> None:
        self._stop.set()
        self._finalize("atexit")

    def _install_signal_hooks(self) -> None:
        # Only from the main thread (signal.signal raises elsewhere), and
        # only where the process runs the DEFAULT handler — a server that
        # installed its own graceful-shutdown path keeps it; its atexit
        # still writes our final record.
        if threading.current_thread() is not threading.main_thread():
            return
        for signum in (signal.SIGTERM, signal.SIGINT):
            try:
                if signal.getsignal(signum) == signal.SIG_DFL:
                    self._prev_handlers[signum] = signal.SIG_DFL
                    signal.signal(signum, self._on_signal)
            except (ValueError, OSError):
                continue

    def _on_signal(self, signum: int, frame: Any) -> None:
        self._finalize("signal", signum=int(signum))
        try:
            signal.signal(signum,
                          self._prev_handlers.get(signum, signal.SIG_DFL))
        except (ValueError, OSError):
            pass
        os.kill(os.getpid(), signum)


# -- process-wide singleton -------------------------------------------

_RECORDER: Optional[FlightRecorder] = None
_RECORDER_LOCK = threading.Lock()


def maybe_start_recorder(name: str = "proc") -> Optional[FlightRecorder]:
    """Arm the process-wide recorder from config (``KT_OBS_SPOOL``).
    Idempotent; returns None — and costs nothing — when no spool is
    configured. Entry points (pod server, store server, rank workers)
    call this unconditionally at boot; the env decides."""
    global _RECORDER
    from ..config import config
    cfg = config()
    spool = getattr(cfg, "obs_spool", "")
    if not spool:
        return None
    with _RECORDER_LOCK:
        if _RECORDER is None:
            _RECORDER = FlightRecorder(
                spool, name=name,
                interval_s=cfg.obs_interval_s,
                max_bytes=cfg.obs_spool_max_bytes,
                max_age_s=cfg.obs_spool_max_age_s).start()
    return _RECORDER


def recorder() -> Optional[FlightRecorder]:
    """The armed process-wide recorder, or None."""
    return _RECORDER


def note_death(rank: int, cause: Optional[str],
               exitcode: Optional[int]) -> None:
    """Watchdog death hook: stamp a worker's demise into this process's
    spool with an immediate commit. No-op when the recorder is off."""
    rec = _RECORDER
    if rec is not None:
        rec.note_event("watchdog.death", rank=rank, cause=cause,
                       exitcode=exitcode)


def _reset_for_tests() -> None:
    global _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is not None:
            _RECORDER.stop(final=False)
        _RECORDER = None

"""From a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone. A device plane
(``/device:TPU:n``) carries a line of program runs (``XLA Modules``) and a
line of operations (``XLA Ops``). Operations nest on that line — a ``while``
spans the operations of its body — so every sum here is of *self* time: an
event's duration less what its children cover. Busy time is the union of the
leaf operations' intervals; the window runs from the first to the last event
of the device planes.

The interval arithmetic takes plain lists, so the tests drive it with a
synthetic trace; only :func:`read_planes` touches the profiler's format.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def read_planes(path: str) -> list:
    """[{name, lines: {line name: [(name, start_ns, dur_ns)]}}] of the device
    planes of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)) for ev in line.events]
        out.append({"name": plane.name, "lines": lines})
    return out


def merge(intervals) -> list:
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def self_times(events) -> list:
    """[(name, start, end, self_ns, is_leaf)] for events that nest by time on
    one line. A child lies inside its parent; siblings do not overlap."""
    evs = sorted(((s, -(d), n) for n, s, d in events))
    out, stack = [], []          # stack of indices into out

    for s, neg_d, n in evs:
        e = s - neg_d
        # a parent holds its child whole; one that merely overlaps (an
        # asynchronous collective beside compute) is a sibling
        while stack and (out[stack[-1]][2] <= s or e > out[stack[-1]][2]):
            stack.pop()
        if stack:
            p = out[stack[-1]]
            p[3] -= e - s
            p[4] = False
        out.append([n, s, e, e - s, True])
        stack.append(len(out) - 1)
    return [tuple(x) for x in out]


_HLO = re.compile(r"^(%?[\w.\-]+) = (\(.*?\)|[a-z0-9]+\[[^\]]*\])\S* "
                  r"([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line; keep its own name,
    what it is and the shape it makes: ``%copy.106 copy bf16[16,2048]``."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    op = m.group(3)
    t = _TARGET.search(name)
    if t:
        op += ":" + t.group(1)
    shape = m.group(2) if not m.group(2).startswith("(") else "(tuple)"
    return f"{m.group(1)} {op} {shape}"[:120]


def reduce_planes(planes, queries=(), top: int = 10) -> dict:
    """The summary the readers work from, averaged over the device planes.

    ``queries``: [{"id", "line": "ops" | "modules", "pattern", "within"}];
    each gives ``{"count", "seconds"}``: the events whose name the pattern
    finds (for operations with ``within``: only those that start inside a
    program run whose name that second pattern finds) and their self time,
    as means over the devices.
    """
    if not planes:
        raise ValueError("the trace holds no device plane")
    n_dev = len(planes)
    lo = min(s for p in planes for evs in p["lines"].values()
             for _, s, _ in evs)
    hi = max(s + d for p in planes for evs in p["lines"].values()
             for _, s, d in evs)
    busy = 0.0
    by_name = defaultdict(float)
    gaps = defaultdict(float)
    q_out = {q["id"]: {"count": 0.0, "seconds": 0.0} for q in queries}
    compiled = {q["id"]: re.compile(q["pattern"]) for q in queries}
    for p in planes:
        ops = self_times(p["lines"].get(OPS_LINE, []))
        mods = self_times(p["lines"].get(MODULES_LINE, []))
        leaves = [(s, e) for _, s, e, _, leaf in ops if leaf]
        merged = merge(leaves)
        busy += length(merged)
        for n, _, _, self_ns, _ in ops:
            by_name[short_name(n)] += self_ns
        # idle gaps, labelled by the operations on either side
        ordered = sorted((s, e, n) for n, s, e, _, leaf in ops if leaf)
        end, last = None, None
        for s, e, n in ordered:
            if end is not None and s > end:
                gaps[f"{short_name(last)} -> {short_name(n)}"] += s - end
            if end is None or e > end:
                end, last = e, n
        for q in queries:
            rx = compiled[q["id"]]
            src = ops if q.get("line", "ops") == "ops" else mods
            hit = [(n, s, e, t, leaf) for n, s, e, t, leaf in src
                   if rx.search(n)]
            if q.get("within") and src is ops:
                runs = merge([(s, e) for n, s, e, _, _ in mods
                              if re.search(q["within"], n)])
                ends = [e for _, e in runs]
                hit = [h for h in hit
                       if (i := bisect.bisect_right(ends, h[1])) < len(runs)
                       and runs[i][0] <= h[1]]
            r = q_out[q["id"]]
            r["count"] += len(hit)
            r["seconds"] += sum(t for _, _, _, t, _ in hit) * 1e-9
    for r in q_out.values():
        for k in r:
            r[k] /= n_dev
    rank = lambda d: [[k, v * 1e-9 / n_dev] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"devices": n_dev, "window_s": (hi - lo) * 1e-9,
            "busy_s": busy * 1e-9 / n_dev, "queries": q_out,
            "breakdown": {"device_ops": rank(by_name),
                          "idle_gaps": rank(gaps)}}

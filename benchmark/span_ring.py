"""The caller's own span ring, as the readers of the program's spans see it.

The benchmark's parent process is the caller of ``svc.to()`` and of every
``svc.generate()``; the program writes what happened on the other side of
each onto the caller's ``client.deploy`` and ``client.call`` spans (the boot
phases of ``/ready``, the ``X-KT-Timing`` header), and keeps them in
``kubetorch_tpu.telemetry.RING`` of this process after the pod is gone.
Spans are stamped on the monotonic clock the window is read on.

A program that has no such span, stamp or attribute (an older one, or one
with tracing disabled) gives an empty list or None here, never an error.
"""


def spans() -> list:
    try:
        from kubetorch_tpu import telemetry
        return telemetry.RING.snapshot()
    except Exception:  # noqa: BLE001 — nothing to read is not a failure
        return []


def window_calls(ctx, span="client.call", method="generate", ring=None):
    """Attributes of the ``span``s of ``method`` that started inside the
    window, in order."""
    a = ctx["window"]["open"]["now"]
    b = ctx["window"]["close"]["now"]
    out = []
    for s in (spans() if ring is None else ring):
        t = s.get("start_mono")
        attrs = s.get("attrs") or {}
        if (s.get("name") == span and attrs.get("method") == method
                and t is not None and a <= t <= b):
            out.append(attrs)
    return out


def total(attrs: dict, names) -> float | None:
    """The sum of the named attributes a span carries; None if it carries
    none of them."""
    have = [attrs[n] for n in names
            if isinstance(attrs.get(n), (int, float))]
    return float(sum(have)) if have else None


def last_deploy(ring=None) -> dict | None:
    """Attributes of the run's (last) ``client.deploy`` span."""
    for s in reversed(spans() if ring is None else ring):
        if s.get("name") == "client.deploy":
            return s.get("attrs") or {}
    return None

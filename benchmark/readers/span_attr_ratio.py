"""The sum over the window's calls of one attribute of the caller's
``client.call`` span over the sum of another: ``engine.host_ms`` over
``engine.blocks`` is the host's own work per decode block, each block
counted once per request seated in it, in both sums."""

import span_ring


def read(ctx, num, den, scale=1.0, span="client.call", method="generate",
         ring=None):
    top = bottom = 0.0
    for a in span_ring.window_calls(ctx, span, method, ring):
        n, d = span_ring.total(a, [num]), span_ring.total(a, [den])
        if n is not None and d is not None:
            top, bottom = top + n, bottom + d
    if bottom <= 0:
        return None
    return scale * top / bottom

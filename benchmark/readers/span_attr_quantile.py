"""A quantile (nearest rank) over the window's calls of one attribute of
the caller's ``client.call`` span, or of the sum of several: what the pod
and the engine said of each call, from inside (``X-KT-Timing``)."""

import math

import span_ring


def read(ctx, attrs, q=0.5, scale=1.0, span="client.call",
         method="generate", ring=None):
    vals = sorted(v for v in (span_ring.total(a, attrs) for a in
                              span_ring.window_calls(ctx, span, method, ring))
                  if v is not None)
    if not vals:
        return None
    return scale * vals[min(len(vals) - 1,
                            max(0, math.ceil(q * len(vals)) - 1))]

"""A quantile (nearest rank) of one field of the records of the requests
sent in the window, scaled: ``ttft`` (rank-side first token less client
send; infinite for a failed request) or ``engine_ttft``
(``RequestHandle.time_to_first_token()``)."""

import math


def read(ctx, field, q=0.5, scale=1.0):
    vals = sorted(r[field] for r in ctx["records"]
                  if r.get(field) is not None)
    if not vals:
        return None
    return scale * vals[min(len(vals) - 1, max(0, math.ceil(q * len(vals)) - 1))]

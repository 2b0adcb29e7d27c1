"""Device time of one program's runs in the trace, per step: the sum of
the matching ``XLA Modules`` events over their count times the steps one
run makes (the engine's ``decode_block``)."""


def read(ctx, query, steps_per_run_key):
    q = (ctx["trace"] or {}).get("queries", {}).get(query)
    if not q or q["count"] <= 0:
        return None
    steps = ctx["config"]["engine"][steps_per_run_key]
    return 1e3 * q["seconds"] / (q["count"] * steps)

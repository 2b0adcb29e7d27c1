"""decode_attn_roofline: the least time the chip could take for the decode
attention kernel's work, over the time its events took in the trace.

Work: the K and V rows that the decoded tokens of the traced window attend
to (a token at position p reads p + 1 rows of every layer), from the rank's
record of each request (``bench_flops.tokens_in``); both sides are taken as rates
(per second of their own window), since the counters' window and the
trace's differ by the profiler's start and stop. The kernel is bound by
memory at these shapes (``bench_flops.roofline_seconds`` says which)."""


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["seconds"] <= 0:
        return None
    a, b = t["c0"]["now"], t["c1"]["now"]
    rows = 0.0
    for rec in t["log"]:
        if rec["t_first"] is None:
            continue
        _, lo, hi = ctx["flops"].tokens_in(rec, a, b)
        n = max(0, hi - lo + 1)
        rows += n * rec["prompt_len"] + (lo + hi) * n / 2.0
    if rows <= 0:
        return None
    cost = ctx["flops"].decode_attention_cost(ctx["config"], rows)
    least, _bound = ctx["flops"].roofline_seconds(cost, ctx["peak"])
    return 100.0 * (least / (b - a)) / (q["seconds"] / t["window_s"])

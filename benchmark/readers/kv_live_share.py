"""kv_pool_live_share: of the rows the engine reserves for keys and values
(slots x max_len), the share that held a live token, averaged over the
whole window (the traced run keeps the rank's record of every request). A
request holds its prompt's rows from its first token on and one more per
token; which tokens fall in the window is read from that record
(``bench_flops.tokens_in``)."""


def read(ctx):
    t, w = ctx["trace"], ctx["window"]
    if not t:
        return None
    a, b = w["open"]["now"], w["close"]["now"]
    e = ctx["config"]["engine"]
    row_seconds = 0.0
    for rec in t["log"]:
        if rec["t_first"] is None or rec["n"] < 2:
            continue
        _, lo, hi = ctx["flops"].tokens_in(rec, a, b)
        if hi < lo:
            continue
        dt = (rec["t_out"] - rec["t_first"]) / (rec["n"] - 1)
        row_seconds += (hi - lo + 1) * dt * (rec["prompt_len"]
                                             + (lo + hi) / 2.0)
    if row_seconds <= 0 or b <= a:
        return None
    return 100.0 * row_seconds / ((b - a) * e["slots"] * e["max_len"])

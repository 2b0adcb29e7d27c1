"""step_mfu.serve: model FLOPs of every token prefilled or decoded between
the two counter readings of the traced window, over that time times chips
times the bfloat16 peak. Which tokens: from the rank's own record of each
request (prompt length, tokens, first-token and finish times); a request's
tokens after the first are taken as evenly spaced between those two times.
Padding, garbage steps and unchosen experts do not count."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    a, b = t["c0"]["now"], t["c1"]["now"]
    f, cfg = ctx["flops"], ctx["config"]
    total = 0.0
    for rec in t["log"]:
        if rec["t_first"] is None:
            continue
        pre, lo, hi = f.tokens_in(rec, a, b)
        if pre:
            total += f.prefill_flops(cfg, rec["prompt_len"])
        total += f.decode_flops(cfg, rec["prompt_len"], lo, hi)
    if total <= 0:
        return None
    return 100.0 * total / ((b - a) * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])

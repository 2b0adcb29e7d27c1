"""slots_busy_share: of the decode steps the engine ran in the traced
window, the share of slot-steps that produced a token a request kept
(``EngineStats``: tokens generated, less the first tokens that prefills
produce, over decode steps times slots)."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    c0, c1 = t["c0"], t["c1"]
    steps = c1["decode_steps"] - c0["decode_steps"]
    if steps <= 0:
        return None
    toks = (c1["tokens_generated"] - c0["tokens_generated"]) \
        - (c1["admitted_total"] - c0["admitted_total"])
    return 100.0 * toks / (steps * c1["slots"])

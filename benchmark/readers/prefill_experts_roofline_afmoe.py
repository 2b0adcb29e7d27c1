"""prefill_experts_roofline.serve_afmoe: the least time the chip could take
for the routed experts' products of the prompts prefilled in the traced
window, over the time the sorted expert kernel's events inside ``_prefill``
took in the trace.

Work: for every request whose first token (its prefill's end) falls between
the window's two counter readings and whose bucket takes the expert layers'
sorted path, its real tokens' (token, choice) pairs in every expert layer —
prompt length × K × expert layers, what the program's
``EngineStats.moe_sorted_pairs`` counts at admission — times an expert's
three products, ``3 · D · F · 2`` operations a pair. The pairs' padding to
the kernel's row tile and the rows of a tile outside an expert's run are not
work: a row tile that is half another expert's reads lower. The kernel is
bound by the MXU, so the least time is the operations over the bfloat16
peak. Both sides are taken as rates (per second of their own window), since
the counters' window and the trace's differ by the profiler's start and
stop.

A program whose prompts never reach the kernel has no such events: nothing
to read."""

import bench_weights_afmoe as W
from bench_flops import tokens_in

# the program's ``models.experts.DENSE_ROWS_MAX``: a prefill of more rows
# sorts its pairs by expert
SORTED_ROWS_OVER = 512


def sorted_pairs(cfg: dict, prompt_len: int) -> int:
    """The (token, choice) pairs of a prompt's real tokens through the
    expert layers' sorted path, every expert layer."""
    m = W.dims(cfg)
    bucket = next(b for b in cfg["engine"]["prefill_buckets"]
                  if b >= prompt_len)
    if bucket <= SORTED_ROWS_OVER:
        return 0
    return prompt_len * m["K"] * (m["L"] - m["Ld"])


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["seconds"] <= 0:
        return None
    cfg = ctx["config"]
    a, b = t["c0"]["now"], t["c1"]["now"]
    pairs = sum(sorted_pairs(cfg, rec["prompt_len"]) for rec in t["log"]
                if rec["t_first"] is not None and tokens_in(rec, a, b)[0])
    if pairs <= 0 or b <= a:
        return None
    m = W.dims(cfg)
    least = pairs * 3 * m["d"] * m["fm"] * 2.0 / ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * (least / (b - a)) / (q["seconds"] / t["window_s"])

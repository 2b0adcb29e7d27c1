"""decode_hbm_roofline.serve_hybrid: the least time the chip's memory could
take for the decode steps' bytes, over the time the decode blocks took in
the trace.

Bytes (``bench_flops_hybrid.decode_bytes``, a least count), over the
counters' window: the weights once a step (Δ``decode_steps``), each live
slot's recurrent state and window read and written once a step (the engine's
``ssm_state_slot_steps`` tally), and the K/V rows the window's decoded tokens
attend to (the rank's record of each request, as ``decode_attn_roofline``
reads it); their least time at the peak's bytes per second. Time: the
``_decode_block`` events on the trace's modules line. Both sides are taken
as rates (per second of their own window), as ``moe_experts_roofline`` does,
since the counters' window and the trace's differ by the profiler's start
and stop, and the trace cuts the blocks at its two ends.

The query has an id of its own: ``trace_reduce.reduce_planes`` sums a query
once for every metric of the cell that declares it, so one shared with
``decode_step_ms`` would count each block twice (a ratio of count and
seconds, as that metric reads, does not see it).

A program without the tally (one that keeps no recurrent state) or without
decode blocks in the trace gives nothing to read."""

import bench_flops_hybrid as F


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["seconds"] <= 0 or not t.get("window_s"):
        return None
    c0, c1 = t["c0"], t["c1"]
    s0, s1 = c0.get("ssm_state_slot_steps"), c1.get("ssm_state_slot_steps")
    if s0 is None or s1 is None:
        return None
    a, b = c0["now"], c1["now"]
    steps = c1["decode_steps"] - c0["decode_steps"]
    if steps <= 0 or b <= a:
        return None
    rows = 0.0
    for rec in t["log"]:
        if rec["t_first"] is None:
            continue
        _, lo, hi = F.tokens_in(rec, a, b)
        n = max(0, hi - lo + 1)
        rows += n * rec["prompt_len"] + (lo + hi) * n / 2.0
    least = F.decode_bytes(ctx["config"], steps, s1 - s0, rows) \
        / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * (least / (b - a)) / (q["seconds"] / t["window_s"])

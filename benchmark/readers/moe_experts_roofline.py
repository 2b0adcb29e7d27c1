"""moe_experts_roofline: the least time the chip could take for the grouped
expert kernel's work, over the time its events took in the trace.

Work: the banks of the experts that got a token of a live slot in a decode
step of the traced window, which are exactly the banks the kernel's work
list streams: the routing tally's ``moe_expert_hits`` (a (layer, expert)
count of such steps) at the window's second reading less at its first, times
an expert's three matrices in bfloat16. Each of the engine's slots goes
through every hit expert (three products of 2·D·F operations a row), which
at 16 slots is a fifteenth of the stream's time: the kernel is bound by
memory (``bench_flops.roofline_seconds`` says which). Both sides are taken
as rates (per second of their own window), since the counters' window and
the trace's differ by the profiler's start and stop.

A program without the kernel has no such events, and one without the tally
no such counter: either gives nothing to read."""

import bench_weights_mla_moe as W


def moe_experts_cost(cfg: dict, hit_banks: float) -> dict:
    """The kernel over ``hit_banks`` (layer, expert, step) triples that got a
    token: an expert's gate, up and down matrices read once in bfloat16 (the
    rows, the gates and the output are left out as small), and every slot's
    three products against them."""
    m = W.dims(cfg)
    bank = 3 * m["d"] * m["fm"]
    return {"bytes": hit_banks * bank * 2,
            "flops": hit_banks * 2.0 * bank * cfg["engine"]["slots"]}


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["seconds"] <= 0:
        return None
    a, b = t["c0"].get("moe_expert_hits"), t["c1"].get("moe_expert_hits")
    if a is None or b is None:
        return None
    hits = sum(map(sum, b)) - sum(map(sum, a))
    dt = t["c1"]["now"] - t["c0"]["now"]
    if hits <= 0 or dt <= 0:
        return None
    least, _bound = ctx["flops"].roofline_seconds(
        moe_experts_cost(ctx["config"], hits), ctx["peak"])
    return 100.0 * (least / dt) / (q["seconds"] / t["window_s"])

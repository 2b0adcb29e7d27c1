"""prefill_attn_roofline.serve_afmoe: the least time the chip could take for
the attention of the prompts prefilled in the traced window, over the time
the flash prefill kernel's events inside ``_prefill`` took in the trace.

Work: for every request whose first token (its prefill's end) falls between
the window's two counter readings, ``bench_flops_afmoe.
prefill_attention_flops`` at the prompt's real length: the band's (query,
key) pairs on a window layer, the causal triangle's on a full one, ``4 · N
· Hd`` operations a pair. Padding to the bucket, the key blocks a band or
the diagonal cuts through, and the whole square a program without a window
would compute are not work: such a program reads lower. The kernel is bound
by the MXU, so the least time is the operations over the bfloat16 peak. Both
sides are taken as rates (per second of their own window), since the
counters' window and the trace's differ by the profiler's start and stop.

A program whose prompts never reach the kernel has no such events: nothing
to read."""

import bench_flops_afmoe as F


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["seconds"] <= 0:
        return None
    a, b = t["c0"]["now"], t["c1"]["now"]
    work = sum(F.prefill_attention_flops(ctx["config"], rec["prompt_len"])
               for rec in t["log"] if rec["t_first"] is not None
               and F.tokens_in(rec, a, b)[0])
    if work <= 0 or b <= a:
        return None
    least = work / ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * (least / (b - a)) / (q["seconds"] / t["window_s"])

"""mla_prefill_attn_roofline: the least time the chip could take for the
attention of the prompts prefilled in the traced window, over the time the
latent-attention prefill kernel's events took in the trace.

Work: for every request whose first token (its prefill's end) falls between
the window's two counter readings, the attended term of
``bench_flops_dsa_moe.context_flops`` at the prompt's real length, the count
``step_mfu.serve_dsa_moe`` uses: ``min(context, index_topk)`` keys a query,
``2 · N · (Hn + Hr + Hv)`` operations a key and layer. The indexer's scores
are not the kernel's work and are left out; so are the padding to the bucket
and the keys of the causal triangle the selection drops, which the kernel
computes: the share reads below 100% by design, and what a later kernel
exploits of the selection's sparsity shows as a higher one. The kernel is
bound by the MXU (its operands are ~1 GB a layer at 8,192 rows, a fifth of
the time its operations need at the peak), so the least time is the
operations over the bfloat16 peak. Both sides are taken as rates (per second
of their own window), since the counters' window and the trace's differ by
the profiler's start and stop.

A program without the kernel (the parent's XLA einsums, or a cell whose
prompts fit one score array) has no such events: nothing to read."""

import bench_flops_dsa_moe as F


def attended_flops(cfg: dict, prompt_len: int) -> float:
    """The attention a prompt of ``prompt_len`` needs, every layer: its
    ``context_flops`` less the scores of every causal key."""
    scored, _ = F.key_flops(cfg)
    p = prompt_len
    return F.context_flops(cfg, 1, p) - scored * p * (p + 1) / 2.0


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["seconds"] <= 0:
        return None
    a, b = t["c0"]["now"], t["c1"]["now"]
    work = sum(attended_flops(ctx["config"], rec["prompt_len"])
               for rec in t["log"] if rec["t_first"] is not None
               and F.tokens_in(rec, a, b)[0])
    if work <= 0 or b <= a:
        return None
    least = work / ctx["peak"]["bf16_flops_per_s"]
    return 100.0 * (least / (b - a)) / (q["seconds"] / t["window_s"])

"""step_mfu.serve_dsa_moe: ``serve_mfu``'s reading — model FLOPs of every
token prefilled or decoded between the two counter readings of the traced
window, over that time times chips times the bfloat16 peak — with the FLOPs
of a latent-attention decoder whose attention selects its keys and whose
expert layers hold a share of their experts (``bench_flops_dsa_moe``): the
decode steps' routed experts by the pairs the program's routing tally
counted between the two readings, not K a token. A program without the tally
gives nothing to read."""

import bench_flops_dsa_moe as F


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    a, b = t["c0"].get("moe_routed_pairs"), t["c1"].get("moe_routed_pairs")
    if a is None or b is None:
        return None
    cfg = ctx["config"]
    total = (sum(map(sum, b)) - sum(map(sum, a))) * F.routed_pair_flops(cfg)
    t0, t1 = t["c0"]["now"], t["c1"]["now"]
    for rec in t["log"]:
        if rec["t_first"] is None:
            continue
        pre, lo, hi = F.tokens_in(rec, t0, t1)
        if pre:
            total += F.prefill_flops(cfg, rec["prompt_len"])
        total += F.decode_flops(cfg, rec["prompt_len"], lo, hi)
    if total <= 0:
        return None
    return 100.0 * total / ((t1 - t0) * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])

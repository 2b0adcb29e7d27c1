"""ssm_state_roofline: the least time the chip's memory could take for the
fused Mamba-2 state kernel's work, over the time its events took in the
trace.

Work: every live slot's float32 state read once and written once in each
Mamba-2 layer of a decode step: the engine's ``ssm_state_slot_steps`` tally
(the live slots a decode step advanced, summed over steps) at the window's
second reading less at its first, times L_mamba × 2 × H·P·N × 4 bytes
(``bench_weights_hybrid.dims``). The kernel streams every slot, a free one
too, so the share counts low when slots stand empty and never above what
the stream reaches. Time: the self time of the ``kt_ssm_state_step``
events inside ``_decode_block`` runs (a prompt's state is made by the
chunked scan and never takes the kernel). Both sides are taken as rates
(per second of their own window), as ``moe_experts_roofline`` does, since
the counters' window and the trace's differ by the profiler's start and
stop.

A program without the kernel has no such events, and one without the tally
no such counter: either gives nothing to read."""

import bench_weights_hybrid as W


def state_bytes(cfg: dict, slot_steps: float) -> float:
    """The state of ``slot_steps`` slot-steps read and written once in
    every Mamba-2 layer, float32."""
    m = W.dims(cfg)
    return slot_steps * m["Lm"] * 2 * m["h"] * m["p"] * m["n"] * 4


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["seconds"] <= 0 or not t.get("window_s"):
        return None
    s0 = t["c0"].get("ssm_state_slot_steps")
    s1 = t["c1"].get("ssm_state_slot_steps")
    if s0 is None or s1 is None:
        return None
    dt = t["c1"]["now"] - t["c0"]["now"]
    if s1 <= s0 or dt <= 0:
        return None
    least = state_bytes(ctx["config"], s1 - s0) \
        / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * (least / dt) / (q["seconds"] / t["window_s"])

"""Where a closed-loop cell's time-to-first-token quantiles fall, without a chip.

    python scripts/closed_loop_sim.py --seeds 2147499301,2147499304 [--decode-block 8] [--no-admit-late]
    python scripts/closed_loop_sim.py --seeds 1,2 --mix longdoc-closed --buckets 8192 --prefill-ms 2400 --step-ms 40 --ramp-s 12

A discrete-event walk through ``GenerationEngine._step_once`` (boundary:
admit, ``_admit_late``, dispatch, first tokens; else run ahead and collect)
under one of the benchmark's own closed-loop decks (``--mix``, a file of
``benchmark/traffic``; ``chat-closed`` unless named), with the device's and
the host's times as constants. The defaults are ``kimivl-chat-closed``'s, from
the per-request records of four chip runs (PERF.md section 6, PR 33's fix
round); another cell passes its mix, its buckets (``--buckets``), its step
and prefill times (``--step-ms``, ``--prefill-ms``, one a bucket), its slots
and its ramp on the command line. It prints, per seed, the requests sent in the window, the
rate, the median and the 90th percentile of the time to first token, and the
shares of the window's requests under 100 ms, in the first mode (the median
+- 4 ms), in the bucket-512 mode (+9..+19 ms) and beyond it. A CPU timing is
never a device metric: this says where a quantile falls among the modes and
how it moves with a seed or a constant, not what a chip will read.
"""

from __future__ import annotations

import argparse
import math
import os
import random
import sys
from collections import deque

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import bench_traffic  # noqa: E402

BUCKETS = (256, 512, 1024)
# ms; host: the fetch's notice, the emit loop, admit.setup and the seat of one
# admission, one block's upload + dispatch, a first token's read-back; the
# reply's way back to the caller (base + per token of the reply), the
# request's way in
KIMIVL = dict(step=15.10, prefill={256: 16.6, 512: 31.6, 1024: 62.0},
              notice=1.5, emit=0.9, setup=5.5, seat=1.0, dispatch=2.6,
              first=0.5, splice=0.3, back=3.6, back_per_token=0.004,
              inbound=3.5)


def quantile(values, q):
    """Nearest rank, as ``benchmark/readers/record_quantile.py``."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))]


def simulate(seed, *, decode_block=8, slots=16, admit_late=True,
             run_ahead=True, host=1.0, jitter=0.15, path_jitter=0.3,
             ramp_ms=4000.0, window_ms=50000.0, mix="chat-closed",
             buckets=BUCKETS, **over):
    """One run of the cell: ([ttft ms of the requests sent in the window],
    tokens/s). ``host`` scales every host time (a slower machine);
    ``buckets``: the engine's prefill buckets, each with its time in
    ``prefill``; ``over``: any of ``KIMIVL``'s constants."""
    c = {**KIMIVL, **over}
    rng = random.Random(seed ^ 0x5BD1)
    sizes = bench_traffic._sizes_in_order(bench_traffic.load(mix), seed)
    k = decode_block

    def h(x, sd=jitter):
        return max(0.0, host * x + (rng.gauss(0, sd) if sd else 0.0))

    arrivals = []                     # (submitted, sent, request), by time

    def send(done_at, reply_tokens):
        p, n = next(sizes)
        sent = done_at + max(0.5, h(c["back"] + c["back_per_token"]
                                    * reply_tokens, path_jitter))
        arrivals.append((sent + max(0.5, h(c["inbound"], path_jitter)), sent,
                         {"p": p, "n": n, "made": 0, "sent": sent}))
        arrivals.sort(key=lambda a: a[0])

    for _ in range(slots):
        send(-c["back"], 0)
    slot = [None] * slots
    inflight = deque()
    t = device_free = 0.0
    records, token_times = [], []

    def pending():
        return [a for a in arrivals if a[0] <= t]

    def may_run_ahead():
        return (run_ahead and not pending() and all(slot)
                and any(r["n"] - r["made"] > k * len(inflight) for r in slot))

    def emit(i):
        r = slot[i]
        r["made"] += 1
        token_times.append(t)
        if r["made"] >= r["n"]:
            slot[i] = None
            send(t, r["n"])

    def admit():
        nonlocal t, device_free
        seated = []
        free = [i for i, r in enumerate(slot) if r is None]
        while free and pending():
            a = pending()[0]
            arrivals.remove(a)
            r, i = a[2], free.pop(0)
            t += h(c["setup"])
            took = c["prefill"][next(b for b in buckets if b >= r["p"])]
            start = max(t, device_free)
            device_free = start + took + c["splice"]
            t += h(c["seat"])
            slot[i] = r
            seated.append((r, i, start + took))
        return seated

    def firsts(seated):
        nonlocal t
        for r, i, ready in seated:
            t = max(t, ready) + h(c["first"])
            records.append((r["sent"], t - r["sent"]))
            if slot[i] is r:
                emit(i)

    def dispatch():
        nonlocal t, device_free
        t += h(c["dispatch"])
        device_free = max(t, device_free) + k * c["step"]
        inflight.append((device_free, list(slot)))

    def collect():
        nonlocal t
        ends, held = inflight.popleft()
        t = max(t, ends) + h(c["notice"])
        for _ in range(k):
            for i, r in enumerate(held):
                if r is not None and slot[i] is r:
                    emit(i)
        t += h(c["emit"])

    end = ramp_ms + window_ms
    while t < end + 3000:
        if not inflight:                              # a batch boundary
            seated = admit()
            while admit_late and seated and not all(slot):
                firsts(seated)
                seated = admit() if pending() else []
            if any(slot):
                dispatch()
            firsts(seated)
            if inflight and not may_run_ahead():
                collect()
            elif not inflight:
                t = max(t, arrivals[0][0])
        else:
            if may_run_ahead():
                dispatch()
            collect()
    ttfts = [x for sent, x in records if ramp_ms <= sent <= end]
    rate = sum(ramp_ms <= x <= end for x in token_times) / (window_ms / 1e3)
    return ttfts, rate


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--decode-block", type=int, default=8)
    ap.add_argument("--no-admit-late", action="store_true")
    ap.add_argument("--host", type=float, default=1.0)
    ap.add_argument("--mix", default="chat-closed",
                    help="a closed-loop mix of benchmark/traffic")
    ap.add_argument("--buckets", default=",".join(map(str, BUCKETS)))
    ap.add_argument("--prefill-ms", default=",".join(
        str(KIMIVL["prefill"][b]) for b in BUCKETS),
        help="a prefill's device time, one a bucket")
    ap.add_argument("--step-ms", type=float, default=KIMIVL["step"])
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--ramp-s", type=float, default=4.0)
    ap.add_argument("--window-s", type=float, default=50.0)
    args = ap.parse_args(argv)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    prefill = [float(x) for x in args.prefill_ms.split(",")]
    if len(prefill) != len(buckets):
        ap.error("--prefill-ms gives one time a bucket of --buckets")
    print("[SIMULATION on the CPU: not a chip result]")
    for seed in (int(s) for s in args.seeds.split(",")):
        tt, rate = simulate(seed, decode_block=args.decode_block,
                            admit_late=not args.no_admit_late, host=args.host,
                            mix=args.mix, buckets=buckets, slots=args.slots,
                            ramp_ms=1e3 * args.ramp_s,
                            window_ms=1e3 * args.window_s,
                            step=args.step_ms,
                            prefill=dict(zip(buckets, prefill)))
        m = quantile(tt, 0.5)

        def share(lo, hi):
            return sum(lo <= x < hi for x in tt) / len(tt)
        print(f"seed {seed}: {len(tt)} requests, {rate:.1f} tokens/s, "
              f"ttft p50 {m:.2f} p90 {quantile(tt, 0.9):.2f} ms; under 100 ms "
              f"{share(0, 100):.3f}, first mode {share(m - 4, m + 4):.3f}, "
              f"bucket-512 mode {share(m + 9, m + 19):.3f}, beyond "
              f"{share(m + 19, 1e9):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

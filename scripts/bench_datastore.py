#!/usr/bin/env python
"""Data-plane microbench: pytree put/get MB/s against a local store.

Measures the three regimes the parallel, content-addressed data plane is
built for (ISSUE 1 / ROADMAP "as fast as the hardware allows"):

- **sequential** — ``KT_STORE_CONCURRENCY=1`` cold put + get (the old
  one-leaf-at-a-time path, kept as the baseline);
- **parallel**   — cold put + get at the default fan-out (8);
- **delta**      — an identical repeated put: every leaf skipped via
  ``/kv/diff``, only the index moves;
- **scrub**      — one full integrity sweep over the stored data (ISSUE 4)
  plus a parallel get racing a concurrent sweep, so the steady-state
  overhead of the background scrubber on the fetch hot path is a tracked
  number, not a guess.
- **checkpoint** — (``--checkpoint`` / ``make bench-ckpt``, ISSUE 6) the
  commit-marker checkpoint loop (``train/checkpoint.py`` two-slot
  ping-pong + marker): per-step committed-checkpoint wall-clock and wire
  bytes vs. the fraction of leaves that changed since the slot's previous
  content — the BENCH-tracked number behind the "~free suspend/resume"
  claim (per-step cost must track bytes-changed, not checkpoint size).
- **trace**      — (``--trace-overhead`` / ``make bench-trace``, ISSUE 5)
  the same put/get hot path with telemetry spans disabled (``KT_TRACE=0``,
  the allocation-free fast path) vs enabled, on both client and store.
  The enforced budget: <3% enabled, ~0% disabled — every later perf PR
  measures against an instrumented data plane, so the instrument itself
  must stay free.

Run: ``make bench-store`` or
``python scripts/bench_datastore.py [--leaves 64] [--mb-per-leaf 4]``.
Prints a table plus a JSON blob (same convention as bench.py) so results
can be tracked over time.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# CPU-only (see Makefile PY_CPU)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _start_store(root: str, port: int,
                 extra_env: dict | None = None) -> subprocess.Popen:
    from kubetorch_tpu.utils.procs import wait_for_port

    env = dict(os.environ)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
         "--host", "127.0.0.1", "--port", str(port), "--root", root],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    assert wait_for_port("127.0.0.1", port, timeout=30), "store did not start"
    return proc


def _make_tree(leaves: int, mb_per_leaf: float, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    n = int(mb_per_leaf * (1 << 20) // 4)
    return {"layers": {f"w{i:03d}": rng.standard_normal(n).astype(np.float32)
                       for i in range(leaves)}}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _bench_root() -> str:
    """RAM-backed store root when available: a disk-backed root folds the
    kernel's writeback of the PREVIOUS regime's 256 MB into the next
    regime's wall-clock, which is exactly the cross-talk a microbench must
    not measure."""
    if os.access("/dev/shm", os.W_OK):
        return "/dev/shm"
    return tempfile.gettempdir()


def bench(leaves: int, mb_per_leaf: float, concurrency: int,
          reps: int = 3) -> dict:
    from kubetorch_tpu.data_store import commands as ds

    total_mb = leaves * mb_per_leaf
    results = {"leaves": leaves, "mb_per_leaf": mb_per_leaf,
               "total_mb": total_mb, "reps": reps,
               "host_cpus": len(os.sched_getaffinity(0))
               if hasattr(os, "sched_getaffinity") else os.cpu_count()}
    tree = _make_tree(leaves, mb_per_leaf)

    with tempfile.TemporaryDirectory(prefix="kt-bench-store-",
                                     dir=_bench_root()) as root:
        from kubetorch_tpu.utils.procs import free_port, kill_process_tree

        port = free_port()
        proc = _start_store(root, port)
        url = f"http://127.0.0.1:{port}"
        try:
            regimes = {"sequential": 1, "parallel": concurrency}
            best = {lbl: {"put_s": float("inf"), "get_s": float("inf")}
                    for lbl in regimes}
            # warmup: connection pools, page cache, jit-ish first-call costs
            os.environ["KT_STORE_CONCURRENCY"] = "1"
            ds.put("bench/warmup", {"w": tree["layers"]["w000"]},
                   store_url=url)
            ds.get("bench/warmup", store_url=url)
            # reps interleave the regimes so slow drift in background host
            # load (shared CI box) hits both alike; best-of sheds the tails
            for rep in range(reps):
                for label, width in regimes.items():
                    os.environ["KT_STORE_CONCURRENCY"] = str(width)
                    key = f"bench/{label}/{rep}"     # fresh key: cold puts
                    stats, t = _timed(
                        lambda: ds.put(key, tree, store_url=url))
                    best[label]["put_s"] = min(best[label]["put_s"], t)
                    best[label]["stats"] = stats
                    for _ in range(2):      # gets are idempotent: resample
                        _, t = _timed(lambda: ds.get(key, store_url=url))
                        best[label]["get_s"] = min(best[label]["get_s"], t)
            for label, width in regimes.items():
                put_s, get_s = best[label]["put_s"], best[label]["get_s"]
                stats = best[label]["stats"]
                results[label] = {
                    "concurrency": width,
                    "put_s": round(put_s, 3), "get_s": round(get_s, 3),
                    "put_mb_s": round(total_mb / put_s, 1),
                    "get_mb_s": round(total_mb / get_s, 1),
                    "uploaded_bytes": stats["bytes"],
                    "skipped": stats["skipped"],
                }
            os.environ["KT_STORE_CONCURRENCY"] = str(concurrency)

            # delta regime: identical re-put at full fan-out — /kv/diff
            # should skip every leaf and move only the index
            dstats, delta_s = _timed(
                lambda: ds.put("bench/parallel/0", tree, store_url=url))
            results["delta"] = {
                "put_s": round(delta_s, 3),
                "uploaded_bytes": dstats["bytes"],
                "skipped": dstats["skipped"],
                # None = nothing at all moved (reduction is unbounded)
                "wire_reduction_x": round(
                    results["parallel"]["uploaded_bytes"] / dstats["bytes"], 1)
                if dstats["bytes"] else None,
            }

            # scrub overhead: one timed full sweep (pacing included), then
            # a get racing a concurrent sweep vs the best uncontended get
            import threading

            import requests as _rq

            rep, scrub_s = _timed(lambda: _rq.post(
                f"{url}/scrub/run", timeout=600).json())
            status = _rq.get(f"{url}/scrub/status", timeout=30).json()
            t = threading.Thread(target=lambda: _rq.post(
                f"{url}/scrub/run", timeout=600))
            t.start()
            _, get_during = _timed(
                lambda: ds.get("bench/parallel/0", store_url=url))
            t.join()
            get_best = results["parallel"]["get_s"]
            results["scrub"] = {
                "sweep_s": round(scrub_s, 3),
                "scanned": rep.get("scanned"),
                "quarantined": rep.get("quarantined"),
                "scrub_mb_s": round(
                    status.get("scanned_bytes", 0) / max(scrub_s, 1e-9)
                    / (1 << 20) / max(status.get("sweeps", 1), 1), 1),
                "get_during_scrub_s": round(get_during, 3),
                "get_overhead_pct": round(
                    100.0 * (get_during - get_best) / get_best, 1)
                if get_best else None,
            }
        finally:
            kill_process_tree(proc.pid)
            os.environ.pop("KT_STORE_CONCURRENCY", None)

    seq, par = results["sequential"], results["parallel"]
    results["speedup_put_x"] = round(seq["put_s"] / par["put_s"], 2)
    results["speedup_get_x"] = round(seq["get_s"] / par["get_s"], 2)
    results["speedup_put_get_x"] = round(
        (seq["put_s"] + seq["get_s"]) / (par["put_s"] + par["get_s"]), 2)
    return results


def bench_trace(leaves: int, mb_per_leaf: float, reps: int = 5) -> dict:
    """Tracing-overhead regime (ISSUE 5): best-of-``reps`` put+get
    wall-clock with KT_TRACE=0 (disabled fast path — must be free) vs
    KT_TRACE=1 (spans on the client AND a traced store server), one store
    per mode so both sides of the wire toggle together."""
    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.utils.procs import free_port, kill_process_tree

    tree = _make_tree(leaves, mb_per_leaf, seed=7)
    total_mb = leaves * mb_per_leaf
    out = {"leaves": leaves, "mb_per_leaf": mb_per_leaf,
           "total_mb": total_mb, "reps": reps}
    saved = os.environ.get("KT_TRACE")
    try:
        for mode, flag in (("disabled", "0"), ("enabled", "1")):
            os.environ["KT_TRACE"] = flag
            with tempfile.TemporaryDirectory(
                    prefix=f"kt-bench-trace-{mode}-",
                    dir=_bench_root()) as root:
                port = free_port()
                proc = _start_store(root, port, extra_env={"KT_TRACE": flag})
                url = f"http://127.0.0.1:{port}"
                try:
                    # warm connections + page cache before timing
                    ds.put("bench/trace/warm", {"w": tree["layers"]["w000"]},
                           store_url=url)
                    ds.get("bench/trace/warm", store_url=url)
                    best_put = best_get = float("inf")
                    for rep in range(reps):
                        key = f"bench/trace/{mode}/{rep}"   # cold puts
                        _, t = _timed(
                            lambda: ds.put(key, tree, store_url=url))
                        best_put = min(best_put, t)
                        _, t = _timed(lambda: ds.get(key, store_url=url))
                        best_get = min(best_get, t)
                    out[mode] = {
                        "put_s": round(best_put, 4),
                        "get_s": round(best_get, 4),
                        "put_mb_s": round(total_mb / best_put, 1),
                        "get_mb_s": round(total_mb / best_get, 1),
                    }
                finally:
                    kill_process_tree(proc.pid)
    finally:
        if saved is None:
            os.environ.pop("KT_TRACE", None)
        else:
            os.environ["KT_TRACE"] = saved
    off = out["disabled"]["put_s"] + out["disabled"]["get_s"]
    on = out["enabled"]["put_s"] + out["enabled"]["get_s"]
    out["overhead_pct"] = round(100.0 * (on - off) / off, 2)
    return out


def bench_checkpoint(leaves: int, mb_per_leaf: float,
                     fractions=(0.0, 0.05, 0.25, 1.0)) -> dict:
    """Checkpoint regime (ISSUE 6): commit cost vs bytes-changed fraction.

    Primes BOTH ping-pong slots (the delta baseline for slot k is the
    content committed two saves earlier), then for each fraction mutates
    that share of leaves and measures one full committed save (leaves +
    index + marker). ``wire_ratio`` ≈ uploaded/changed bytes — the claim
    under test is that it stays ~1 instead of scaling with checkpoint
    size."""
    import numpy as np

    from kubetorch_tpu.train.checkpoint import Checkpointer, commit_info
    from kubetorch_tpu.utils.procs import free_port, kill_process_tree

    tree = _make_tree(leaves, mb_per_leaf, seed=3)
    total_mb = leaves * mb_per_leaf
    out = {"leaves": leaves, "mb_per_leaf": mb_per_leaf,
           "total_mb": total_mb, "regimes": []}
    names = sorted(tree["layers"])
    rng = np.random.default_rng(11)
    with tempfile.TemporaryDirectory(prefix="kt-bench-ckpt-",
                                     dir=_bench_root()) as root:
        port = free_port()
        proc = _start_store(root, port)
        url = f"http://127.0.0.1:{port}"
        try:
            ck = Checkpointer("bench/ckpt", store_url=url)
            step = 1
            _, cold_s = _timed(lambda: ck.save(tree, step))
            out["cold"] = {"save_s": round(cold_s, 3),
                           "mb_s": round(total_mb / cold_s, 1)}
            step += 1
            ck.save(tree, step)                 # prime the second slot
            for frac in fractions:
                n_mut = int(round(frac * leaves))
                for name in names[:n_mut]:      # deterministic subset
                    arr = tree["layers"][name]
                    arr[:] = rng.standard_normal(arr.shape).astype(arr.dtype)
                step += 1
                stats, save_s = _timed(
                    lambda s=step: ck.save(tree, s))
                changed_mb = n_mut * mb_per_leaf
                out["regimes"].append({
                    "changed_frac": frac,
                    "changed_mb": changed_mb,
                    "save_s": round(save_s, 3),
                    "uploaded_bytes": stats["bytes"],
                    "skipped": stats["skipped"],
                    "wire_ratio": round(
                        stats["bytes"] / (changed_mb * (1 << 20)), 2)
                    if changed_mb else None,
                })
            info = commit_info("bench/ckpt", store_url=url)
            _, restore_s = _timed(lambda: ck.restore())
            out["restore"] = {"restore_s": round(restore_s, 3),
                              "mb_s": round(total_mb / restore_s, 1),
                              "committed_step": info["step"]}
        finally:
            kill_process_tree(proc.pid)
    return out


def bench_fleet(leaves: int, mb_per_leaf: float, max_nodes: int = 3,
                reps: int = 3) -> dict:
    """Store-fleet regime (ISSUE 7 / ``make bench-fleet``): cold and delta
    sync MB/s vs ring size (1/2/.../N nodes, R=2 W=2).

    Each size gets its own subprocess fleet; the client routes per-leaf
    via ``KT_STORE_NODES``. The number under test: cold-put throughput
    should HOLD (or grow, once client and nodes stop sharing cores) as
    nodes are added even though every byte is written twice (W=2), because
    leaves hash across every node's disk/NIC instead of one origin's —
    and the delta path must stay ~free at any fleet size."""
    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.data_store import ring as ring_mod
    from tests.assets.store_fleet import SubprocessStoreFleet

    tree = _make_tree(leaves, mb_per_leaf, seed=5)
    total_mb = leaves * mb_per_leaf
    out = {"leaves": leaves, "mb_per_leaf": mb_per_leaf,
           "total_mb": total_mb, "reps": reps, "replication": 2,
           "write_quorum": 2, "fleets": [],
           "host_cpus": len(os.sched_getaffinity(0))
           if hasattr(os, "sched_getaffinity") else os.cpu_count()}
    saved = {k: os.environ.get(k) for k in
             ("KT_STORE_NODES", "KT_STORE_REPLICATION",
              "KT_STORE_WRITE_QUORUM", "KT_STORE_NODE_TTL_S")}
    try:
        for n in range(1, max_nodes + 1):
            with tempfile.TemporaryDirectory(prefix=f"kt-bench-fleet{n}-",
                                             dir=_bench_root()) as root:
                with SubprocessStoreFleet(root, n=n,
                                          replication=min(2, n),
                                          write_quorum=min(2, n)) as fleet:
                    for k, v in fleet.client_env().items():
                        os.environ[k] = v
                    ring_mod.reset_rings()
                    url = fleet.urls[0]
                    ds.put("bench/fleet/warm",
                           {"w": tree["layers"]["w000"]}, store_url=url)
                    best_put = best_get = float("inf")
                    for rep in range(reps):
                        key = f"bench/fleet/{n}/{rep}"      # cold puts
                        stats, t = _timed(
                            lambda k=key: ds.put(k, tree, store_url=url))
                        best_put = min(best_put, t)
                        _, t = _timed(
                            lambda k=key: ds.get(k, store_url=url))
                        best_get = min(best_get, t)
                    dstats, delta_s = _timed(lambda: ds.put(
                        f"bench/fleet/{n}/0", tree, store_url=url))
                    out["fleets"].append({
                        "nodes": n,
                        "put_s": round(best_put, 3),
                        "get_s": round(best_get, 3),
                        "put_mb_s": round(total_mb / best_put, 1),
                        "get_mb_s": round(total_mb / best_get, 1),
                        "delta_put_s": round(delta_s, 3),
                        "delta_uploaded_bytes": dstats["bytes"],
                        "delta_skipped": dstats["skipped"],
                    })
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        from kubetorch_tpu.data_store import ring as ring_mod2
        ring_mod2.reset_rings()
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--leaves", type=int, default=64)
    p.add_argument("--mb-per-leaf", type=float, default=4.0)
    p.add_argument("--concurrency", type=int, default=None,
                   help="parallel-regime width (default: the store "
                        "client's own default for this host)")
    p.add_argument("--trace-overhead", action="store_true",
                   help="run ONLY the tracing-overhead regime "
                        "(`make bench-trace`): put/get hot path with "
                        "telemetry disabled vs enabled")
    p.add_argument("--checkpoint", action="store_true",
                   help="run ONLY the checkpoint regime (`make bench-ckpt`):"
                        " committed-save cost vs bytes-changed fraction")
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="run ONLY the store-fleet regime (`make "
                        "bench-fleet`): cold + delta sync MB/s vs ring "
                        "size 1..N (R=2, W=2)")
    p.add_argument("--reps", type=int, default=5,
                   help="trace-overhead regime repetitions (best-of)")
    args = p.parse_args()

    if args.fleet:
        r = bench_fleet(args.leaves, args.mb_per_leaf,
                        max_nodes=args.fleet)
        print(f"\nstore-fleet regime: {r['leaves']} leaves x "
              f"{r['mb_per_leaf']} MB = {r['total_mb']:.0f} MB, "
              f"R={r['replication']} W={r['write_quorum']}, "
              f"best of {r['reps']}")
        print(f"{'nodes':>6} {'put MB/s':>10} {'get MB/s':>10} "
              f"{'delta s':>8} {'delta bytes':>12} {'skipped':>8}")
        for row in r["fleets"]:
            print(f"{row['nodes']:>6} {row['put_mb_s']:>10} "
                  f"{row['get_mb_s']:>10} {row['delta_put_s']:>8} "
                  f"{row['delta_uploaded_bytes']:>12} "
                  f"{row['delta_skipped']:>8}")
        if r["host_cpus"] <= max(f["nodes"] for f in r["fleets"]):
            print("NOTE: client + all store nodes share "
                  f"{r['host_cpus']} CPU(s) here, so multi-node wall-clock "
                  "cannot beat single-node locally; the regime still "
                  "tracks the W=2 replication tax and the fleet-size-"
                  "independent delta path.")
        print("\n" + json.dumps(r))
        return

    if args.checkpoint:
        r = bench_checkpoint(args.leaves, args.mb_per_leaf)
        print(f"\ncheckpoint regime: {r['leaves']} leaves x "
              f"{r['mb_per_leaf']} MB = {r['total_mb']:.0f} MB "
              f"(commit-marker protocol, two-slot ping-pong)")
        print(f"cold committed save: {r['cold']['save_s']}s "
              f"({r['cold']['mb_s']} MB/s)")
        print(f"{'changed':>8} {'save s':>8} {'uploaded':>12} "
              f"{'skipped':>8} {'wire ratio':>11}")
        for row in r["regimes"]:
            ratio = row["wire_ratio"] if row["wire_ratio"] is not None \
                else "-"
            print(f"{row['changed_frac']:>7.0%} {row['save_s']:>8} "
                  f"{row['uploaded_bytes']:>12} {row['skipped']:>8} "
                  f"{ratio:>11}")
        print(f"restore (committed step {r['restore']['committed_step']}): "
              f"{r['restore']['restore_s']}s ({r['restore']['mb_s']} MB/s)")
        print("\nper-step commit cost tracks bytes-changed (wire ratio ~1),"
              " not checkpoint size — the delta sync behind '~free"
              " suspend/resume'; unchanged leaves move zero bytes.")
        print("\n" + json.dumps(r))
        return
    if args.trace_overhead:
        r = bench_trace(args.leaves, args.mb_per_leaf, reps=args.reps)
        print(f"\ntracing overhead: {r['leaves']} leaves x "
              f"{r['mb_per_leaf']} MB = {r['total_mb']:.0f} MB, "
              f"best of {r['reps']}")
        print(f"{'mode':<10} {'put s':>8} {'get s':>8} "
              f"{'put MB/s':>10} {'get MB/s':>10}")
        for mode in ("disabled", "enabled"):
            row = r[mode]
            print(f"{mode:<10} {row['put_s']:>8} {row['get_s']:>8} "
                  f"{row['put_mb_s']:>10} {row['get_mb_s']:>10}")
        budget = "within" if r["overhead_pct"] < 3.0 else "OVER"
        print(f"\ntracing-enabled overhead on put+get: "
              f"{r['overhead_pct']}% ({budget} the <3% budget; "
              f"disabled path short-circuits to a shared no-op span)")
        print("\n" + json.dumps(r))
        return
    if args.concurrency is None:
        from kubetorch_tpu.data_store import netpool
        args.concurrency = netpool.store_concurrency()

    r = bench(args.leaves, args.mb_per_leaf, args.concurrency)
    print(f"\npytree: {r['leaves']} leaves x {r['mb_per_leaf']} MB "
          f"= {r['total_mb']:.0f} MB")
    print(f"{'regime':<16} {'put MB/s':>10} {'get MB/s':>10} "
          f"{'uploaded':>12} {'skipped':>8}")
    for label in ("sequential", "parallel"):
        row = r[label]
        name = f"{label} (w={row['concurrency']})"
        print(f"{name:<16} {row['put_mb_s']:>10} {row['get_mb_s']:>10} "
              f"{row['uploaded_bytes']:>12} {row['skipped']:>8}")
    d = r["delta"]
    print(f"{'delta':<16} {'-':>10} {'-':>10} "
          f"{d['uploaded_bytes']:>12} {d['skipped']:>8}")
    reduction = (f"{d['wire_reduction_x']}x" if d["wire_reduction_x"]
                 else "unbounded (0 bytes moved)")
    print(f"\nput+get speedup: {r['speedup_put_get_x']}x "
          f"(put {r['speedup_put_x']}x, get {r['speedup_get_x']}x); "
          f"delta wire reduction: {reduction}")
    s = r["scrub"]
    print(f"scrub: full sweep {s['sweep_s']}s ({s['scrub_mb_s']} MB/s paced, "
          f"{s['scanned']} objects, {s['quarantined']} quarantined); "
          f"get during scrub {s['get_during_scrub_s']}s "
          f"({s['get_overhead_pct']}% over uncontended)")
    if r["host_cpus"] <= 1:
        print("NOTE: this host exposes 1 CPU; the client fan-out and the "
              "store server share one core, so loopback wall-clock cannot "
              "exceed the sequential path here. The concurrency win needs "
              "client and server on separate cores (any real deployment); "
              "the delta regime is core-count-independent.")
    print("\n" + json.dumps(r))


if __name__ == "__main__":
    main()

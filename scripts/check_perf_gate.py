#!/usr/bin/env python
"""Per-stage perf regression gate (ISSUE 9 satellite, expanded in ISSUE 10
to the full hot-path stage set / ROADMAP item 5).

``make bench-trace`` proved the telemetry plane itself is ~free; this gate
spends that instrumentation: it drives the REAL hot paths in-process and
compares the measured ``kt_stage_seconds`` p50 per stage against a
committed baseline (``scripts/perf_baseline.json``). CI fails when any
gated stage regresses more than the tolerance — so this PR and every later
one can't silently re-fatten the dispatch path.

Gated stages and how each is driven:

- ``deserialize`` / ``queue_wait`` / ``execute`` — JSON echo calls through
  the in-process pod server (HTTP POST → deserialize → process-pool
  submit → rank-worker echo → response). ``execute`` on an echo payload IS
  dispatch overhead: the user fn is a no-op return.
- ``shm_copy`` — msgpack echo calls carrying arrays above
  ``KT_SHM_THRESHOLD`` through the same server, so the zero-copy envelope
  encode/decode (``serving/shm_ring.py``) is exercised and measured where
  /metrics scrapes it (the parent process: request-encode + response-
  decode).
- ``store_fetch`` — pytree get against a real store-server subprocess
  (the ``_RoutedFetcher`` client path that observes the stage).
- ``rollout_apply`` — host-staged weight-delta apply + per-leaf blake2b
  fingerprint verify in the rank worker, with the delta array arriving
  over the real shm envelope path (ISSUE 11, CPU-proxy sized): the
  end-to-end cost of landing one rollout leaf, gated so roadmap items
  can't silently eat the live-swap time.
- ``train_step`` — real jitted tiny-llama train steps (accum_steps=2,
  CPU proxy) through ``make_train_step``'s wrapper; reads the
  ``kt_train_step_seconds{phase="compute"}`` histogram (ISSUE 12).
- ``snapshot_stall`` — the inline portion of ``Checkpointer.maybe_save``
  (``copy_to_host_async`` fan-out + IO-thread handoff) against a real
  store subprocess; gated so the async snapshot path can never quietly
  regress back to blocking on a full host copy.
- ``cold_start`` — AOT-cache-warmed engine inits against one persistent
  cache dir (first boot seeds, the rest must HIT): reads
  ``kt_cold_start_seconds{phase="compile_or_cache"}`` so a broken cache
  key or serialize path (silent fallback to full XLA compiles) fails the
  gate instead of slowing every fleet scale-out (ISSUE 16).
- ``recorder_overhead`` — the one RATIO stage (ISSUE 20): the flight
  recorder's steady-state per-flush cost (ring refilled with real stage
  spans between flushes, flush timed inline) over the flush interval —
  the fraction of a busy single core the recorder steals at 10x the
  production cadence. Judged against an ABSOLUTE budget (<3%,
  ``--recorder-budget``), not the baseline rule — "always-on" is only
  true if the recorder's price stays a rounding error no matter what
  the baseline drifted to.

Gate rule (per stage)::

    p50 <= baseline_p50 * (1 + tolerance) + abs_floor_s

``tolerance`` defaults to 0.10 (the ISSUE's >10% rule;
``KT_PERF_GATE_TOLERANCE`` / ``--tolerance`` override). ``abs_floor_s``
(default 2ms, ``--abs-floor-ms``) absorbs shared-CI scheduling noise:
10% of a sub-millisecond p50 is jitter, not a regression — the gate
exists to catch real ones.

``--retries N`` (default 1; ``make test`` passes 3) re-measures FAILING
stages up to N total attempts and judges the median of the per-attempt
p50s against the SAME limit: the tolerance and floor never loosen, the
gate just refuses to flunk a stage on a single scheduler burst a second
and third measurement both contradict. Each attempt's p50 is isolated by
diffing the cumulative histogram buckets, so a bad first attempt cannot
pollute the retries.

Run: ``make perf-gate`` (also part of ``make test``); ``--update``
re-baselines after a DELIBERATE hot-path change (commit the JSON with the
PR that explains it).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# CPU-only (see Makefile PY_CPU)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the shm_copy stage needs the envelope path armed for the driver's pod
# server (64 KiB threshold, well under the driver's array payloads)
os.environ.setdefault("KT_SHM_THRESHOLD", "65536")

BASELINE_PATH = os.path.join(REPO, "scripts", "perf_baseline.json")
GATED_STAGES = ("deserialize", "queue_wait", "execute", "store_fetch",
                "shm_copy", "rollout_apply", "train_step", "snapshot_stall",
                "cold_start")

# most stages read the kt_stage_seconds histogram; the two train-loop
# stages (ISSUE 12) read the step-anatomy histogram the train wrapper and
# Checkpointer.maybe_save observe into, and cold_start (ISSUE 16) reads
# the boot-anatomy histogram the AOT-cached engine init observes
STAGE_SOURCES = {
    "train_step": ("kt_train_step_seconds", 'phase="compute"'),
    "snapshot_stall": ("kt_train_step_seconds", 'phase="snapshot_stall"'),
    "cold_start": ("kt_cold_start_seconds", 'phase="compile_or_cache"'),
}

PAYLOAD_MODULE = textwrap.dedent("""
    def echo(x):
        return x
""")

ROLLOUT_MODULE = textwrap.dedent("""
    import hashlib

    import numpy as np

    _PARAMS = {}

    def rollout_apply(arr, path, want):
        # the worker half of a live weight swap: verify the staged leaf's
        # content address, then land it in the host param tree
        a = np.ascontiguousarray(arr)
        got = hashlib.blake2b(a.tobytes(), digest_size=20).hexdigest()
        assert got == want, f"leaf hash mismatch: {got} != {want}"
        _PARAMS[path] = a
        return {"applied": path, "bytes": int(a.nbytes)}
""")


async def _drive(calls: int, payload_kb: int, shm_calls: int,
                 shm_kb: int) -> None:
    """Real calls through the in-process pod server: JSON echoes pay the
    deserialize/queue_wait/execute stages; msgpack array echoes above the
    shm threshold pay shm_copy on top — exactly the counters the
    autoscaler and this gate read."""
    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from kubetorch_tpu import serialization as ser
    from kubetorch_tpu.serving.http_server import ServerState, create_app

    state = ServerState()
    app = create_app(state)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        # wait out the load+warmup window (worker spawn + module import)
        for _ in range(600):
            r = await client.get("/ready")
            if r.status == 200:
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("pod server never became ready")
        body = json.dumps(
            {"args": [[1.0] * (payload_kb * 128)], "kwargs": {}})
        for _ in range(calls):
            r = await client.post("/echo", data=body,
                                  headers={"Content-Type":
                                           "application/json"})
            assert r.status == 200, await r.text()
        arr = np.arange(shm_kb * 256, dtype=np.float32)   # shm_kb KiB
        mp_body = ser.serialize({"args": [arr], "kwargs": {}}, ser.MSGPACK)
        for _ in range(shm_calls):
            r = await client.post("/echo", data=mp_body,
                                  headers={"X-Serialization": ser.MSGPACK})
            assert r.status == 200, await r.text()
    finally:
        await client.close()


async def _drive_rollout(calls: int, leaf_kb: int) -> None:
    """Real rollout-leaf applies through the in-process pod server: each
    call carries one delta leaf above the shm threshold (so it rides the
    zero-copy envelope path), the worker verifies its blake2b and lands it
    in a host param tree, and the DRIVER wraps the round trip in the
    ``rollout_apply`` stage — the number ``serve/rollout.py`` also
    observes around its stage+swap+verify in production."""
    import hashlib

    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from kubetorch_tpu import serialization as ser
    from kubetorch_tpu import telemetry
    from kubetorch_tpu.serving.http_server import ServerState, create_app

    state = ServerState()
    app = create_app(state)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        for _ in range(600):
            r = await client.get("/ready")
            if r.status == 200:
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("pod server never became ready")
        arr = np.arange(leaf_kb * 256, dtype=np.float32)   # leaf_kb KiB
        want = hashlib.blake2b(np.ascontiguousarray(arr).tobytes(),
                               digest_size=20).hexdigest()
        bodies = [ser.serialize({"args": [arr, f"leaf{i}", want],
                                 "kwargs": {}}, ser.MSGPACK)
                  for i in range(calls)]
        for body in bodies:
            with telemetry.stage("rollout_apply"):
                r = await client.post("/rollout_apply", data=body,
                                      headers={"X-Serialization":
                                               ser.MSGPACK})
                assert r.status == 200, await r.text()
    finally:
        await client.close()


def _drive_store(gets: int, snapshot_saves: int) -> None:
    """Pytree put + repeated gets against a real store-server subprocess:
    every leaf fetch observes the ``store_fetch`` stage in THIS process
    (the client side, where the gate reads the registry). While the store
    is up, ``snapshot_saves`` real ``Checkpointer.maybe_save`` calls
    observe the ``snapshot_stall`` phase — the inline cost the async
    snapshot path (ISSUE 12) promises stays O(dispatch)."""
    import numpy as np

    from kubetorch_tpu.data_store import commands as ds
    from kubetorch_tpu.train.checkpoint import Checkpointer
    from kubetorch_tpu.utils.procs import (free_port, kill_process_tree,
                                           wait_for_port)

    port = free_port()
    with tempfile.TemporaryDirectory() as root:
        env = dict(os.environ)
        env["KT_STORE_FSYNC"] = "0"
        env["KT_SCRUB_INTERVAL_S"] = "0"
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
             "--host", "127.0.0.1", "--port", str(port), "--root", root],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            assert wait_for_port("127.0.0.1", port, timeout=30), \
                "store did not start"
            url = f"http://127.0.0.1:{port}"
            rng = np.random.default_rng(0)
            tree = {"w": {f"l{i}": rng.standard_normal(1 << 14).astype(
                np.float32) for i in range(4)}}
            ds.put("perf-gate/w", tree, store_url=url)
            for _ in range(gets):
                ds.get("perf-gate/w", store_url=url)
            import jax.numpy as jnp
            ck = Checkpointer("perf-gate/ckpt", store_url=url, every=1)
            state = {"w": jnp.asarray(
                rng.standard_normal(1 << 16).astype(np.float32))}
            for i in range(snapshot_saves):
                fut = ck.maybe_save(state, i + 1)
                assert fut is not None
                ck.flush(timeout=60)
        finally:
            kill_process_tree(proc.pid)


def _drive_train_step(steps: int) -> None:
    """Real jitted tiny-llama train steps (CPU proxy) through
    ``make_train_step``'s wrapper — each call observes
    ``kt_train_step_seconds{phase="compute"}``, the wall-time the
    ``train_step`` stage gates so roadmap items can't silently eat the
    step (ISSUE 12)."""
    import jax
    import jax.numpy as jnp
    import optax

    from kubetorch_tpu.models.llama import (LlamaConfig, llama_init,
                                            llama_loss)
    from kubetorch_tpu.train import init_train_state, make_train_step

    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    opt = optax.adam(1e-3)
    step = make_train_step(lambda p, t, y: llama_loss(p, t, y, cfg),
                           optimizer=opt, accum_steps=2)
    state = init_train_state(llama_init(jax.random.PRNGKey(0), cfg), opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    state, m = step(state, batch)        # compile (observed, but p50-safe
    float(m["loss"])                     # across `steps` warm calls)
    for _ in range(steps):
        state, m = step(state, batch)
    float(m["loss"])


def _drive_cold_start(boots: int) -> None:
    """Real AOT-cached engine inits against one persistent cache dir: the
    first boot seeds (compiles + publishes — observed too, but p50-safe
    across ``boots`` warm inits), every later boot must be a cache HIT.
    Each init observes ``kt_cold_start_seconds{phase="compile_or_cache"}``
    — the stage this gate pins so a broken cache key or a lost serialize
    path (which silently falls back to full XLA compiles) shows up as a
    p50 cliff, not a slow fleet rollout (ISSUE 16)."""
    import jax
    import jax.numpy as jnp

    from kubetorch_tpu.models.llama import LlamaConfig, llama_init
    from kubetorch_tpu.serve.aot_cache import AOTCompileCache
    from kubetorch_tpu.serve.engine import GenerationEngine

    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    with tempfile.TemporaryDirectory() as root:
        for _ in range(boots + 1):
            eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                                   prefill_buckets=(8,),
                                   aot_cache=AOTCompileCache(root))
            eng.stop()


def _measure_recorder_overhead(batches: int, ops: int) -> float:
    """The flight recorder's foreground price as a fraction: median
    per-flush wall cost at steady state, divided by the flush interval
    (0.1s — 10x the production default cadence, so the quotient is a
    deliberate overestimate of always-on).

    Each round refills the trace ring with ``ops`` real
    ``telemetry.stage`` spans — the exact state a busy pod's flush must
    drain — then times ONE ``flush()`` inline. cost/interval is the
    single-busy-core worst case: a foreground that never idles pays
    every flush millisecond (GIL + IO); any real deployment (idle gaps,
    spare cores) pays less. Inline timing is deterministic where the
    obvious paired on/off wall-clock design is not: a 3% signal sits
    below this host's scheduler jitter, and that design flapped between
    0% and 25% on the same build."""
    import statistics
    import time

    from kubetorch_tpu import telemetry
    from kubetorch_tpu.obs import FlightRecorder

    interval_s = 0.1
    with tempfile.TemporaryDirectory() as root:
        rec = FlightRecorder(os.path.join(root, "spool"),
                             name="perf-gate", interval_s=interval_s)
        rec.dir.mkdir(parents=True, exist_ok=True)
        costs = []
        for _ in range(batches + 1):
            for _ in range(ops):
                with telemetry.stage("recorder_probe"):
                    pass
            t0 = time.perf_counter()
            rec.flush()
            costs.append(time.perf_counter() - t0)
        rec.stop(final=False)
    # the first flush writes the full (not delta) snapshot — steady
    # state starts at the second
    return statistics.median(costs[1:]) / interval_s


def measure(calls: int, payload_kb: int, shm_calls: int, shm_kb: int,
            store_gets: int, rollout_calls: int, rollout_kb: int,
            train_steps: int, snapshot_saves: int,
            cold_boots: int, prev: dict = None) -> tuple:
    """({stage: p50 seconds}, bucket snapshot) for THIS attempt only.

    The registry is process-global and histograms only accumulate, so a
    re-measure (``--retries``) diffs the cumulative bucket counts against
    the ``prev`` snapshot — each attempt's p50 covers exactly its own
    observations, never a blend with the attempt that failed."""
    from kubetorch_tpu import telemetry
    from kubetorch_tpu.controller.app import (_parse_histogram_buckets,
                                              _quantile_from_buckets)
    from kubetorch_tpu.serving.env_contract import (
        KT_CLS_OR_FN_NAME, KT_FILE_PATH, KT_LAUNCH_ID, KT_MODULE_NAME,
        KT_PROJECT_ROOT)

    with tempfile.TemporaryDirectory() as root:
        with open(os.path.join(root, "perf_gate_payload.py"), "w") as f:
            f.write(PAYLOAD_MODULE)
        os.environ.update({
            KT_PROJECT_ROOT: root,
            KT_MODULE_NAME: "perf_gate_payload",
            KT_FILE_PATH: "perf_gate_payload.py",
            KT_CLS_OR_FN_NAME: "echo",
            KT_LAUNCH_ID: "perf-gate",
        })
        asyncio.run(_drive(calls, payload_kb, shm_calls, shm_kb))
    with tempfile.TemporaryDirectory() as root:
        with open(os.path.join(root, "rollout_gate_payload.py"), "w") as f:
            f.write(ROLLOUT_MODULE)
        os.environ.update({
            KT_PROJECT_ROOT: root,
            KT_MODULE_NAME: "rollout_gate_payload",
            KT_FILE_PATH: "rollout_gate_payload.py",
            KT_CLS_OR_FN_NAME: "rollout_apply",
            KT_LAUNCH_ID: "perf-gate-rollout",
        })
        asyncio.run(_drive_rollout(rollout_calls, rollout_kb))
    _drive_store(store_gets, snapshot_saves)
    _drive_train_step(train_steps)
    _drive_cold_start(cold_boots)
    text = telemetry.REGISTRY.render()
    out, snap = {}, {}
    for stage in GATED_STAGES:
        metric, selector = STAGE_SOURCES.get(
            stage, ("kt_stage_seconds", f'stage="{stage}"'))
        buckets = _parse_histogram_buckets(text, metric, selector)
        snap[stage] = dict(buckets)
        before = (prev or {}).get(stage, {})
        delta = {le: n - before.get(le, 0.0) for le, n in buckets.items()}
        p50 = _quantile_from_buckets(delta, 0.5)
        if p50 is None:
            raise RuntimeError(
                f"stage {stage!r} recorded no observations — the hot path "
                "lost its instrumentation (that IS a gate failure)")
        out[stage] = p50
    return out, snap


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--calls", type=int, default=80)
    p.add_argument("--payload-kb", type=int, default=64)
    p.add_argument("--shm-calls", type=int, default=40)
    p.add_argument("--shm-kb", type=int, default=512)
    p.add_argument("--store-gets", type=int, default=20)
    p.add_argument("--rollout-calls", type=int, default=30)
    p.add_argument("--rollout-kb", type=int, default=512)
    p.add_argument("--train-steps", type=int, default=20)
    p.add_argument("--snapshot-saves", type=int, default=20)
    p.add_argument("--cold-boots", type=int, default=6)
    p.add_argument("--recorder-batches", type=int, default=12)
    p.add_argument("--recorder-ops", type=int, default=2000)
    p.add_argument("--recorder-budget", type=float, default=float(
        os.environ.get("KT_RECORDER_OVERHEAD_BUDGET", "0.03")),
        help="absolute cap on the recorder_overhead ratio (fraction; the "
             "ISSUE-20 always-on promise is <3%%)")
    p.add_argument("--tolerance", type=float, default=float(
        os.environ.get("KT_PERF_GATE_TOLERANCE", "0.10")))
    p.add_argument("--abs-floor-ms", type=float, default=2.0)
    p.add_argument("--retries", type=int, default=1,
                   help="total measurement attempts for FAILING stages: a "
                        "stage only fails if the MEDIAN of its per-attempt "
                        "p50s exceeds the unchanged limit — shared-CI "
                        "scheduling bursts wash out, a real regression "
                        "(present in every attempt) still fails (make "
                        "test uses 3)")
    p.add_argument("--update", action="store_true",
                   help="re-baseline (deliberate hot-path changes only; "
                        "commit the JSON with the explaining PR)")
    args = p.parse_args()

    # the ratio stage runs FIRST, while the registry is small and the
    # process quiet — the recorder's price is measured, not the other
    # drivers' cache pollution
    recorder_ratio = _measure_recorder_overhead(args.recorder_batches,
                                                args.recorder_ops)

    measured, snap = measure(args.calls, args.payload_kb, args.shm_calls,
                             args.shm_kb, args.store_gets,
                             args.rollout_calls, args.rollout_kb,
                             args.train_steps, args.snapshot_saves,
                             args.cold_boots)

    if args.update or not os.path.exists(BASELINE_PATH):
        baseline = {
            "stages": {s: round(v, 6) for s, v in measured.items()},
            "calls": args.calls,
            "payload_kb": args.payload_kb,
            "shm_calls": args.shm_calls,
            "shm_kb": args.shm_kb,
            "store_gets": args.store_gets,
            "rollout_calls": args.rollout_calls,
            "rollout_kb": args.rollout_kb,
            "train_steps": args.train_steps,
            "snapshot_saves": args.snapshot_saves,
            "cold_boots": args.cold_boots,
            # informational only: recorder_overhead is judged against the
            # ABSOLUTE --recorder-budget, never against this snapshot
            "recorder_overhead": round(recorder_ratio, 6),
            "note": "p50 seconds per stage from scripts/check_perf_gate.py"
                    " --update; gate = p50 <= baseline*(1+tol) + floor",
        }
        with open(BASELINE_PATH, "w") as f:
            json.dump(baseline, f, indent=2)
            f.write("\n")
        print(f"perf-gate: baseline written to {BASELINE_PATH}: "
              + ", ".join(f"{s}={v * 1000:.3f}ms"
                          for s, v in measured.items()))
        return 0

    with open(BASELINE_PATH) as f:
        baseline = json.load(f)["stages"]
    floor_s = args.abs_floor_ms / 1000.0
    limits = {s: float(baseline[s]) * (1.0 + args.tolerance) + floor_s
              for s in GATED_STAGES}
    failures = []
    for stage in GATED_STAGES:
        got = measured[stage]
        verdict = "ok" if got <= limits[stage] else "REGRESSED"
        print(f"perf-gate: {stage:<12} p50 {got * 1000:8.3f}ms  "
              f"baseline {float(baseline[stage]) * 1000:8.3f}ms  "
              f"limit {limits[stage] * 1000:8.3f}ms  {verdict}")
        if got > limits[stage]:
            failures.append(stage)

    # median-of-N re-measure (ISSUE 19 satellite): failing stages get up
    # to --retries total attempts; the verdict compares the MEDIAN of the
    # per-attempt p50s against the SAME limit — the gate never loosens,
    # it just refuses to fail on one scheduling burst. Each attempt
    # re-drives the full workload (stages share drivers) but only the
    # stages that failed are re-judged.
    import statistics

    # recorder_overhead (ISSUE 20): absolute-budget ratio stage, its own
    # median-of-N retries (same ethos: the budget never loosens, one
    # scheduling burst doesn't flunk an always-on promise that holds)
    rec_attempts = [recorder_ratio]
    rec_verdict = "ok" if recorder_ratio <= args.recorder_budget \
        else "REGRESSED"
    print(f"perf-gate: recorder_overhead ratio {recorder_ratio * 100:6.2f}%"
          f"  budget {args.recorder_budget * 100:.1f}%  {rec_verdict}")
    for attempt in range(2, max(1, args.retries) + 1):
        if statistics.median(rec_attempts) <= args.recorder_budget:
            break
        print(f"perf-gate: re-measuring recorder_overhead "
              f"(attempt {attempt}/{args.retries})")
        rec_attempts.append(_measure_recorder_overhead(
            args.recorder_batches, args.recorder_ops))
    rec_median = statistics.median(rec_attempts)
    if rec_median > args.recorder_budget:
        print(f"perf-gate: recorder_overhead median-of-"
              f"{len(rec_attempts)} {rec_median * 100:6.2f}%  budget "
              f"{args.recorder_budget * 100:.1f}%  REGRESSED")

    attempts = {s: [measured[s]] for s in GATED_STAGES}
    for attempt in range(2, max(1, args.retries) + 1):
        if not failures:
            break
        print(f"perf-gate: re-measuring {len(failures)} failing stage(s) "
              f"(attempt {attempt}/{args.retries}): {', '.join(failures)}")
        remeasured, snap = measure(
            args.calls, args.payload_kb, args.shm_calls, args.shm_kb,
            args.store_gets, args.rollout_calls, args.rollout_kb,
            args.train_steps, args.snapshot_saves, args.cold_boots,
            prev=snap)
        for stage in GATED_STAGES:
            attempts[stage].append(remeasured[stage])
        still = []
        for stage in failures:
            med = statistics.median(attempts[stage])
            verdict = "ok" if med <= limits[stage] else "REGRESSED"
            print(f"perf-gate: {stage:<12} median-of-{attempt} "
                  f"{med * 1000:8.3f}ms  "
                  f"limit {limits[stage] * 1000:8.3f}ms  {verdict}")
            if med > limits[stage]:
                still.append(stage)
        failures = still
    if rec_median > args.recorder_budget:
        failures.append("recorder_overhead")
    if failures:
        print(f"\nperf-gate: FAIL — {', '.join(failures)} p50 regressed "
              f"past baseline*(1+{args.tolerance:g}) + "
              f"{args.abs_floor_ms:g}ms. Either fix the hot path or, for "
              "a deliberate trade, re-baseline with --update and justify "
              "it in the PR.")
        return 1
    print("perf-gate: OK — dispatch hot path within budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Headline benchmark: Llama pretraining tokens/sec/chip.

Runs a scaled Llama-3-architecture training step on the TPU, in this process,
and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

There is no probe, retry, replay or CPU fallback: without a TPU it says so on
stderr and exits non-zero, printing nothing under the metric's name. Run it on
a machine that has the chip.

The reference publishes no numeric baselines (BASELINE.md — "published": {}),
so ``vs_baseline`` reports achieved MFU divided by a 0.40 MFU target — i.e.
1.0 means we hit 40% model-FLOPs utilization on the chip, the strong-baseline
regime for this size class.

``--step-overlap`` and ``--pipeline`` are host-mesh proxies (8 forced CPU
devices in a fresh subprocess) and label their output as such.

Env knobs: KT_BENCH_BATCH (starting batch), KT_BENCH_REMAT=1 (remat on).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time


PEAK_BF16_FLOPS = {
    # per-chip dense bf16 peak, keyed by a substring of device_kind
    "v4": 275e12,
    "v5 lite": 197e12,   # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,   # trillium
    "v6e": 918e12,
}
MFU_TARGET = 0.40

# ~0.5B-param Llama-3 architecture that fits one 16G-HBM chip with Adam
# state (LlamaConfig kwargs; chip_smoke.py trains the same model).
BENCH_MODEL = dict(vocab_size=32768, dim=1536, n_layers=12, n_heads=12,
                   n_kv_heads=4, ffn_dim=6144, max_seq_len=2048,
                   attn_impl="flash")


def peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device``; a kind that is not in the table is an
    error, not a default — an MFU against the wrong peak is not a number."""
    kind = getattr(device, "device_kind", "").lower()
    for key, val in PEAK_BF16_FLOPS.items():
        if key in kind:
            return val
    raise KeyError(f"no bf16 peak recorded for device_kind {kind!r}; add it "
                   f"to PEAK_BF16_FLOPS with its source")


def _host_mesh_subprocess(mode: str) -> int:
    """Run a host-mesh proxy regime in a fresh subprocess on 8 forced CPU
    devices (the flags must precede jax init)."""
    env = {**os.environ, "KT_BENCH_WORKER": mode, "JAX_PLATFORMS": "cpu"}
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    return subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, timeout=900).returncode


def main() -> int:
    mode = os.environ.get("KT_BENCH_WORKER")
    if mode == "step-overlap":
        return step_overlap_worker()
    if mode == "pipeline":
        return pipeline_worker()
    if "--pipeline" in sys.argv:
        # elastic pipeline regime (ISSUE 17): pipelined-vs-SPMD A/B plus a
        # real stage-SIGKILL re-group drill
        return _host_mesh_subprocess("pipeline")
    if "--step-overlap" in sys.argv:
        # step-anatomy A/B regime (ISSUE 12)
        return _host_mesh_subprocess("step-overlap")
    return bench_worker()


_T0 = time.monotonic()


def _progress(msg: str) -> None:
    # stderr heartbeat so a hung attempt shows WHERE it hung (stdout must
    # stay one clean JSON line for the driver)
    print(f"[bench-worker +{time.monotonic() - _T0:5.0f}s] {msg}",
          file=sys.stderr, flush=True)


def bench_worker() -> int:
    from kubetorch_tpu.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import jax

    _progress("initializing accelerator backend (jax.devices())")
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        print(f"bench: no TPU — accelerator backend unavailable ({e})",
              file=sys.stderr)
        return 1
    if dev.platform != "tpu":
        print(f"bench: no TPU in the device list (jax came up on "
              f"{dev.platform!r}); this benchmark measures the chip and has "
              "no CPU fallback", file=sys.stderr)
        return 1
    peak = peak_flops(dev)
    _progress(f"backend up: {dev.device_kind}")

    import jax.numpy as jnp
    import optax

    from kubetorch_tpu.models.llama import (LlamaConfig, llama_init,
                                            llama_loss_chunked)
    from kubetorch_tpu.train import init_train_state, make_train_step

    # batch tuned down on RESOURCE_EXHAUSTED. KT_BENCH_REMAT=1 turns remat
    # on (bigger-HBM chips may prefer a larger batch with it).
    cfg = LlamaConfig(**BENCH_MODEL,
                      remat=os.environ.get("KT_BENCH_REMAT", "0") == "1")
    # start high and let the RESOURCE_EXHAUSTED handler halve: larger
    # batches amortize per-step overhead. KT_BENCH_BATCH pins the starting
    # batch (tuning experiments).
    batch, seq, steps, warmup = 16, 2048, 10, 3
    batch = int(os.environ.get("KT_BENCH_BATCH", batch))

    _progress(f"init params ({cfg.param_count():,})")
    params = llama_init(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-4)
    state = init_train_state(params, opt)
    _progress("params initialized")
    # chunked CE: never materializes the (B, S, V) fp32 logits tensor
    step_fn = make_train_step(
        lambda p, t, y: llama_loss_chunked(p, t, y, cfg, chunk=256),
        optimizer=opt)

    def run(batch_size):
        nonlocal state
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch_size, seq), 0, cfg.vocab_size)
        b = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
        _progress(f"warmup/compile start (batch={batch_size})")
        for i in range(warmup):
            state, m = step_fn(state, b)
            if i == 0:
                float(m["loss"])
                _progress("first step compiled + executed")
        float(m["loss"])  # host fetch: hard sync
        _progress("warmup done; measuring")
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step_fn(state, b)
        float(m["loss"])
        dt = time.perf_counter() - t0
        tps = batch_size * seq * steps / dt
        # Sanity: an impossible rate (> chip peak / ~1 flop/token) means the
        # timing was an async-dispatch artifact; re-measure with a per-step
        # host sync, which cannot overlap execution with the timer.
        if tps * 6 * cfg.param_count() > peak:
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = step_fn(state, b)
                float(m["loss"])
            dt = time.perf_counter() - t0
            tps = batch_size * seq * steps / dt
        return tps

    def _looks_oom(e: Exception) -> bool:
        # only an HBM overflow is a cue to halve the batch; any other
        # compile or run failure (a refused kernel included) is raised
        s = str(e)
        return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s

    tokens_per_sec = None
    while batch >= 1:
        try:
            tokens_per_sec = run(batch)
            break
        except Exception as e:
            if _looks_oom(e) and batch > 1:
                batch //= 2
                # release the failed attempt's arrays BEFORE re-initializing:
                # `params` shares device buffers with `state`, and keeping
                # them alive would give the halved-batch retry LESS free HBM
                # than a fresh run at that batch size
                state = params = None   # noqa: F841
                params = llama_init(jax.random.PRNGKey(0), cfg)
                state = init_train_state(params, opt)
                continue
            raise

    # the step is not sharded: the chips used are the ones its state is on
    n_chips = len(jax.tree_util.tree_leaves(state.params)[0].devices())
    tps_per_chip = tokens_per_sec / n_chips
    model_flops = 6 * cfg.param_count() + 12 * cfg.n_layers * cfg.dim * seq
    mfu = tps_per_chip * model_flops / peak
    from kubetorch_tpu import telemetry
    telemetry.train_metrics()["mfu"].set(mfu)   # the gated headline gauge

    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tps_per_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / MFU_TARGET, 4),
        "detail": {
            "params": cfg.param_count(),
            "batch": batch,
            "seq": seq,
            "mfu": round(mfu, 4),
            "device": dev.device_kind,
            "n_chips": n_chips,
        },
    }))
    return 0


class _TransferLeaf:
    """A pytree leaf that models a device array's D2H transfer on the CPU
    proxy: ``copy_to_host_async`` is an O(dispatch) no-op (the DMA would
    run concurrently with compute), materializing the value pays the
    transfer time. CPU jax arrays gather zero-copy (~0.2ms for 64MB), so
    without this proxy the blocking-vs-async A/B measures nothing — the
    modeled rate (8 GB/s, a v5e-ish PCIe D2H) makes the stall the ISSUE
    claims visible and honest about being modeled."""

    RATE = 8e9  # bytes/s

    def __init__(self, arr):
        self._arr = arr

    def copy_to_host_async(self):
        return None

    def __array__(self, dtype=None):
        time.sleep(self._arr.nbytes / self.RATE)
        return self._arr if dtype is None else self._arr.astype(dtype)


def step_overlap_worker() -> int:
    """`bench.py --step-overlap`: the ISSUE 12 step-anatomy A/B on the
    8-device forced-host mesh. Emits ONE bench-convention JSON line with
    overlap on/off step times, bit-comparability, accumulator shard
    fraction, compiled temp bytes, and the snapshot-stall A/B."""
    import statistics
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubetorch_tpu import telemetry
    from kubetorch_tpu.models.llama import LlamaConfig, llama_init, llama_loss
    from kubetorch_tpu.parallel.mesh import build_mesh
    from kubetorch_tpu.parallel.sharding import LLAMA_RULES
    from kubetorch_tpu.train import init_train_state, make_train_step
    from kubetorch_tpu.train import checkpoint as ckpt

    assert len(jax.devices()) >= 8, "needs the forced 8-device host mesh"
    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    opt = optax.adam(1e-3)
    loss = lambda p, t, y: llama_loss(p, t, y, cfg)  # noqa: E731
    mesh = build_mesh({"data": 2, "fsdp": 4})
    batch_n, seq, accum, steps, warmup = 8, 64, 4, 10, 3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch_n, seq), 0,
                                cfg.vocab_size)
    hist = telemetry.train_metrics()["step_seconds"]

    results = {}
    grads_by_mode = {}
    for overlap in (False, True):
        step = make_train_step(loss, optimizer=opt, mesh=mesh,
                               rules=LLAMA_RULES, accum_steps=accum,
                               overlap_grads=overlap)
        state = step.shard_state(init_train_state(
            llama_init(jax.random.PRNGKey(0), cfg), opt))
        b = {"tokens": jax.device_put(tokens, step.batch_sharding),
             "targets": jax.device_put(jnp.roll(tokens, -1, 1),
                                       step.batch_sharding)}
        # pure accumulation probe BEFORE the donating step consumes state
        l, g = step.grads_fn(state.params, b)
        jax.block_until_ready(g)
        grads_by_mode[overlap] = (float(l), jax.device_get(g))
        frac = []
        for leaf in jax.tree_util.tree_leaves(g):
            if leaf.size:
                frac.append(leaf.addressable_shards[0].data.size / leaf.size)
        ma = step.jitted.lower(state, b).compile().memory_analysis()
        times = []
        for i in range(warmup + steps):
            t0 = time.perf_counter()
            state, m = step(state, b)
            with telemetry.timed(hist, phase="grad_sync"):
                gn = float(m["grad_norm"])   # host sync: grads are real
            if i >= warmup:
                times.append(time.perf_counter() - t0)
        dt = statistics.median(times)
        results["overlap" if overlap else "plain"] = {
            "step_ms_p50": round(dt * 1000, 3),
            "tokens_per_sec": round(batch_n * seq / dt, 1),
            "grad_norm": gn,
            "loss": float(m["loss"]),
            "min_accum_shard_fraction": round(min(frac), 4),
            "compiled_temp_bytes": int(ma.temp_size_in_bytes),
        }

    # bit-comparability of the accumulated grads themselves
    (l0, g0), (l1, g1) = grads_by_mode[False], grads_by_mode[True]
    max_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(c))))
                   for a, c in zip(jax.tree_util.tree_leaves(g0),
                                   jax.tree_util.tree_leaves(g1)))
    results["bit_comparable"] = {
        "loss_abs_diff": abs(l0 - l1),
        "grad_max_abs_diff": max_diff,
    }

    # snapshot-stall A/B: >=64MB modeled-transfer state against a real
    # store subprocess (the blocking comparator is the pre-ISSUE-12 inline
    # gather; the async number is maybe_save's inline return)
    from kubetorch_tpu.utils.procs import (free_port, kill_process_tree,
                                           wait_for_port)
    proxy = {f"w{i}": _TransferLeaf(
        np.random.default_rng(i).standard_normal(1 << 20).astype(np.float32))
        for i in range(16)}                                   # 16 x 4MB
    state_bytes = sum(leaf._arr.nbytes for leaf in proxy.values())
    t0 = time.perf_counter()
    gathered = ckpt._snapshot_async(proxy)()       # blocking: fan-out+gather
    stall_blocking = time.perf_counter() - t0
    assert len(gathered) == 16
    port = free_port()
    with tempfile.TemporaryDirectory() as root:
        env = {**os.environ, "KT_STORE_FSYNC": "0", "KT_SCRUB_INTERVAL_S": "0"}
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
             "--host", "127.0.0.1", "--port", str(port), "--root", root],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            assert wait_for_port("127.0.0.1", port, timeout=30)
            ck = ckpt.Checkpointer("bench/step-overlap",
                                   store_url=f"http://127.0.0.1:{port}",
                                   every=1)
            t0 = time.perf_counter()
            fut = ck.maybe_save(proxy, 1)
            stall_async = time.perf_counter() - t0
            assert fut is not None
            ck.flush(timeout=120)
        finally:
            kill_process_tree(proc.pid)

    ratio = stall_blocking / max(stall_async, 1e-9)
    telemetry.train_metrics()["mfu"].set(0.0)   # CPU proxy: no real MFU
    print(json.dumps({
        "metric": "train_step_overlap_ab",
        "value": results["overlap"]["tokens_per_sec"],
        "unit": "tokens/s/chip",
        "vs_baseline": round(results["overlap"]["tokens_per_sec"]
                             / max(results["plain"]["tokens_per_sec"], 1e-9),
                             4),
        "detail": {
            "mfu": 0.0,
            "device": "cpu-proxy (8 forced host devices, data=2 fsdp=4)",
            "accum_steps": accum,
            **results,
            "snapshot_stall": {
                "state_bytes": state_bytes,
                "blocking_ms": round(stall_blocking * 1000, 3),
                "async_inline_ms": round(stall_async * 1000, 3),
                "ratio": round(ratio, 1),
                "modeled_d2h_gbps": _TransferLeaf.RATE / 1e9,
            },
        },
    }))
    if ratio < 10:
        print(f"step-overlap: FAIL — snapshot stall ratio {ratio:.1f}x < "
              "10x (async path is blocking on the host copy again?)",
              file=sys.stderr)
        return 1
    return 0


def pipeline_worker() -> int:
    """`bench.py --pipeline`: the ISSUE 17 elastic-pipeline regime. Two
    phases, ONE bench-convention JSON line:

    A. pipelined llama loss (pipe=4) vs pure-SPMD (data=4) at EQUAL chips
       on the forced-host mesh: tokens/s for both, plus the analytic
       bubble fraction (from the elastic membership math) and the measured
       one (throughput deficit vs SPMD — folds in ppermute overhead, so
       it upper-bounds the schedule bubble).
    B. re-group cost: SIGKILL stage 1 of the real 4-subprocess trainer
       (tests/assets/pipeline_trainer.py) and read the stall from fault
       detection to the first post-re-group committed step.

    Exits nonzero when the drill loses a committed step or the stall is
    not a finite positive number.
    """
    import statistics
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubetorch_tpu.models.llama import LlamaConfig, llama_init, llama_loss
    from kubetorch_tpu.parallel.mesh import build_mesh
    from kubetorch_tpu.parallel.pipeline import llama_loss_pipelined
    from kubetorch_tpu.parallel.pipeline_elastic import ElasticPipeline

    assert len(jax.devices()) >= 8, "needs the forced 8-device host mesh"
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = LlamaConfig.tiny(n_layers=4, attn_impl="xla", dtype=jnp.float32,
                           remat=False)
    chips, batch_n, seq, M, steps, warmup = 4, 8, 64, 8, 10, 3
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch_n, seq), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, 1)

    def timed(fn, *args):
        out = fn(*args)
        float(out)                       # compile + first run
        for _ in range(warmup):
            float(fn(*args))
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            float(fn(*args))             # host fetch = hard sync
            times.append(time.perf_counter() - t0)
        return float(out), statistics.median(times)

    # -- A1: pipelined on pipe=4 --------------------------------------------
    pipe_mesh = Mesh(np.asarray(jax.devices()[:chips]).reshape(chips),
                     ("pipe",))

    def place(leaf, is_layer):
        spec = P("pipe") if is_layer else P()
        return jax.device_put(leaf, NamedSharding(pipe_mesh, spec))

    sharded = {
        "embed": place(params["embed"], False),
        "layers": jax.tree_util.tree_map(lambda l: place(l, True),
                                         params["layers"]),
        "final_norm": place(params["final_norm"], False),
        "lm_head": place(params["lm_head"], False),
    }
    pipe_fn = jax.jit(lambda p, t, y: llama_loss_pipelined(
        p, t, y, cfg, pipe_mesh, n_microbatches=M))
    loss_pipe, dt_pipe = timed(pipe_fn, sharded, tokens, targets)

    # -- A2: SPMD (data=4) at the same chip count ---------------------------
    spmd_mesh = build_mesh({"data": chips}, devices=jax.devices()[:chips])
    spmd_tokens = jax.device_put(
        tokens, NamedSharding(spmd_mesh, P("data")))
    spmd_targets = jax.device_put(
        targets, NamedSharding(spmd_mesh, P("data")))
    spmd_fn = jax.jit(lambda p, t, y: llama_loss(p, t, y, cfg))
    loss_spmd, dt_spmd = timed(spmd_fn, params, spmd_tokens, spmd_targets)

    tps_pipe = batch_n * seq / dt_pipe
    tps_spmd = batch_n * seq / dt_spmd
    # the membership math IS the analytic model: (P-1)/(M+P-1) at width 1
    analytic = ElasticPipeline(n_layers=cfg.n_layers, n_stages=chips,
                               n_microbatches=M,
                               job="bench").membership.bubble_fraction
    measured = max(0.0, 1.0 - dt_spmd / dt_pipe)

    # -- B: stage-SIGKILL re-group drill (real subprocesses) ----------------
    trainer = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tests", "assets", "pipeline_trainer.py")
    drill_steps = 4
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("KT_CHAOS")}
    env.update({"JAX_PLATFORMS": "cpu",
                "KT_CHAOS": "kill-stage:9@1", "KT_CHAOS_STAGE": "1",
                "KT_CHAOS_SEED": "7"})
    with tempfile.TemporaryDirectory() as root:
        result = os.path.join(root, "result.jsonl")
        proc = subprocess.run(
            [sys.executable, trainer, "--steps", str(drill_steps),
             "--stages", "4", "--result", result,
             "--workdir", os.path.join(root, "wd")],
            env=env, timeout=180, capture_output=True, text=True)
        if proc.returncode != 0:
            print(f"pipeline drill failed rc={proc.returncode}:\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            return 1
        recs = [json.loads(line)
                for line in open(result, encoding="utf-8")]
    committed = sorted(r["step"] for r in recs if r["event"] == "committed")
    regroups = [r for r in recs if r["event"] == "regroup"]
    done = [r for r in recs if r["event"] == "regroup-done"]
    stall_s = done[0]["stall_s"] if done else float("nan")
    lost = [s for s in range(1, drill_steps + 1) if s not in committed]

    from kubetorch_tpu import telemetry
    telemetry.train_metrics()["mfu"].set(0.0)   # CPU proxy: no real MFU
    print(json.dumps({
        "metric": "pipeline_elastic_ab",
        "value": round(tps_pipe, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_pipe / max(tps_spmd, 1e-9), 4),
        "detail": {
            "mfu": 0.0,
            "device": f"cpu-proxy (8 forced host devices; pipe={chips} "
                      f"vs data={chips})",
            "chips": chips,
            "n_microbatches": M,
            "pipeline_tokens_per_sec": round(tps_pipe, 1),
            "spmd_tokens_per_sec": round(tps_spmd, 1),
            "bubble_fraction_analytic": round(analytic, 4),
            "bubble_fraction_measured": round(measured, 4),
            "loss_abs_diff": abs(loss_pipe - loss_spmd),
            "regroup": {
                "cause": regroups[0].get("cause") if regroups else None,
                "mode": regroups[0].get("mode") if regroups else None,
                "stall_s": round(stall_s, 3)
                if stall_s == stall_s else None,
                "steps_committed": len(committed),
                "lost_steps": lost,
            },
        },
    }))
    if lost or not regroups:
        print(f"pipeline: FAIL — lost steps {lost} / regroups "
              f"{len(regroups)} (drill must re-group and commit every "
              "step)", file=sys.stderr)
        return 1
    if not (stall_s == stall_s and 0 < stall_s < float("inf")):
        print(f"pipeline: FAIL — re-group stall {stall_s} not finite",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A 4-stage pipelined trainer for the elastic pipeline chaos drill.

The subprocess half of ISSUE 17: a DRIVER process owns the full param
tree, the :class:`ElasticPipeline` membership, and a
:class:`PipelineSupervisor`; each STAGE is a real subprocess owning a
contiguous layer shard, chained through a file-based activation data
plane whose keys come from ``ElasticPipeline.activation_key`` — epoch-
scoped, so a zombie stage's writes land in a namespace nobody reads.

Determinism is the oracle: the forward is a fixed float32 recurrence
applied layer by layer in ascending order (identical op order however
the layers are partitioned), the param update depends only on
``(layer, step)``, and the per-step loss folds the final-boundary
activations in ascending microbatch order — so a pipelined run, a
re-grouped run, and the single-process ``--replay`` all produce
bit-identical ``tree_fingerprint``s for the same committed step. The
``pipeline-progress`` soak invariant compares exactly that.

Chaos wiring: the driver inherits ``KT_CHAOS`` (``kill-stage:SIG@N`` /
``stall-stage:SECONDS@N``) + ``KT_CHAOS_STAGE`` and passes them to epoch-0
stage workers only (recovery runs clean, matching the soak conductor's
restart convention); each worker exports its own ``KT_STAGE`` and
consults ``chaos.stage_kill_plan`` / ``stage_stall_plan`` at the top of
every step op. A killed stage is seen by the supervisor as a death
(classify_death); a stalled stage keeps its process alive but stops
heartbeating — workers heartbeat *while waiting for input* too, so only
the genuinely sleeping stage goes quiet — and is classified ``Slow``.

Ledger (JSON lines at ``--result``; the conductor imports them as
``kind="pipeline"`` history records):

- ``{"event": "placed", "stage": s, "epoch": e}``
- ``{"event": "committed", "step": n, "epoch": e, "loss": x,
  "fingerprint": f}``
- ``{"event": "regroup", "epoch": e, "cause": c, "mode": m, "lost_stage": s}``
- ``{"event": "regroup-done", "step": n, "stall_s": x}`` — first
  post-re-group commit, with the measured stall
- ``{"event": "stale-refused", "stage": s, "epoch": old}`` — the zombie
  confirm bounced by the epoch fence
- ``{"event": "replay", "step": n, "fingerprint": f}`` (``--replay``)
- ``{"event": "done", "final_step": n, "fingerprint": f}``
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# stage workers must boot FAST (the supervisor's straggler clock starts
# at launch), so only the light chaos module is imported at top level;
# the driver/replay paths pull in checkpoint/telemetry (jax-adjacent)
# lazily inside their entry points
from kubetorch_tpu import chaos  # noqa: E402

JOB = "soak"
WIDTH = 16          # activation / weight vector width
MICROBATCHES = 4    # fixed DATA microbatch count (schedule M is separate)


def initial_params(n_layers: int) -> dict:
    rng = np.random.default_rng(11)
    return {l: rng.standard_normal(WIDTH).astype(np.float32)
            for l in range(n_layers)}


def microbatch_input(step: int, mb: int) -> np.ndarray:
    # deterministic per-(step, microbatch) input — no RNG state to drift
    base = np.arange(WIDTH, dtype=np.float32)
    return base * np.float32(0.01 * (mb + 1)) + np.float32(step)


def apply_layer(h: np.ndarray, w: np.ndarray) -> np.ndarray:
    # basic float32 ops only: bit-identical wherever the layer runs
    return h * np.float32(0.5) + w


def update_weight(w: np.ndarray, layer: int, step: int) -> np.ndarray:
    # depends only on (layer, step): partitioning-invariant by design
    return w * np.float32(0.9) + np.float32(0.01) * np.float32(
        layer + 1) * np.float32(step)


def committed_state(params: dict, loss: np.float32) -> dict:
    return {"layers": {f"w{l}": params[l] for l in sorted(params)},
            "loss": np.asarray(loss, dtype=np.float32)}


def emit(path: str, record: dict) -> None:
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
        f.flush()
        os.fsync(f.fileno())


def act_path(workdir: str, epoch: int, step: int, boundary: int,
             mb: int) -> str:
    # the same key shape ElasticPipeline.activation_key produces — epoch
    # first, so stale-epoch writes are invisible to the new membership
    return os.path.join(workdir,
                        f"pipeline/{JOB}/e{epoch}/step{step}"
                        f"/b{boundary}/mb{mb}.npy")


def hb_path(workdir: str, epoch: int, stage: int) -> str:
    return os.path.join(workdir, f"hb-e{epoch}-s{stage}")


def write_array(path: str, arr: np.ndarray) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.save(f, arr)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)     # atomic: readers never see a torn file


def read_array(path: str):
    try:
        with open(path, "rb") as f:
            return np.load(f)
    except (OSError, ValueError):
        return None            # not there yet / mid-rename


# ---------------------------------------------------------------------------
# stage worker
# ---------------------------------------------------------------------------


def run_stage(args) -> int:
    os.environ[chaos.STAGE_ENV] = str(args.stage)
    kill_plan = chaos.stage_kill_plan()
    stall_plan = chaos.stage_stall_plan()
    layers = [int(x) for x in args.layers.split(",")]
    shard = dict(np.load(args.shard))
    weights = {l: shard[str(l)] for l in layers}
    parent = os.getppid()
    beats = 0

    def beat() -> None:
        nonlocal beats
        beats += 1
        with open(hb_path(args.workdir, args.epoch, args.stage), "w") as f:
            f.write(str(beats))

    for op, step in enumerate(range(args.start_step, args.steps + 1)):
        if op in kill_plan:
            # mid-step death: the driver's last commit is the anchor the
            # zero-lost-committed-steps check holds against
            os.kill(os.getpid(), kill_plan[op])
        stall = stall_plan.get(op)
        if stall:
            time.sleep(stall)   # alive but silent: must classify as Slow
        for mb in range(args.microbatches):
            src = act_path(args.workdir, args.epoch, step, args.stage, mb)
            h = read_array(src)
            while h is None:
                beat()          # heartbeat WHILE waiting: only a stalled
                time.sleep(0.01)  # stage goes quiet, not a blocked one
                if os.getppid() != parent:
                    return 0    # driver died; don't orphan-spin forever
                h = read_array(src)
            for l in layers:
                h = apply_layer(h, weights[l])
            write_array(act_path(args.workdir, args.epoch, step,
                                 args.stage + 1, mb), h)
            beat()
        for l in layers:
            weights[l] = update_weight(weights[l], l, step)
    return 0


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_driver(args) -> int:
    from kubetorch_tpu.exceptions import StaleStageEpochError
    from kubetorch_tpu.parallel.pipeline_elastic import ElasticPipeline
    from kubetorch_tpu.serving.pipeline_supervisor import \
        PipelineSupervisor
    from kubetorch_tpu.train.checkpoint import (Checkpointer,
                                                tree_fingerprint)

    n_layers = 2 * args.stages
    os.makedirs(args.workdir, exist_ok=True)
    params = initial_params(n_layers)
    ckpt = Checkpointer(args.base_key, store_url=args.store,
                        every=1) if args.store else None
    pipe = ElasticPipeline(n_layers, args.stages,
                           n_microbatches=MICROBATCHES, job=JOB)
    cur = {"step": 1}
    chaos_env = {k: os.environ[k] for k in
                 (chaos.CHAOS_ENV, chaos.CHAOS_STAGE_ENV,
                  chaos.CHAOS_SEED_ENV) if k in os.environ}

    def launch(assignment, epoch, resume):
        shard_file = os.path.join(args.workdir,
                                  f"shard-e{epoch}-s{assignment.stage}.npz")
        np.savez(shard_file, **{str(l): params[l]
                                for l in assignment.layers})
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        for k in (chaos.CHAOS_ENV, chaos.CHAOS_STAGE_ENV):
            env.pop(k, None)
        if not resume:
            env.update(chaos_env)   # recovery runs clean: epoch 0 only
        env[chaos.STAGE_ENV] = str(assignment.stage)
        log = open(os.path.join(args.workdir,
                                f"stage-e{epoch}-s{assignment.stage}.log"),
                   "wb")
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--stage-worker",
             "--stage", str(assignment.stage),
             "--layers", ",".join(str(l) for l in assignment.layers),
             "--epoch", str(epoch), "--workdir", args.workdir,
             "--shard", shard_file,
             "--microbatches", str(MICROBATCHES),
             "--steps", str(args.steps),
             "--start-step", str(cur["step"]),
             "--result", args.result],
            env=env, stdout=subprocess.DEVNULL, stderr=log)
        log.close()
        emit(args.result, {"event": "placed", "stage": assignment.stage,
                           "epoch": epoch})
        return proc

    sup = PipelineSupervisor(pipe, launch, stall_after_s=args.stall_after)
    sup.start()
    hb_seen: dict = {}

    def pump_beats(epoch: int) -> None:
        for a in pipe.membership.assignments:
            try:
                with open(hb_path(args.workdir, epoch, a.stage)) as f:
                    val = f.read()
            except OSError:
                continue
            if hb_seen.get((epoch, a.stage)) != val:
                hb_seen[(epoch, a.stage)] = val
                sup.beat(a.stage)

    def handle_regroup(ev: dict) -> None:
        emit(args.result, {"event": "regroup", "epoch": ev["epoch"],
                           "cause": ev["cause"], "mode": ev.get("mode"),
                           "lost_stage": ev["lost_stage"]})
        # the zombie's side of the fence: a confirm under the pre-regroup
        # epoch must raise the typed error, never hand out an assignment
        try:
            pipe.confirm(ev["lost_stage"], ev["epoch"] - 1)
        except StaleStageEpochError:
            emit(args.result, {"event": "stale-refused",
                               "stage": ev["lost_stage"],
                               "epoch": ev["epoch"] - 1})
        if ckpt is not None:
            restored = ckpt.restore()
            if restored is not None:
                state, _ = restored
                for l in range(n_layers):
                    params[l] = np.asarray(state["layers"][f"w{l}"],
                                           dtype=np.float32)

    while cur["step"] <= args.steps:
        step = cur["step"]
        epoch = pipe.epoch
        membership = pipe.membership
        for mb in range(MICROBATCHES):
            write_array(act_path(args.workdir, epoch, step, 0, mb),
                        microbatch_input(step, mb))
        final_b = membership.n_stages
        deadline = time.monotonic() + args.step_timeout
        regrouped = False
        while True:
            outs = [read_array(act_path(args.workdir, epoch, step,
                                        final_b, mb))
                    for mb in range(MICROBATCHES)]
            if all(o is not None for o in outs):
                break
            pump_beats(epoch)
            ev = sup.poll()
            if ev is not None:
                handle_regroup(ev)
                regrouped = True
                break
            if time.monotonic() > deadline:
                emit(args.result, {"event": "error",
                                   "detail": f"step {step} timed out"})
                sup.stop()
                return 1
            time.sleep(0.02)
        if regrouped:
            continue            # retry the SAME step at the new epoch
        loss = np.float32(0.0)
        for mb in range(MICROBATCHES):   # ascending: fixed fold order
            loss = loss + np.float32(np.sum(outs[mb], dtype=np.float32))
        for l in range(n_layers):
            params[l] = update_weight(params[l], l, step)
        state = committed_state(params, loss)
        fp = tree_fingerprint(state)
        if ckpt is not None:
            ckpt.save(state, step)
        emit(args.result, {"event": "committed", "step": step,
                           "epoch": pipe.epoch, "loss": float(loss),
                           "fingerprint": fp})
        stall = sup.note_committed_step(step)
        if stall is not None:
            emit(args.result, {"event": "regroup-done", "step": step,
                               "stall_s": round(stall, 3)})
        cur["step"] = step + 1
    fp = tree_fingerprint(committed_state(params, loss))
    emit(args.result, {"event": "done", "final_step": args.steps,
                       "fingerprint": fp})
    sup.stop()
    return 0


# ---------------------------------------------------------------------------
# unpartitioned replay (the bit-identity oracle)
# ---------------------------------------------------------------------------


def run_replay(args) -> int:
    from kubetorch_tpu.train.checkpoint import tree_fingerprint

    n_layers = 2 * args.stages
    params = initial_params(n_layers)
    for step in range(1, args.steps + 1):
        loss = np.float32(0.0)
        for mb in range(MICROBATCHES):
            h = microbatch_input(step, mb)
            for l in range(n_layers):
                h = apply_layer(h, params[l])
            loss = loss + np.float32(np.sum(h, dtype=np.float32))
        for l in range(n_layers):
            params[l] = update_weight(params[l], l, step)
        emit(args.result, {"event": "replay", "step": step,
                           "fingerprint": tree_fingerprint(
                               committed_state(params, loss))})
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--stage-worker", action="store_true")
    p.add_argument("--replay", action="store_true")
    p.add_argument("--stage", type=int, default=0)
    p.add_argument("--layers", default="")
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--shard", default="")
    p.add_argument("--start-step", type=int, default=1)
    p.add_argument("--stages", type=int, default=4)
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--microbatches", type=int, default=MICROBATCHES)
    p.add_argument("--store", default="")
    p.add_argument("--base-key", default="soak/pipeline/ckpt")
    p.add_argument("--result", required=True)
    p.add_argument("--workdir", default="")
    p.add_argument("--stall-after", type=float, default=1.2)
    p.add_argument("--step-timeout", type=float, default=60.0)
    args = p.parse_args()
    if args.stage_worker:
        return run_stage(args)
    if args.replay:
        return run_replay(args)
    if not args.workdir:
        args.workdir = os.path.join(
            os.path.dirname(os.path.abspath(args.result)), "pipe-data")
    return run_driver(args)


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    sys.exit(main())

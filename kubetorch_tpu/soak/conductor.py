"""The chaos conductor: one seeded schedule against one real fleet.

``run_soak`` boots the profile's subprocess fleet (store ring, elastic
trainer, serving gateway), then walks the op-indexed schedule: at each
op index it first delivers every due :class:`~.schedule.FaultEvent`,
then performs ONE client workload op (put/get/rm/ls/generate/lease-tick,
drawn from a second seeded RNG so the op stream is as replayable as the
fault stream), recording the client-visible outcome into the
:class:`~.history.History`. After the last op it SETTLES — partition
down, dead processes revived chaos-free, trainer drained with
``--resume``, scrub driven to convergence, every acked write read back
at quorum, leaks scanned — and runs the invariant checkers over the
complete record.

Everything rides the repo's own resilient client surfaces:
``data_store.commands`` for store ops (ring failover + typed errors),
:class:`~kubetorch_tpu.federation.geo.GeoFrontDoor` for serving ops
(exhausted spill is ALWAYS typed), the real ``LeaseTable`` for the
fencing dance. A raw exception reaching the history is therefore a real
contract breach, not a harness artifact — which is what lets the
typed-errors invariant be an invariant.

On violation, :func:`shrink_violation` replays ddmin subsets of the
event list (same seed, same boot chaos, same op stream) until the
schedule is 1-minimal for the SAME invariant, and writes a replay file
``kt soak replay`` refires.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .. import telemetry
from ..chaos import reset_partition_state
from ..data_store import commands as ds
from ..data_store import netpool, ring
from ..exceptions import StaleLeaseError
from ..federation.lease import LeaseTable
from ..utils.procs import free_port, kill_process_tree, wait_for_port
from .history import History, Violation, check_all, classify_error
from .schedule import FaultEvent, Schedule
from .shrink import ddmin

# env this run mutates and must restore (the conductor runs inside the
# operator's process — a soak must not leave chaos armed in their shell)
_MUTATED_ENV = ("KT_STORE_NODES", "KT_STORE_REPLICATION",
                "KT_STORE_WRITE_QUORUM", "KT_STORE_NODE_TTL_S",
                "KT_DATA_STORE_URL", "KT_CHAOS", "KT_CHAOS_SEED",
                "KT_CHAOS_REGION_HOSTS", "PYTHONPATH",
                "KT_OBS_SPOOL", "KT_OBS_INTERVAL_S")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TRAINER = os.path.join(_REPO_ROOT, "tests", "assets", "fed_trainer.py")
_PIPELINE_TRAINER = os.path.join(_REPO_ROOT, "tests", "assets",
                                 "pipeline_trainer.py")
_FLYWHEEL_TRAINER = os.path.join(_REPO_ROOT, "tests", "assets",
                                 "flywheel_trainer.py")
_FLYWHEEL_SERVICE = "soak-fly"
_FLYWHEEL_REPLICA = "replica-0"


@dataclass
class SoakResult:
    """One run's verdict: the schedule it played, the history it built,
    and the violations the checkers found (empty == green)."""

    schedule: Schedule
    violations: List[Violation]
    ops: int = 0
    events_fired: int = 0
    duration_s: float = 0.0
    history_path: Optional[str] = None
    records: List[Dict] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> Dict[str, Any]:
        return {"ok": self.ok, "seed": self.schedule.seed,
                "profile": self.schedule.profile, "ops": self.ops,
                "events_fired": self.events_fired,
                "duration_s": round(self.duration_s, 2),
                "history": self.history_path,
                "violations": [v.to_dict() for v in self.violations]}


def _clean_child_env() -> Dict[str, str]:
    """Base env for fleet children: the operator's env minus any armed
    chaos (each child gets its OWN arming from the schedule), held to the
    CPU: no soak child may open the accelerator."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k in ("KT_CHAOS", "KT_CHAOS_SEED", "KT_CHAOS_REGION_HOSTS"):
        env.pop(k, None)
    return env


class _Gateway:
    """One sim-region serving gateway subprocess (the front door the
    generate ops hit through the GeoFrontDoor)."""

    def __init__(self, region: str, seed: int, chaos_token: str = ""):
        self.region = region
        self.seed = seed
        self.chaos_token = chaos_token
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.proc: Optional[subprocess.Popen] = None

    def start(self, chaos: bool = True) -> None:
        env = _clean_child_env()
        if chaos and self.chaos_token:
            env["KT_CHAOS"] = self.chaos_token
            env["KT_CHAOS_SEED"] = str(self.seed)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.federation.sim_region",
             "--port", str(self.port), "--region", self.region,
             "--replicas", "2", "--slots", "4"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        if not wait_for_port("127.0.0.1", self.port, timeout=30):
            raise RuntimeError(f"soak gateway {self.region} did not start")

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            kill_process_tree(self.proc.pid)
        self.proc = None

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class _Trainer:
    """The elastic trainer under fire: fed_trainer.py runs against the
    soak's store ring; kills are SIGKILL, resumes re-spawn with
    ``--resume`` appending to the same JSONL ledger."""

    def __init__(self, store: str, base_dir: str, steps: int):
        self.store = store
        self.steps = steps
        self.result = os.path.join(base_dir, "trainer-ledger.jsonl")
        self.base_key = "soak/trainer/ckpt"
        self.proc: Optional[subprocess.Popen] = None

    def start(self, resume: bool) -> None:
        if not os.path.exists(_TRAINER):
            raise RuntimeError(f"trainer asset missing: {_TRAINER}")
        args = [sys.executable, _TRAINER, "--base-key", self.base_key,
                "--store", self.store, "--steps", str(self.steps),
                "--result", self.result, "--step-sleep", "0.05"]
        if resume:
            args.append("--resume")
        self.proc = subprocess.Popen(args, env=_clean_child_env(),
                                     stdout=subprocess.DEVNULL,
                                     stderr=subprocess.DEVNULL)

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait(timeout=30)

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def ledger(self) -> List[Dict]:
        out: List[Dict] = []
        if os.path.exists(self.result):
            with open(self.result) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            out.append({"corrupt_line": line[:120]})
        return out


class _PipelineTrainer:
    """The 4-stage pipelined trainer under fire (ISSUE 17):
    ``pipeline_trainer.py`` drives real stage subprocesses over the soak's
    store ring. The schedule's ``stage:N`` boot-chaos token rides
    ``KT_CHAOS`` + ``KT_CHAOS_STAGE`` into the driver's environment, so
    exactly one stage self-faults mid-step (kill or stall) and the
    driver's embedded supervisor must re-group. Settle waits the driver
    out, then runs the unpartitioned ``--replay`` pass whose fingerprints
    the pipeline-progress invariant bit-compares against the committed
    steps."""

    def __init__(self, store: str, base_dir: str, steps: int, seed: int,
                 boot_chaos: Dict[str, str]):
        self.store = store
        self.steps = steps
        self.seed = seed
        self.result = os.path.join(base_dir, "pipeline-ledger.jsonl")
        self.replay_result = os.path.join(base_dir,
                                          "pipeline-replay.jsonl")
        self.stage_token = ""
        self.stage_index = ""
        for target, tok in sorted(boot_chaos.items()):
            if target.startswith("stage:"):
                self.stage_index = target.split(":")[1]
                self.stage_token = tok
        self.proc: Optional[subprocess.Popen] = None

    def start(self) -> None:
        if not os.path.exists(_PIPELINE_TRAINER):
            raise RuntimeError(
                f"pipeline trainer asset missing: {_PIPELINE_TRAINER}")
        env = _clean_child_env()
        if self.stage_token:
            env["KT_CHAOS"] = self.stage_token
            env["KT_CHAOS_STAGE"] = self.stage_index
            env["KT_CHAOS_SEED"] = str(self.seed)
        self.proc = subprocess.Popen(
            [sys.executable, _PIPELINE_TRAINER, "--store", self.store,
             "--steps", str(self.steps), "--stages", "4",
             "--result", self.result],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def replay(self, timeout: float) -> None:
        """The bit-identity oracle: recompute the same steps in ONE
        process with no pipeline partitioning, chaos-free."""
        try:
            subprocess.run(
                [sys.executable, _PIPELINE_TRAINER, "--replay",
                 "--steps", str(self.steps), "--stages", "4",
                 "--result", self.replay_result],
                env=_clean_child_env(), timeout=timeout,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                check=False)
        except subprocess.TimeoutExpired:
            pass

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            kill_process_tree(self.proc.pid)
        self.proc = None

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def ledger(self) -> List[Dict]:
        out: List[Dict] = []
        for path in (self.result, self.replay_result):
            if not os.path.exists(path):
                continue
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            out.append({"corrupt_line": line[:120]})
        return out


class _FlywheelTrainer:
    """The harvest trainer under fire (ISSUE 19): flywheel_trainer.py
    consumes the soak's feedback ledger through the real cursor +
    Checkpointer. The schedule's ``flywheel-trainer`` boot-chaos token
    (``kill-flywheel:SIG@N``) rides ``KT_CHAOS`` into the FIRST spawn
    only — the ``resume-flywheel`` event and the settle pass run clean,
    the way recovery always runs clean in this conductor."""

    def __init__(self, store: str, base_dir: str, seed: int,
                 chaos_token: str = ""):
        self.store = store
        self.seed = seed
        self.chaos_token = chaos_token
        self.result = os.path.join(base_dir, "flywheel-ledger.jsonl")
        self.base_key = "soak/flywheel/ckpt"
        self.proc: Optional[subprocess.Popen] = None

    def start(self, resume: bool, chaos: bool = False,
              idle_polls: int = 400) -> None:
        if not os.path.exists(_FLYWHEEL_TRAINER):
            raise RuntimeError(
                f"flywheel trainer asset missing: {_FLYWHEEL_TRAINER}")
        env = _clean_child_env()
        if chaos and self.chaos_token:
            env["KT_CHAOS"] = self.chaos_token
            env["KT_CHAOS_SEED"] = str(self.seed)
        args = [sys.executable, _FLYWHEEL_TRAINER,
                "--service", _FLYWHEEL_SERVICE,
                "--replicas", _FLYWHEEL_REPLICA,
                "--store", self.store, "--base-key", self.base_key,
                "--result", self.result, "--poll-sleep", "0.1",
                "--idle-polls", str(idle_polls)]
        if resume:
            args.append("--resume")
        self.proc = subprocess.Popen(args, env=env,
                                     stdout=subprocess.DEVNULL,
                                     stderr=subprocess.DEVNULL)

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait(timeout=30)

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def ledger(self) -> List[Dict]:
        out: List[Dict] = []
        if os.path.exists(self.result):
            with open(self.result) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            out.append({"corrupt_line": line[:120]})
        return out


def _import_flywheel_ledger(history: History,
                            ftrainer: Optional["_FlywheelTrainer"]) -> None:
    """Trainer JSONL → history records: checkpoint lines feed the commits
    invariant (kind=trainer), cursor/consume lines feed the
    flywheel-ledger invariant (kind=flywheel)."""
    for rec in ftrainer.ledger() if ftrainer is not None else []:
        if "committed" in rec:
            history.record("trainer", event="committed",
                           step=rec["committed"],
                           fingerprint=rec.get("fingerprint"))
        elif "restored" in rec:
            history.record("trainer", event="restored",
                           step=rec["restored"],
                           fingerprint=rec.get("fingerprint"))
        elif "consumed" in rec:
            history.record("flywheel", event="consumed",
                           hashes=rec["consumed"], step=rec.get("step"))
        elif "cursor_committed" in rec:
            history.record("flywheel", event="cursor-committed",
                           step=rec["cursor_committed"])
        elif "cursor_restored" in rec:
            history.record("flywheel", event="cursor-restored",
                           step=rec["cursor_restored"])
        elif "dying_at_op" in rec:
            history.record("flywheel", event="dying",
                           op=rec["dying_at_op"])
        elif "done" in rec or "drained" in rec:
            history.record("trainer", event="done",
                           step=rec.get("final_step", rec.get("drained")),
                           fingerprint=rec.get("fingerprint"))


def _promote_drill(history: History, store_url: str) -> None:
    """Settle-phase gated-promotion closure (ISSUE 19 acceptance): promote
    a good delta through the real publish→canary path on the soak's store
    ring, then drive the deliberately-bad delta with the break-glass env
    blinding the eval gate AND a canary that dies mid-bake (a dead canary
    yields no healthy evidence — the verdict is ``regressed``). The bad
    delta must roll back with the fleet fingerprint unchanged; the
    flywheel-ledger invariant's gate clause certifies it from the
    history."""
    import numpy as np

    from ..flywheel.promoter import Promoter
    from ..serve import rollout as ro

    class _Router:
        verdict = "ok"

        def set_canary(self, replica, fraction=0.1):
            pass

        def clear_canary(self):
            pass

        def canary_verdict(self, **kw):
            return self.verdict

    router = _Router()
    promoter = Promoter(
        _FLYWHEEL_SERVICE, router, store_url=store_url,
        eval_fn=lambda t: float(np.abs(t["w"]).mean()),
        bake_s=0.5, min_requests=1, poll_s=0.05)
    good = {"w": np.full(8, 1.0, dtype=np.float32)}
    v1 = promoter.promote(good, step=1)
    history.record("flywheel", event="gate", verdict=v1, bad=False)
    # second good delta so a previous manifest exists and the bad delta
    # takes the canary path, not the first-ever fast path
    v2 = promoter.promote(good, step=2)
    history.record("flywheel", event="gate", verdict=v2, bad=False)
    before = ro.read_manifest(_FLYWHEEL_SERVICE, store_url=store_url)
    router.verdict = "regressed"      # canary SIGKILLed mid-bake: no
    os.environ["KT_FLYWHEEL_BREAK"] = "promote-bad-delta"
    try:
        bad = {"w": np.full(8, 100.0, dtype=np.float32)}
        v3 = promoter.promote(bad, step=3)
    finally:
        os.environ.pop("KT_FLYWHEEL_BREAK", None)
    after = ro.read_manifest(_FLYWHEEL_SERVICE, store_url=store_url)
    unchanged = bool(before and after
                     and after.get("fingerprint") == before.get(
                         "fingerprint"))
    if not unchanged:
        v3 = "promoted" if v3 == "promoted" else f"{v3}-but-fleet-moved"
    history.record("flywheel", event="gate", verdict=v3, bad=True)


def _import_pipeline_ledger(history: History,
                            ptrainer: Optional["_PipelineTrainer"]) -> None:
    for rec in ptrainer.ledger() if ptrainer is not None else []:
        event = rec.get("event")
        if not event:
            continue
        history.record("pipeline", **{k: v for k, v in rec.items()
                                      if k != "kind"})


def _record_op(history: History, op: str, key: str, fn) -> Any:
    """Run one client op, record its client-visible outcome (typed or
    raw), never let the exception escape the soak loop."""
    m = telemetry.soak_metrics()
    try:
        result = fn()
    except BaseException as e:  # noqa: BLE001 — classifying is the point
        if isinstance(e, (KeyboardInterrupt, SystemExit)):
            raise
        name, typed = classify_error(e)
        history.record("op", op=op, key=key, ok=False, error=name,
                       typed=typed, detail=str(e)[:200])
        m["ops"].inc(op=op, outcome="typed-error" if typed else "raw-error")
        return None
    history.record("op", op=op, key=key, ok=True,
                   acked=(op == "put"))
    m["ops"].inc(op=op, outcome="ok")
    return result


def _import_ledger(history: History, trainer: Optional[_Trainer]) -> None:
    if trainer is None:
        return
    for rec in trainer.ledger():
        if "committed" in rec:
            history.record("trainer", event="committed",
                           step=rec["committed"],
                           fingerprint=rec.get("fingerprint"))
        elif "restored" in rec:
            history.record("trainer", event="restored",
                           step=rec["restored"],
                           fingerprint=rec.get("fingerprint"))
        elif "dying_at_step" in rec:
            history.record("trainer", event="dying",
                           step=rec["dying_at_step"])
        elif "done" in rec:
            history.record("trainer", event="done",
                           step=rec.get("final_step"),
                           fingerprint=rec.get("fingerprint"))


def _scan_leaks(store_roots: List[str]) -> Dict[str, List[str]]:
    shm = sorted(os.path.basename(p)
                 for p in glob.glob("/dev/shm/kt-*")
                 if os.path.exists(p))
    tmp: List[str] = []
    for root in store_roots:
        for p in glob.glob(os.path.join(root, "**", "*.tmp"),
                           recursive=True):
            tmp.append(os.path.relpath(p, root))
    return {"shm": shm, "tmp": sorted(tmp)}


def _scan_spools(spool_root: str, kills: int) -> Dict[str, Any]:
    """Flight-recorder census after teardown (ISSUE 20): hash-verify
    every child's spool. Run AFTER the fleet is dead, so each spool is
    final — a surviving writer would race the read."""
    from ..obs import read_spool, spool_dirs, spool_identity
    from ..obs.blackbox import pid_alive

    spools: List[Dict[str, Any]] = []
    for d in spool_dirs(spool_root):
        name, pid = spool_identity(d)
        loaded = read_spool(d)
        spools.append({
            "dir": str(d), "name": name, "pid": pid,
            "alive": bool(pid is not None and pid_alive(pid)),
            "records": len(loaded["records"]),
            "errors": loaded["errors"],
        })
    return {"armed": True, "kills": kills, "spools": spools}


def run_soak(sched: Schedule, base_dir: str,
             op_interval_s: float = 0.25,
             settle_timeout_s: float = 60.0,
             history_path: Optional[str] = None,
             events_override: Optional[List[FaultEvent]] = None,
             log=lambda msg: None) -> SoakResult:
    """Play one schedule against a real fleet and return the verdict.

    ``events_override`` substitutes the conductor-delivered event list
    (seed, boot chaos, and the op stream stay fixed) — the shrinker's
    replay knob. ``log`` gets human progress lines (the CLI wires it to
    stderr; tests leave it silent)."""
    import random

    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)
    from tests.assets.store_fleet import \
        SubprocessStoreFleet  # test-asset reuse is the point (ISSUE 15)

    events = sorted(events_override if events_override is not None
                    else sched.events,
                    key=lambda e: (e.at_op, e.action, e.target))
    history = History(history_path)
    ops_rng = random.Random(f"{sched.seed}-ops")
    m = telemetry.soak_metrics()
    started = time.monotonic()

    has_store = sched.store_nodes > 0
    has_trainer = sched.profile in ("train", "federation", "all")
    has_gateway = sched.profile in ("serve", "federation", "all")
    has_regions = sched.profile in ("federation", "all")
    has_pipeline = sched.profile == "pipeline"
    has_flywheel = sched.profile == "flywheel"

    saved_env = {k: os.environ.get(k) for k in _MUTATED_ENV}
    # fleet/gateway/trainer children spawn with `python -m kubetorch_tpu...`
    # and inherit os.environ at spawn time: make the package importable
    # regardless of the conductor's cwd
    pp = os.environ.get("PYTHONPATH", "")
    if _REPO_ROOT not in pp.split(os.pathsep):
        os.environ["PYTHONPATH"] = (_REPO_ROOT + os.pathsep + pp if pp
                                    else _REPO_ROOT)
    # arm the flight recorder in every fleet child (ISSUE 20): each
    # subprocess spools delta-encoded telemetry under the run dir at a
    # fast cadence, so a SIGKILLed store node/rank leaves a black box
    # the post-teardown census can hash-verify (check_blackbox)
    spool_root = os.path.join(base_dir, "obs-spool")
    os.environ["KT_OBS_SPOOL"] = spool_root
    os.environ["KT_OBS_INTERVAL_S"] = "0.05"
    from ..config import config
    cfg = config()
    saved_cfg_url = cfg.data_store_url
    fleet = None
    gateway: Optional[_Gateway] = None
    trainer: Optional[_Trainer] = None
    ptrainer: Optional[_PipelineTrainer] = None
    ftrainer: Optional[_FlywheelTrainer] = None
    fly_ledger = None  # conductor-side appender (the "serving replica")
    door = None
    lease: Optional[LeaseTable] = None
    holder: Dict[str, Any] = {}
    expected: Dict[str, Dict] = {}
    key_space = max(8, sched.n_ops // 4)
    fired = 0

    def fire(ev: FaultEvent) -> None:
        nonlocal fired
        fired += 1
        m["events"].inc(action=ev.action)
        history.record("event", action=ev.action, target=ev.target,
                       at_op=ev.at_op, token=ev.token)
        log(f"  op {ev.at_op}: {ev.action} {ev.target}")
        if ev.action == "kill-node" and fleet is not None:
            fleet.kill_node(int(ev.target.split(":")[1]))
        elif ev.action == "restart-node" and fleet is not None:
            i = int(ev.target.split(":")[1])
            # recovery runs clean: a restart never re-arms boot chaos
            fleet.chaos.pop(i, None)
            proc = fleet.procs[i]
            if proc is None or proc.poll() is not None:
                fleet.start_node(i)
            # else: a boot-armed self-kill hasn't consumed its op index
            # yet — leave the node alone; settle revives whatever died
            # after its revival slot passed
        elif ev.action == "kill-trainer" and trainer is not None:
            trainer.kill()
        elif ev.action == "resume-trainer" and trainer is not None:
            if not trainer.alive():
                trainer.start(resume=True)
        elif ev.action == "resume-flywheel" and ftrainer is not None:
            # the boot-chaos kill-flywheel token already fired (or never
            # will); recovery runs clean and must adopt the committed
            # cursor state — the flywheel-ledger invariant checks it
            if not ftrainer.alive():
                ftrainer.start(resume=True, chaos=False)
        elif ev.action == "kill-gateway" and gateway is not None:
            gateway.kill()
        elif ev.action == "restart-gateway" and gateway is not None:
            gateway.start(chaos=False)
        elif ev.action == "scale-to-zero" and gateway is not None:
            # deliberate drain, delivered the way a scale-down lands on a
            # pod: SIGKILL, no goodbye. The workload keeps firing into
            # the zero-replica window — typed errors only, per invariant.
            gateway.kill()
        elif ev.action == "cold-burst" and gateway is not None:
            # burst back under load; recovery runs clean (no chaos
            # re-arm). The end-of-run leak scan owns the "no shm/tmp
            # segments left behind" half of this episode's contract.
            gateway.start(chaos=False)
        elif ev.action == "partition-start":
            os.environ["KT_CHAOS"] = ev.token
            os.environ["KT_CHAOS_SEED"] = str(sched.seed)
            # the store ring is region-local: the partition black-holes
            # the cross-region front door, never the local data plane
            locals_ = list(fleet.urls) if fleet is not None else []
            os.environ["KT_CHAOS_REGION_HOSTS"] = ",".join(locals_)
            reset_partition_state()
        elif ev.action == "partition-stop":
            os.environ.pop("KT_CHAOS", None)
            reset_partition_state()
        elif ev.action == "lease-failover" and lease is not None:
            old = dict(holder)
            epoch = lease.grant(ev.target, "region-b")
            history.record("lease", event="grant", workload=ev.target,
                           region="region-b", epoch=epoch)
            # the fenced region's next heartbeat must die typed — and the
            # conductor records the stop BEFORE region-b starts, which is
            # exactly the ordering the fencing checker certifies
            if old:
                try:
                    lease.validate(ev.target, old["region"], old["epoch"])
                except StaleLeaseError:
                    pass
                history.record("placement", event="stop",
                               workload=ev.target, region=old["region"],
                               epoch=old["epoch"])
            history.record("placement", event="start", workload=ev.target,
                           region="region-b", epoch=epoch)
            holder.update({"workload": ev.target, "region": "region-b",
                           "epoch": epoch})

    def one_op(op_i: int) -> None:
        choices: List[str] = []
        if has_store:
            choices += ["put"] * 4 + ["get"] * 3 + ["ls", "rm"]
        if has_gateway:
            choices += ["generate"] * 2
        if has_regions:
            choices += ["lease-tick"]
        if has_flywheel:
            choices += ["fly-append"] * 3
        op = choices[ops_rng.randrange(len(choices))]
        key = f"soak/k{ops_rng.randrange(key_space)}"
        if op == "put":
            value = {"op": op_i, "nonce": ops_rng.randrange(1 << 30)}
            if _record_op(history, "put", key,
                          lambda: ds.put_json(key, value)) is not None:
                expected[key] = value
        elif op == "get":
            _record_op(history, "get", key,
                       lambda: ds.get_json(key, default=None))
        elif op == "ls":
            _record_op(history, "ls", "soak/", lambda: ds.ls("soak/"))
        elif op == "rm":
            if _record_op(history, "rm", key,
                          lambda: ds.rm(key)) is not None:
                expected.pop(key, None)
        elif op == "generate":
            import asyncio
            payload = {"prompt_len": 8 + ops_rng.randrange(16),
                       "new_tokens": 1 + ops_rng.randrange(4)}
            _record_op(history, "generate", "gateway",
                       lambda: asyncio.run(door.dispatch(payload, {})))
        elif op == "fly-append" and fly_ledger is not None:
            # live-traffic feedback: the ack the client sees is the
            # at-least-once anchor — only records the conductor saw
            # acked are owed back by the settle-read
            payload = {"op": op_i, "prompt": ops_rng.randrange(1 << 30),
                       "reward": round(ops_rng.random(), 6)}
            hashes = _record_op(history, "fly-append", _FLYWHEEL_REPLICA,
                                lambda: fly_ledger.append([payload]))
            if hashes:
                history.record("flywheel", event="acked", hashes=hashes)
        elif op == "lease-tick" and holder:
            def _tick():
                lease.validate(holder["workload"], holder["region"],
                               holder["epoch"])
                history.record("placement", event="confirmed",
                               workload=holder["workload"],
                               region=holder["region"],
                               epoch=holder["epoch"])
            _record_op(history, "lease-tick", holder["workload"], _tick)

    try:
        # --- boot -----------------------------------------------------------
        if has_store:
            chaos_by_node = {
                int(t.split(":")[1]): tok
                for t, tok in sched.boot_chaos.items()
                if t.startswith("store:")}
            fleet = SubprocessStoreFleet(
                os.path.join(base_dir, "store"), n=sched.store_nodes,
                replication=2, write_quorum=2, node_ttl_s=1.0,
                chaos=chaos_by_node,
                extra_env={"KT_CHAOS_SEED": str(sched.seed)})
            fleet.__enter__()
            os.environ.update(fleet.client_env())
            # commands.* resolve their origin from here; ring failover
            # walks the membership list when the seed node is down. The
            # cached config layer outranks the env var, so set both.
            os.environ["KT_DATA_STORE_URL"] = fleet.urls[0]
            cfg.data_store_url = fleet.urls[0]
            ring.reset_rings()
            netpool.reset_breakers()
        os.environ.pop("KT_CHAOS", None)
        reset_partition_state()
        if has_gateway:
            gateway = _Gateway("region-a", sched.seed,
                               sched.boot_chaos.get("gateway:0", ""))
            gateway.start()
            from ..federation.geo import GeoFrontDoor, HttpRegionTarget
            door = GeoFrontDoor(
                [HttpRegionTarget("region-a", gateway.url)],
                local_region="region-a")
        if has_trainer and fleet is not None:
            trainer = _Trainer(",".join(fleet.urls), base_dir,
                               steps=max(6, sched.n_ops // 3))
            trainer.start(resume=False)
        if has_pipeline and fleet is not None:
            # the driver supervises its own stage gang and re-groups
            # in-process; the conductor only arms the stage-scoped chaos
            # and, at settle, runs the unpartitioned replay oracle
            ptrainer = _PipelineTrainer(",".join(fleet.urls), base_dir,
                                        steps=max(6, sched.n_ops // 2),
                                        seed=sched.seed,
                                        boot_chaos=sched.boot_chaos)
            ptrainer.start()
        if has_flywheel and fleet is not None:
            from ..flywheel.ledger import FeedbackLedger
            fly_ledger = FeedbackLedger(_FLYWHEEL_SERVICE,
                                        _FLYWHEEL_REPLICA,
                                        store_url=fleet.urls[0])
            ftrainer = _FlywheelTrainer(
                ",".join(fleet.urls), base_dir, seed=sched.seed,
                chaos_token=sched.boot_chaos.get("flywheel-trainer", ""))
            ftrainer.start(resume=False, chaos=True)
        if has_regions:
            lease = LeaseTable()
            epoch = lease.grant("job-0", "region-a")
            history.record("lease", event="grant", workload="job-0",
                           region="region-a", epoch=epoch)
            history.record("placement", event="start", workload="job-0",
                           region="region-a", epoch=epoch)
            holder.update({"workload": "job-0", "region": "region-a",
                           "epoch": epoch})

        # --- the conducted run ---------------------------------------------
        log(f"soak: {sched.profile} seed={sched.seed} ops={sched.n_ops} "
            f"events={len(events)} boot_chaos={sched.boot_chaos}")
        pending = list(events)
        for op_i in range(sched.n_ops):
            while pending and pending[0].at_op <= op_i:
                fire(pending.pop(0))
            one_op(op_i)
            time.sleep(op_interval_s)
        for ev in pending:  # events past the horizon still fire once
            fire(ev)

        # --- settle ---------------------------------------------------------
        log("soak: settling")
        os.environ.pop("KT_CHAOS", None)
        reset_partition_state()
        if fleet is not None:
            fleet.chaos.clear()
            for i in range(fleet.n):
                proc = fleet.procs[i]
                if proc is None or proc.poll() is not None:
                    fleet.start_node(i)
        if trainer is not None:
            if not trainer.alive():
                trainer.start(resume=True)
            try:
                trainer.proc.wait(timeout=settle_timeout_s)
            except subprocess.TimeoutExpired:
                trainer.kill()
        if gateway is not None and not gateway.alive():
            gateway.start(chaos=False)

        if fleet is not None:
            deadline = time.monotonic() + settle_timeout_s
            status: Dict[str, Any] = {}
            while time.monotonic() < deadline:
                try:
                    for u in fleet.urls:
                        netpool.request("POST", f"{u}/scrub/run",
                                        timeout=60)
                    statuses = [netpool.request(
                        "GET", f"{u}/scrub/status", timeout=10).json()
                        for u in fleet.urls]
                    status = {
                        "under_replicated": sum(
                            s.get("under_replicated", 0)
                            for s in statuses),
                        # a member still in any peer's down-book means the
                        # ring has not re-converged on full membership
                        "nodes_down": max(
                            len((s.get("ring") or {}).get("down", {}))
                            for s in statuses),
                    }
                    if not status["under_replicated"] \
                            and not status["nodes_down"]:
                        break
                except Exception:  # noqa: BLE001 — converging, keep driving
                    status = {"under_replicated": -1, "nodes_down": -1}
                time.sleep(0.25)
            history.record("ring-status", **(status or
                                             {"under_replicated": -1,
                                              "nodes_down": -1}))

            for key in sorted(expected):
                got = None
                err = ""
                for _ in range(3):
                    try:
                        got = ds.get_json(key, quorum=True, default=None)
                        err = ""
                        if got is not None:
                            break
                    except Exception as e:  # noqa: BLE001
                        err = classify_error(e)[0]
                    time.sleep(0.2)
                history.record("verify", key=key, ok=got is not None,
                               match=(got == expected[key]), error=err)
        if ptrainer is not None:
            try:
                ptrainer.proc.wait(timeout=settle_timeout_s)
            except subprocess.TimeoutExpired:
                ptrainer.kill()
            ptrainer.replay(timeout=settle_timeout_s)
        if ftrainer is not None:
            # drain the live run over SIGTERM (the PR 6 contract), then a
            # clean --resume sweep consumes whatever the chaos kill
            # orphaned; only then does the settle-read take its census
            if ftrainer.alive():
                ftrainer.proc.send_signal(signal.SIGTERM)
                try:
                    ftrainer.proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    ftrainer.kill()
            ftrainer.start(resume=True, chaos=False, idle_polls=5)
            try:
                ftrainer.proc.wait(timeout=settle_timeout_s)
            except subprocess.TimeoutExpired:
                ftrainer.kill()
            from ..flywheel.ledger import read_all_hashes
            settle_hashes: List[str] = []
            if fleet is not None:
                try:
                    settle_hashes = read_all_hashes(
                        _FLYWHEEL_SERVICE, [_FLYWHEEL_REPLICA],
                        store_url=fleet.urls[0])
                except Exception as e:  # noqa: BLE001 — census best-effort
                    history.record("flywheel", event="settle-read-error",
                                   error=classify_error(e)[0])
                else:
                    history.record("flywheel", event="settle-read",
                                   hashes=settle_hashes)
            if fleet is not None:
                try:
                    _promote_drill(history, fleet.urls[0])
                except Exception as e:  # noqa: BLE001 — verdict, not crash
                    history.record(
                        "flywheel", event="gate",
                        verdict=f"drill-error:{type(e).__name__}",
                        bad=True)
        if holder:
            history.record("placement", event="stop",
                           workload=holder["workload"],
                           region=holder["region"],
                           epoch=holder["epoch"])
        _import_ledger(history, trainer)
        _import_pipeline_ledger(history, ptrainer)
        _import_flywheel_ledger(history, ftrainer)
    finally:
        if trainer is not None:
            trainer.kill()
        if ptrainer is not None:
            ptrainer.kill()
        if ftrainer is not None:
            ftrainer.kill()
        if gateway is not None:
            gateway.kill()
        roots = list(fleet.roots) if fleet is not None else []
        if fleet is not None:
            fleet.__exit__()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        cfg.data_store_url = saved_cfg_url
        ring.reset_rings()
        reset_partition_state()

    time.sleep(0.2)  # give SIGKILLed children a beat to release segments
    history.record("leak-scan", **_scan_leaks(roots))
    kill_events = sum(
        1 for r in history.records()
        if r.get("kind") == "event"
        and (str(r.get("action", "")).startswith("kill")
             or r.get("action") == "scale-to-zero"
             or str(r.get("verb", "")).startswith("kill")))
    history.record("blackbox", **_scan_spools(spool_root, kill_events))

    violations = check_all(history.records())
    for v in violations:
        m["violations"].inc(invariant=v.invariant)
    m["runs"].inc(outcome="violation" if violations else "ok")
    return SoakResult(schedule=sched, violations=violations,
                      ops=sched.n_ops, events_fired=fired,
                      duration_s=time.monotonic() - started,
                      history_path=history_path,
                      records=history.records())


# ---------------------------------------------------------------------------
# Shrinking a violating run to a minimal repro
# ---------------------------------------------------------------------------


def shrink_violation(sched: Schedule, base_dir: str,
                     invariant: str,
                     op_interval_s: float = 0.25,
                     settle_timeout_s: float = 60.0,
                     max_tests: int = 24,
                     log=lambda msg: None) -> Schedule:
    """ddmin the event list down to a 1-minimal schedule that still
    violates ``invariant``. Each predicate call is a full replay in a
    fresh directory (same seed → same boot chaos and op stream), so
    ``max_tests`` bounds wall-clock, not correctness: on cap the best
    reduction so far is returned, still a valid repro."""
    attempt = [0]

    def violates(subset: List[FaultEvent]) -> bool:
        attempt[0] += 1
        d = os.path.join(base_dir, f"shrink-{attempt[0]:03d}")
        os.makedirs(d, exist_ok=True)
        log(f"shrink: replay {attempt[0]} with {len(subset)} event(s)")
        res = run_soak(sched, d, op_interval_s=op_interval_s,
                       settle_timeout_s=settle_timeout_s,
                       events_override=list(subset))
        return any(v.invariant == invariant for v in res.violations)

    minimal = ddmin(list(sched.events), violates, max_tests=max_tests)
    out = Schedule(seed=sched.seed, profile=sched.profile,
                   n_ops=sched.n_ops, store_nodes=sched.store_nodes,
                   boot_chaos=dict(sched.boot_chaos),
                   events=sorted(minimal,
                                 key=lambda e: (e.at_op, e.action,
                                                e.target)))
    return out


def write_replay(sched: Schedule, path: str,
                 violations: List[Violation]) -> None:
    """Persist a replay file: the (possibly shrunk) schedule plus the
    violations it reproduces — the artifact ``kt soak replay`` refires."""
    body = sched.to_dict()
    body["violations"] = [v.to_dict() for v in violations]
    with open(path, "w") as f:
        json.dump(body, f, indent=2, sort_keys=True)
        f.write("\n")


def load_replay(path: str) -> Schedule:
    with open(path) as f:
        body = json.load(f)
    try:
        return Schedule.from_dict(body)
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(
            f"{path} is not a soak replay file (write one with "
            f"`kt soak run` on a violating seed): {e}") from e

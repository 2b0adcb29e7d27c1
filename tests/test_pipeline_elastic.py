"""Elastic pipeline parallelism (ISSUE 17): stage membership, Ada-Grouper
re-grouping, the epoch fence, stage chaos verbs, scheduler gang admission,
and the soak invariant — ``make test-pipeline``.

The acceptance scenario rides REAL processes: a 4-stage pipelined numpy
trainer (``tests/assets/pipeline_trainer.py``) loses one stage to SIGKILL
mid-step, the survivors absorb its layer shard and keep committing, a
zombie confirm bounces off the epoch fence, and every committed step's
``tree_fingerprint`` bit-matches an unpartitioned replay.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.level("minimal"), pytest.mark.pipeline]

from kubetorch_tpu import chaos, telemetry
from kubetorch_tpu.exceptions import (StaleStageEpochError,
                                      package_exception,
                                      rehydrate_exception)
from kubetorch_tpu.parallel.pipeline_elastic import (
    _MAX_MICROBATCH_GROWTH, REGROUP_CAUSES, ElasticPipeline,
    PipelineMembership, StageAssignment, _derive_microbatches)

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


def _pipe(n_layers=8, n_stages=4, **kw):
    return ElasticPipeline(n_layers, n_stages, job="t", **kw)


def _layers(pipe):
    return [list(a.layers) for a in pipe.membership.assignments]


# ---------------------------------------------------------------------------
# membership math
# ---------------------------------------------------------------------------


def test_membership_validation():
    with pytest.raises(ValueError, match="no layers"):
        StageAssignment(0, ())
    with pytest.raises(ValueError, match="not contiguous"):
        StageAssignment(0, (0, 2))
    with pytest.raises(ValueError, match="width"):
        StageAssignment(0, (0,), width=0)
    with pytest.raises(ValueError, match="carries stage"):
        PipelineMembership(0, (StageAssignment(1, (0,)),), 1)
    with pytest.raises(ValueError, match="tile"):
        PipelineMembership(0, (StageAssignment(0, (0,)),
                               StageAssignment(1, (2,))), 1)


def test_initial_split_even_and_uneven():
    assert _layers(_pipe(8, 4)) == [[0, 1], [2, 3], [4, 5], [6, 7]]
    # remainder layers go to the EARLY stages (they also hold the embed
    # end of the model in the llama placement)
    assert _layers(_pipe(9, 4)) == [[0, 1, 2], [3, 4], [5, 6], [7, 8]]
    with pytest.raises(ValueError, match="n_layers"):
        _pipe(2, 4)


def test_schedule_derived_from_membership():
    m = _pipe(8, 4, n_microbatches=4).membership
    sched = m.schedule()
    assert len(sched) == 4 + 4 - 1                       # M + P - 1 ticks
    assert sum(len(tick) for tick in sched) == 4 * 4     # M*P real slots
    assert sched[0] == [(0, 0)]
    assert sched[3] == [(0, 3), (1, 2), (2, 1), (3, 0)]  # full tick
    assert sched[-1] == [(3, 3)]
    # bubble fraction matches the schedule's empty slots
    slots = len(sched) * m.n_stages
    assert m.bubble_fraction == pytest.approx(1 - (4 * 4) / slots)


def test_slowdown_and_bubble_nonuniform():
    uniform = PipelineMembership(
        0, (StageAssignment(0, (0,), 2), StageAssignment(1, (1,), 2)), 2)
    assert uniform.slowdown == 1.0
    narrow = PipelineMembership(
        0, (StageAssignment(0, (0,), 2), StageAssignment(1, (1,), 1)), 2)
    assert narrow.slowdown == 2.0
    assert narrow.bubble_fraction == pytest.approx(1 - 2 / (3 * 2))
    assert narrow.bubble_fraction > uniform.bubble_fraction


def test_layer_owner():
    m = _pipe(8, 4).membership
    assert [m.layer_owner(l) for l in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    with pytest.raises(ValueError, match="not in any stage"):
        m.layer_owner(8)


def test_derive_microbatches_grows_to_budget_and_caps():
    # uniform widths, bubble budget at the canonical value: M unchanged
    assert _derive_microbatches(4, 3, 1.0, 2 / 6) == 4
    # 2x slowdown: the asymptote 1 - 1/2 = 0.5 is above any budget < 0.5,
    # so M grows to the cap and stops
    assert _derive_microbatches(4, 4, 2.0, 0.4) == 4 * _MAX_MICROBATCH_GROWTH
    # modest budget tightening grows M a little, not to the cap
    m = _derive_microbatches(4, 4, 1.0, 0.3)
    assert 4 <= m < 16 and 1 - m / (m + 3) <= 0.3 + 1e-9


# ---------------------------------------------------------------------------
# re-grouping
# ---------------------------------------------------------------------------


def test_regroup_absorb_middle_stage():
    pipe = _pipe(8, 4, n_microbatches=4)
    old_bubble = pipe.membership.bubble_fraction
    new = pipe.regroup(1, "Killed")
    # front half of the lost shard to the previous stage, back half to
    # the next; stages renumbered
    assert _layers(pipe) == [[0, 1, 2], [3, 4, 5], [6, 7]]
    assert new.epoch == 1 and pipe.epoch == 1
    assert new.n_stages == 3
    # shorter pipe at the same M: bubble can only improve
    assert new.bubble_fraction <= old_bubble + 1e-9
    ev = pipe.regroups[-1]
    assert ev["cause"] == "Killed" and ev["mode"] == "absorb"
    assert ev["lost_stage"] == 1 and ev["n_stages"] == 3


def test_regroup_absorb_edge_stages():
    pipe = _pipe(8, 4)
    pipe.regroup(0, "Crashed")          # stage 0: all layers to the next
    assert _layers(pipe) == [[0, 1, 2, 3], [4, 5], [6, 7]]
    pipe2 = _pipe(8, 4)
    pipe2.regroup(3, "Preempted")       # last stage: all to the previous
    assert _layers(pipe2) == [[0, 1], [2, 3], [4, 5, 6, 7]]


def test_regroup_narrow_keeps_stages_and_rederives_microbatches():
    pipe = _pipe(8, 4, n_microbatches=4, stage_width=2)
    new = pipe.regroup(2, "Slow", slot_width=1)
    assert new.n_stages == 4 and new.epoch == 1
    assert [a.width for a in new.assignments] == [2, 2, 1, 2]
    assert new.slowdown == 2.0
    # M re-derived against the pace factor: grows toward the budget
    assert new.n_microbatches > 4
    assert pipe.regroups[-1]["mode"] == "narrow"


def test_regroup_validation_and_budget():
    pipe = _pipe(8, 4)
    with pytest.raises(ValueError, match="unknown regroup cause"):
        pipe.regroup(1, "Gremlins")
    with pytest.raises(ValueError, match="lost_stage"):
        pipe.regroup(7, "Killed")
    assert "Slow" in REGROUP_CAUSES and "Preempted" in REGROUP_CAUSES

    from kubetorch_tpu.serving.elastic import ElasticPolicy
    tight = _pipe(8, 4, policy=ElasticPolicy(max_resumes=1))
    tight.regroup(1, "Killed")
    with pytest.raises(RuntimeError, match="budget exhausted"):
        tight.regroup(1, "Killed")

    last = _pipe(2, 1)
    with pytest.raises(RuntimeError, match="only stage"):
        last.regroup(0, "Killed")


def test_on_regroup_hook_and_state_dict():
    seen = []
    pipe = ElasticPipeline(8, 4, job="t",
                           on_regroup=lambda m, ev: seen.append((m, ev)))
    pipe.regroup(1, "Evicted")
    assert len(seen) == 1 and seen[0][0].epoch == 1
    state = pipe.state_dict()
    assert state["job"] == "t"
    assert state["membership"]["epoch"] == 1
    assert state["regroups"][-1]["cause"] == "Evicted"
    assert state["stale_refusals"] == 0
    assert state["budget_remaining"] < state["budget_budget"]


# ---------------------------------------------------------------------------
# epoch fence
# ---------------------------------------------------------------------------


def test_confirm_current_epoch_returns_assignment():
    pipe = _pipe(8, 4)
    a = pipe.confirm(2, 0)
    assert a.stage == 2 and list(a.layers) == [4, 5]


def test_stale_epoch_confirm_raises_typed_error():
    pipe = _pipe(8, 4)
    pipe.regroup(1, "Killed")
    with pytest.raises(StaleStageEpochError) as ei:
        pipe.confirm(1, 0)
    e = ei.value
    assert (e.job, e.stage, e.epoch, e.current_epoch) == ("t", 1, 0, 1)
    assert pipe.stale_refusals == 1
    # a stage index outside the shrunk membership is fenced too
    with pytest.raises(StaleStageEpochError):
        pipe.confirm(3, 1)


def test_stale_stage_epoch_error_rehydrates():
    err = StaleStageEpochError("stale", job="j", stage=2, epoch=3,
                               current_epoch=5)
    back = rehydrate_exception(package_exception(err))
    assert isinstance(back, StaleStageEpochError)
    assert (back.job, back.stage, back.epoch, back.current_epoch) == \
        ("j", 2, 3, 5)


def test_activation_keys_epoch_scoped():
    pipe = _pipe(8, 4)
    k0 = pipe.activation_key(3, 1, 2)
    assert k0 == "pipeline/t/e0/step3/b1/mb2"
    pipe.regroup(1, "Killed")
    assert pipe.activation_key(3, 1, 2) == "pipeline/t/e1/step3/b1/mb2"
    # explicit epoch pin (the zombie's namespace, never read again)
    assert pipe.activation_key(3, 1, 2, epoch=0) == k0


# ---------------------------------------------------------------------------
# chaos verbs
# ---------------------------------------------------------------------------


def test_stage_verbs_parse_and_registry():
    faults = chaos.parse_spec("kill-stage:9@2")
    assert len(faults) == 1
    f = faults[0]
    assert f.kind == "kill-stage" and f.signal_no == 9 and f.op_index == 2
    assert chaos.parse_spec("kill-stage@1")[0].signal_no == 9  # default SIG
    s = chaos.parse_spec("stall-stage:2.5@1")[0]
    assert s.kind == "stall-stage" and s.seconds == 2.5 and s.op_index == 1
    with pytest.raises(chaos.ChaosError, match="SECONDS"):
        chaos.parse_spec("stall-stage@1")

    reg = {v.name: v for v in chaos.verb_registry()}
    assert reg["kill-stage"].process_fatal
    assert not reg["stall-stage"].process_fatal
    for name in ("kill-stage", "stall-stage"):
        assert reg[name].scope == "process"
        chaos.parse_spec(reg[name].example)      # examples stay parseable
        assert name in chaos.grammar_markdown()


def test_stage_plans_scoped_by_stage_env(monkeypatch):
    monkeypatch.setenv(chaos.CHAOS_ENV, "kill-stage:9@2,stall-stage:1.5@0")
    monkeypatch.setenv(chaos.CHAOS_STAGE_ENV, "1")
    monkeypatch.setenv(chaos.STAGE_ENV, "1")
    assert chaos.stage_kill_plan() == {2: 9}
    assert chaos.stage_stall_plan() == {0: 1.5}
    monkeypatch.setenv(chaos.STAGE_ENV, "2")     # other stages: clean
    assert chaos.stage_kill_plan() == {}
    assert chaos.stage_stall_plan() == {}
    monkeypatch.delenv(chaos.CHAOS_STAGE_ENV)    # unscoped: every stage
    assert chaos.stage_kill_plan() == {2: 9}


def test_stage_verbs_do_not_arm_http_middleware(monkeypatch):
    # stage verbs are process-side plans, not HTTP faults: an engine built
    # from a stage-only spec injects nothing
    eng = chaos.ChaosEngine(chaos.parse_spec("kill-stage:9@1,"
                                             "stall-stage:2.5@0"))
    assert not eng.schedule and not eng.persistent


# ---------------------------------------------------------------------------
# scheduler: gang admission / partial preemption
# ---------------------------------------------------------------------------


def _sched(capacity):
    from kubetorch_tpu.controller.app import ControllerState
    from kubetorch_tpu.controller.scheduler import Scheduler
    from tests.test_scheduler import FakeBackend

    state = ControllerState(backend=FakeBackend())
    state.scheduler = Scheduler(state, capacity=capacity)
    return state.scheduler


def test_gang_admission_all_or_nothing():
    sched = _sched({"cpu": 4})
    pipe = _pipe(8, 4)
    out = sched.admit_gang("pipe1", pipe.gang_request())
    assert out["admitted"] and out["stages"] == 4
    assert sched.book.allocations["gang/pipe1/stage0"]["gang"] == "pipe1"
    # a second gang does NOT fit: nothing allocates, ONE queue entry
    out2 = sched.admit_gang("pipe2", pipe.gang_request())
    assert out2.get("queued") and not out2.get("admitted")
    assert len(sched.gang_queue) == 1
    assert not any(a.get("gang") == "pipe2"
                   for a in sched.book.allocations.values())
    # capacity frees -> kick admits the queued gang whole
    assert sched.release_gang("pipe1") == 4
    assert sched.kick_gangs() == 1
    assert not sched.gang_queue
    assert sched.book.allocations["gang/pipe2/stage3"]["stage"] == 3


def test_partial_gang_preemption_regroups_not_kills():
    sched = _sched({"cpu": 4})
    events = []
    sched.admit_gang("pipe1", _pipe(8, 4).gang_request(),
                     on_preempt=lambda **kw: events.append(kw))
    out = sched.preempt_gang_stage("pipe1", "default/preemptor")
    # uniform widths: cheapest = LAST stage (fewest downstream activations)
    assert out == {"stage": 3, "width": 1}
    assert events == [{"stage": 3, "width": 1, "cause": "Preempted"}]
    led = sched.ledger[-1]
    assert led["phase"] == "regrouped" and led["gang"] == "pipe1"
    # the other three stages kept their slots: degraded, not dead
    assert sum(1 for a in sched.book.allocations.values()
               if a.get("gang") == "pipe1") == 3


def test_victim_selection_only_offers_cheapest_gang_stage():
    sched = _sched({"cpu": 4})
    rows = [{"stage": s, "device_class": "cpu", "width": w}
            for s, w in ((0, 2), (1, 1), (2, 1))]
    sched.admit_gang("pipe1", rows, priority="batch")
    victims = sched._select_victims("default/preemptor", "cpu", 1,
                                    parse_priority("high"))
    # stages 1 and 2 tie on width; later stage wins; stage0 (width 2) and
    # stage1 must NOT be offered independently of the cheapest
    assert victims == ["gang/pipe1/stage2"]


def test_gang_queue_survives_snapshot_roundtrip():
    sched = _sched({"cpu": 2})
    sched.admit_gang("big", [{"stage": 0, "device_class": "cpu",
                              "width": 3}])
    snap = sched.state_dict()
    sched2 = _sched({"cpu": 2})
    sched2.restore(snap)
    assert [e["gang"] for e in sched2.gang_queue] == ["big"]


from kubetorch_tpu.controller.scheduler import parse_priority  # noqa: E402


# ---------------------------------------------------------------------------
# watchdog straggler classification + supervisor
# ---------------------------------------------------------------------------


def test_classify_straggler():
    from kubetorch_tpu.serving.watchdog import (CAUSE_SLOW,
                                                classify_straggler)
    assert classify_straggler(5.0, 2.0) == CAUSE_SLOW
    assert classify_straggler(1.0, 2.0) is None
    assert classify_straggler(99.0, 0.0) is None    # disabled


class _FakeProc:
    def __init__(self):
        self.exitcode = None
        self.killed = False

    def poll(self):
        return self.exitcode

    def kill(self):
        self.killed = True


def test_supervisor_regroups_on_death_and_measures_stall():
    from kubetorch_tpu.serving.pipeline_supervisor import PipelineSupervisor

    t = [0.0]
    procs = {}

    def launch(assignment, epoch, resume):
        p = _FakeProc()
        procs[(epoch, assignment.stage)] = p
        return p

    pipe = _pipe(8, 4)
    sup = PipelineSupervisor(pipe, launch, clock=lambda: t[0])
    sup.start()
    assert len(procs) == 4 and sup.poll() is None
    procs[(0, 1)].exitcode = -9
    t[0] = 1.0
    ev = sup.poll()
    assert ev["cause"] == "Killed" and ev["lost_stage"] == 1
    # every epoch-0 survivor was killed and the new membership launched
    assert all(p.killed for (e, _), p in procs.items() if e == 0)
    assert sum(1 for (e, _) in procs if e == 1) == 3
    state = sup.pipeline_state()
    assert state["regroup_pending"] and state["stages_live"] == 3
    t[0] = 2.5
    assert sup.note_committed_step(1) == pytest.approx(1.5)
    assert sup.note_committed_step(2) is None       # clock already closed
    assert not sup.pipeline_state()["regroup_pending"]


def test_supervisor_classifies_straggler_slow():
    from kubetorch_tpu.serving.pipeline_supervisor import PipelineSupervisor

    t = [0.0]
    pipe = _pipe(8, 4)
    sup = PipelineSupervisor(pipe, lambda a, e, resume: _FakeProc(),
                             stall_after_s=2.0, clock=lambda: t[0])
    sup.start()
    t[0] = 1.0
    for s in range(4):
        sup.beat(s)
    t[0] = 2.5
    sup.beat(0), sup.beat(2), sup.beat(3)           # stage 1 goes quiet
    t[0] = 3.5
    ev = sup.poll()
    assert ev["cause"] == "Slow" and ev["lost_stage"] == 1
    assert ev["stall_age_s"] == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# telemetry + /health surfacing
# ---------------------------------------------------------------------------


def test_pipeline_metrics_registered():
    m = telemetry.pipeline_metrics()
    for key in ("regroups", "stale", "epoch", "stages", "bubble",
                "regroup_seconds"):
        assert key in m
    text = telemetry.REGISTRY.render()
    for series in ("kt_pipeline_regroups_total", "kt_pipeline_stage_epoch",
                   "kt_pipeline_bubble_fraction",
                   "kt_pipeline_regroup_seconds"):
        assert series in text


# ---------------------------------------------------------------------------
# soak: schedule draw + invariant checker
# ---------------------------------------------------------------------------


def test_pipeline_profile_schedule_deterministic():
    from kubetorch_tpu.soak.schedule import generate

    a = generate(42, "pipeline", 32)
    b = generate(42, "pipeline", 32)
    assert a.to_json() == b.to_json()
    assert a.store_nodes == 3                    # ring carries the ckpts
    stage_keys = [k for k in a.boot_chaos if k.startswith("stage:")]
    assert len(stage_keys) == 1
    tok = a.boot_chaos[stage_keys[0]]
    assert tok.startswith(("kill-stage:", "stall-stage:"))
    chaos.parse_spec(tok)                        # armable as-is
    # both verbs reachable across seeds
    toks = {generate(s, "pipeline", 32).boot_chaos.get(
        next((k for k in generate(s, "pipeline", 32).boot_chaos
              if k.startswith("stage:")), ""), "")[:5]
        for s in range(20)}
    assert "kill-" in toks and "stall" in toks


def _rec(event, index, **kw):
    return {"kind": "pipeline", "event": event, "index": index, **kw}


def test_pipeline_progress_invariant():
    from kubetorch_tpu.soak.history import check_pipeline_progress

    good = [
        _rec("placed", 0, stage=0, epoch=0),
        _rec("committed", 1, step=1, epoch=0, fingerprint="aa"),
        _rec("regroup", 2, epoch=1, cause="Killed", lost_stage=1),
        _rec("placed", 3, stage=0, epoch=1),
        _rec("committed", 4, step=2, epoch=1, fingerprint="bb"),
        _rec("replay", 5, step=1, fingerprint="aa"),
        _rec("replay", 6, step=2, fingerprint="bb"),
    ]
    assert check_pipeline_progress(good) == []

    stalled = good[:3]                           # regroup, then nothing
    v = check_pipeline_progress(stalled)
    assert len(v) == 1 and "stalled" in v[0].detail

    stale = good + [_rec("placed", 7, stage=2, epoch=0)]
    v = check_pipeline_progress(stale)
    assert len(v) == 1 and "stale epoch" in v[0].detail

    forked = [dict(r) for r in good]
    forked[6] = _rec("replay", 6, step=2, fingerprint="XX")
    v = check_pipeline_progress(forked)
    assert len(v) == 1 and "bit-match" in v[0].detail

    uncovered = good[:6]                         # replay missed step 2
    v = check_pipeline_progress(uncovered)
    assert len(v) == 1 and "never covered" in v[0].detail


def test_pipeline_invariant_registered():
    from kubetorch_tpu.soak.history import INVARIANTS
    assert "pipeline-progress" in INVARIANTS


# ---------------------------------------------------------------------------
# acceptance: the real-subprocess chaos drill
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("token,stage", [("kill-stage:9@1", 1),
                                         ("stall-stage:2.5@1", 2)])
def test_stage_loss_drill_regroups_and_bit_matches_replay(
        tmp_path, token, stage):
    """SIGKILL (or stall) one stage of a 4-stage pipelined trainer
    mid-step: survivors re-group and commit every step, the zombie confirm
    raises the typed fence error, and each committed fingerprint
    bit-matches the unpartitioned replay — zero lost committed steps."""
    trainer = os.path.join(ASSETS, "pipeline_trainer.py")
    result = tmp_path / "result.jsonl"
    replay = tmp_path / "replay.jsonl"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "KT_CHAOS": token, "KT_CHAOS_STAGE": str(stage),
           "KT_CHAOS_SEED": "7"}
    steps = 6
    proc = subprocess.run(
        [sys.executable, trainer, "--steps", str(steps), "--stages", "4",
         "--result", str(result), "--workdir", str(tmp_path / "wd")],
        env=env, timeout=180, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    clean_env = {k: v for k, v in env.items() if not k.startswith("KT_CHAOS")}
    subprocess.run(
        [sys.executable, trainer, "--replay", "--steps", str(steps),
         "--stages", "4", "--result", str(replay)],
        env=clean_env, timeout=120, check=True)

    recs = [json.loads(line) for line in result.read_text().splitlines()]
    regroups = [r for r in recs if r["event"] == "regroup"]
    assert len(regroups) == 1 and regroups[0]["lost_stage"] == stage
    expect_cause = "Killed" if token.startswith("kill") else "Slow"
    assert regroups[0]["cause"] == expect_cause
    assert any(r["event"] == "stale-refused" for r in recs)
    committed = {r["step"]: r["fingerprint"]
                 for r in recs if r["event"] == "committed"}
    assert sorted(committed) == list(range(1, steps + 1))  # zero lost steps
    # progress resumed within one elastic-resume window
    done = [r for r in recs if r["event"] == "regroup-done"]
    from kubetorch_tpu.serving.elastic import ElasticPolicy
    assert len(done) == 1 and 0 < done[0]["stall_s"] < \
        ElasticPolicy().resume_window_s
    replayed = {r["step"]: r["fingerprint"]
                for line in replay.read_text().splitlines()
                for r in [json.loads(line)]}
    assert replayed == committed                 # bit-identical throughout


@pytest.mark.slow
def test_clean_pipeline_run_matches_replay(tmp_path):
    trainer = os.path.join(ASSETS, "pipeline_trainer.py")
    result = tmp_path / "result.jsonl"
    replay = tmp_path / "replay.jsonl"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("KT_CHAOS", None)
    subprocess.run(
        [sys.executable, trainer, "--steps", "4", "--stages", "4",
         "--result", str(result), "--workdir", str(tmp_path / "wd")],
        env=env, timeout=120, check=True)
    subprocess.run(
        [sys.executable, trainer, "--replay", "--steps", "4", "--stages",
         "4", "--result", str(replay)], env=env, timeout=120, check=True)
    recs = [json.loads(line) for line in result.read_text().splitlines()]
    assert not any(r["event"] == "regroup" for r in recs)
    committed = {r["step"]: r["fingerprint"]
                 for r in recs if r["event"] == "committed"}
    replayed = {r["step"]: r["fingerprint"]
                for line in replay.read_text().splitlines()
                for r in [json.loads(line)]}
    assert committed == replayed and len(committed) == 4

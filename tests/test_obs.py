"""The fleet flight recorder, black-box forensics, fleet rollup math,
trace recording, and the observability docs drift gate (ISSUE 20).

Everything here runs without a cluster: the recorder writes to tmp_path
spools, the aggregator is fed hand-crafted exposition text with injected
timestamps, and the one subprocess test SIGKILLs a real child to prove
the spool survives the death it exists to record.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest
from click.testing import CliRunner

from kubetorch_tpu import telemetry
from kubetorch_tpu.exceptions import (SloBurnAlert, package_exception,
                                      rehydrate_exception)
from kubetorch_tpu.obs import (CounterEpochs, FleetAggregator, FlightRecorder,
                               format_blackbox, merge_histograms, read_spool,
                               reconstruct)
from kubetorch_tpu.soak.history import check_blackbox

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# flight recorder: spool roundtrip, rotation, tamper, torn tail
# ---------------------------------------------------------------------------

def _manual_recorder(tmp_path, **kw):
    """A recorder driven by explicit flush() calls — no thread, no signal
    handlers — against a private registry so tests don't pollute the
    process-global one."""
    reg = telemetry.MetricsRegistry()
    rec = FlightRecorder(str(tmp_path / "spool"), registry=reg, **kw)
    rec.dir.mkdir(parents=True, exist_ok=True)
    return rec, reg


def test_recorder_roundtrip_reconstructs_final_state(tmp_path):
    rec, reg = _manual_recorder(tmp_path, name="unit")
    ops = reg.counter("kt_test_ops_total", "test ops", labels=("op",))
    for i in range(5):
        ops.inc(op="write")
        if i % 2:
            ops.inc(op="read")
        rec.flush()
    rec.stop(final=True)

    data = read_spool(rec.dir)
    assert data["errors"] == []
    assert not data["torn_tail"]
    seqs = [r["seq"] for r in data["records"]]
    assert seqs == list(range(len(seqs)))

    recon = reconstruct(rec.dir)
    assert recon["errors"] == []
    assert recon["note"] == {"reason": "stop"}
    values = recon["metrics"]["kt_test_ops_total"]["values"]
    assert values["write"] == 5
    assert values["read"] == 2
    # delta encoding: steady-state records carry only what changed
    later = [r for r in data["records"][1:] if r.get("kind") == "snapshot"]
    assert later and all(not r.get("full") for r in later)


def test_rotation_keeps_spool_bounded_and_contiguous(tmp_path):
    rec, reg = _manual_recorder(tmp_path, name="rot", max_bytes=64 * 1024)
    # bounded cardinality (the registry's contract), high churn: every
    # flush carries a delta touching all 40 series
    wide = reg.counter("kt_test_wide_total", "wide", labels=("k",))
    for _ in range(80):
        for j in range(40):
            wide.inc(k=f"series-{j:04d}-" + "x" * 48)
        rec.flush()
    rec.stop(final=True)

    segments = sorted(rec.dir.glob("segment-*.jsonl"))
    total = sum(s.stat().st_size for s in segments)
    assert total <= rec.max_bytes, f"spool grew to {total} bytes"
    # rotation deleted old segments: the survivors verify clean, with no
    # seq gaps among what was retained
    data = read_spool(rec.dir)
    assert data["errors"] == []
    assert data["records"][0]["seq"] > 0, "expected old segments dropped"


def test_tampered_record_breaks_the_chain(tmp_path):
    rec, reg = _manual_recorder(tmp_path, name="tamper")
    ops = reg.counter("kt_test_ops_total2", "test ops")
    for _ in range(4):
        ops.inc()
        rec.flush()
    rec.stop(final=True)

    seg = sorted(rec.dir.glob("segment-*.jsonl"))[0]
    lines = seg.read_text("utf-8").splitlines()
    assert len(lines) >= 3
    lines[1] = lines[1].replace('"kind":"snapshot"', '"kind":"snapsh0t"')
    seg.write_text("\n".join(lines) + "\n", "utf-8")

    errors = read_spool(rec.dir)["errors"]
    assert errors and "hash chain broken" in errors[0]


def test_torn_final_line_is_expected_crash_artifact(tmp_path):
    rec, reg = _manual_recorder(tmp_path, name="torn")
    ops = reg.counter("kt_test_ops_total3", "test ops")
    for _ in range(4):
        ops.inc()
        rec.flush()
    rec.stop(final=False)

    seg = sorted(rec.dir.glob("segment-*.jsonl"))[-1]
    raw = seg.read_bytes()
    # tear the last record mid-append, the one place SIGKILL can reach
    seg.write_bytes(raw[:-(len(raw.splitlines()[-1]) // 2) - 1])
    data = read_spool(rec.dir)
    assert data["torn_tail"]
    assert data["errors"] == []
    assert len(data["records"]) == 3


def test_truncation_anywhere_else_is_an_error(tmp_path):
    rec, reg = _manual_recorder(tmp_path, name="midcut")
    ops = reg.counter("kt_test_ops_total4", "test ops")
    for _ in range(4):
        ops.inc()
        rec.flush()
    rec.stop(final=False)

    seg = sorted(rec.dir.glob("segment-*.jsonl"))[-1]
    lines = seg.read_text("utf-8").splitlines()
    lines[1] = lines[1][:len(lines[1]) // 2]
    seg.write_text("\n".join(lines) + "\n", "utf-8")
    data = read_spool(rec.dir)
    assert not data["torn_tail"]
    assert data["errors"] and "truncated or corrupt" in data["errors"][0]


_CHILD_SCRIPT = """
import sys, time
from kubetorch_tpu import telemetry
from kubetorch_tpu.obs import FlightRecorder

rec = FlightRecorder(sys.argv[1], name="rank", interval_s=0.05)
rec.start()
with telemetry.stage("doomed_op", request="req-blackbox"):
    telemetry.observe_stage("warmup", 0.01)
    rec.flush()
    print("READY", flush=True)
    time.sleep(120)
"""


def test_sigkill_leaves_readable_blackbox_with_inflight_span(tmp_path):
    """The chaos drill's rank half: a process SIGKILLed mid-span leaves a
    verifiable spool whose last record still holds the in-flight work."""
    spool = tmp_path / "spool"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    proc = subprocess.Popen([sys.executable, "-c", _CHILD_SCRIPT, str(spool)],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            env=env, cwd=REPO)
    try:
        deadline = time.time() + 120
        seen = False
        while time.time() < deadline and not seen:
            if proc.poll() is not None:
                pytest.fail("child died early: "
                            + proc.stderr.read().decode("utf-8", "replace"))
            for d in spool.glob("rank-*"):
                recon = reconstruct(d)
                if any("doomed_op" in s.get("name", "")
                       for s in recon.get("inflight", [])):
                    seen = True
                    break
            time.sleep(0.1)
        assert seen, "recorder never committed the in-flight span"
        proc.kill()  # SIGKILL: no atexit, no signal handler, no flush
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    dirs = list(spool.glob("rank-*"))
    assert len(dirs) == 1
    data = read_spool(dirs[0])
    assert data["errors"] == [], data["errors"]
    recon = reconstruct(dirs[0])
    assert any("doomed_op" in s.get("name", "") for s in recon["inflight"])
    report = format_blackbox(recon)
    assert "doomed_op" in report
    assert "dead" in report


# ---------------------------------------------------------------------------
# merge math (satellite: mismatched buckets, empty pods, counter resets)
# ---------------------------------------------------------------------------

def test_merge_histograms_union_of_edges_floor_semantics():
    merged = merge_histograms({
        "pod-a": {"0.1": 1, "1.0": 3, "+Inf": 3},
        "pod-b": {"0.5": 2, "+Inf": 4},
    })
    # pod-b has no edge <= 0.1, so it contributes nothing there; at 0.5
    # pod-a is floored to its 0.1 bucket
    assert merged == {"0.1": 1, "0.5": 3, "1.0": 5, "+Inf": 7}


def test_merge_histograms_empty_inputs():
    assert merge_histograms({}) == {}
    assert merge_histograms({"pod-a": {}}) == {}
    merged = merge_histograms({"pod-a": {"0.1": 2, "+Inf": 2}, "pod-b": {}})
    assert merged == {"0.1": 2, "+Inf": 2}


def test_counter_epochs_reset_opens_epoch_never_negative():
    ep = CounterEpochs()
    ep.update("k", {"0.1": 5, "+Inf": 10})
    # pod restarted: totals went DOWN — fresh values ARE the delta
    corrected = ep.update("k", {"0.1": 1, "+Inf": 3})
    assert ep.resets == 1
    assert corrected == {"0.1": 6, "+Inf": 13}
    # a single edge dipping without the total dropping clamps at zero
    corrected = ep.update("k", {"0.1": 0, "+Inf": 4})
    assert ep.resets == 1
    assert corrected["0.1"] == 6
    assert corrected["+Inf"] == 14
    assert all(v >= 0 for v in corrected.values())


def _stage_text(stage, buckets):
    lines = [f'kt_stage_seconds_bucket{{stage="{stage}",le="{le}"}} {count}'
             for le, count in buckets.items()]
    total = buckets.get("+Inf", 0)
    lines.append(f'kt_stage_seconds_count{{stage="{stage}"}} {total}')
    return "\n".join(lines) + "\n"


def test_aggregator_survives_pod_restart_and_dead_pods():
    agg = FleetAggregator(slo_s=0.5, fast_window_s=10, slow_window_s=100)
    agg.ingest("pod-a", _stage_text("execute", {"0.5": 8, "+Inf": 10}),
               now=0.0)
    agg.ingest("pod-b", _stage_text("execute", {"0.5": 4, "+Inf": 5}),
               now=0.0)
    agg.tick(now=0.0)
    assert agg.merged_stages()["execute"]["+Inf"] == 15

    # pod-a restarts (counters reset low) and pod-b goes dark: history
    # from both epochs and the dead pod's last totals both survive
    agg.ingest("pod-a", _stage_text("execute", {"0.5": 1, "+Inf": 2}),
               now=5.0)
    agg.ingest("pod-b", None, now=5.0)
    agg.tick(now=5.0)
    merged = agg.merged_stages()["execute"]
    assert merged["+Inf"] == 17  # 10 + 2 (new epoch) + 5 (dead pod history)
    status = agg.status()
    assert status["pods"]["pod-a"]["up"] is True
    assert status["pods"]["pod-b"]["up"] is False


def test_aggregator_quantiles_match_single_scrape_reference():
    buckets = {"0.1": 50, "0.5": 90, "1.0": 100, "+Inf": 100}
    agg = FleetAggregator(slo_s=1.0)
    half = {le: c / 2 for le, c in buckets.items()}
    agg.ingest("pod-a", _stage_text("execute", half), now=0.0)
    agg.ingest("pod-b", _stage_text("execute", half), now=0.0)
    agg.tick(now=0.0)
    from kubetorch_tpu.controller.app import _quantile_from_buckets
    for q in (0.5, 0.99):
        assert agg.quantile("execute", q) == pytest.approx(
            _quantile_from_buckets(buckets, q))


# ---------------------------------------------------------------------------
# SLO burn rates, alert emission, cooldown
# ---------------------------------------------------------------------------

def test_burn_alert_fires_once_per_window_and_rehydrates():
    agg = FleetAggregator(slo_s=0.1, target=0.9, burn_threshold=2.0,
                          fast_window_s=10.0, slow_window_s=100.0)
    agg.ingest("pod", _stage_text("serve", {"0.1": 100, "+Inf": 100}),
               now=0.0)
    assert agg.tick(now=0.0) == []

    # 100 new observations, all slower than the SLO: bad_frac 1.0 over a
    # 0.1 budget = 10x burn, past the 2x threshold on both windows
    agg.ingest("pod", _stage_text("serve", {"0.1": 100, "+Inf": 200}),
               now=5.0)
    raised = agg.tick(now=5.0)
    windows = {a.window for a in raised}
    assert windows == {"fast", "slow"}
    fast = next(a for a in raised if a.window == "fast")
    assert fast.stage == "serve"
    assert fast.burn_rate > 2.0

    # still breaching one second later: cooldown holds the page
    agg.ingest("pod", _stage_text("serve", {"0.1": 100, "+Inf": 300}),
               now=6.0)
    assert agg.tick(now=6.0) == []

    # a fast-window length later the ongoing breach pages again (fast
    # only — the slow window's cooldown is still running)
    agg.ingest("pod", _stage_text("serve", {"0.1": 100, "+Inf": 400}),
               now=16.0)
    again = agg.tick(now=16.0)
    assert {a.window for a in again} == {"fast"}

    # the /fleet/alerts surface ships the typed exception, not a dict
    back = rehydrate_exception(package_exception(fast))
    assert isinstance(back, SloBurnAlert)
    assert back.stage == "serve" and back.window == "fast"
    assert back.burn_rate == fast.burn_rate


def test_histogram_blind_above_slo_reads_all_good():
    # no finite edge at or above the SLO: the data can't distinguish
    # good from bad, so burn stays zero rather than inventing badness
    agg = FleetAggregator(slo_s=10.0, target=0.9, burn_threshold=1.0,
                          fast_window_s=10.0, slow_window_s=100.0)
    agg.ingest("pod", _stage_text("serve", {"0.1": 0, "1.0": 0, "+Inf": 0}),
               now=0.0)
    agg.tick(now=0.0)
    agg.ingest("pod", _stage_text("serve", {"0.1": 0, "1.0": 0, "+Inf": 50}),
               now=5.0)
    assert agg.tick(now=5.0) == []
    assert agg.status()["stages"]["serve"]["burn"]["fast"] == 0.0


# ---------------------------------------------------------------------------
# surfaces: build-info gauge, kt blackbox CLI, soak invariant
# ---------------------------------------------------------------------------

def test_build_info_gauge_on_every_metrics_page():
    telemetry.build_info_metrics()
    text = telemetry.REGISTRY.render()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("kt_build_info{"))
    for label in ("version=", "jax=", "jaxlib=", "backend=", "host="):
        assert label in line


def test_blackbox_cli_reports_and_flags_tamper(tmp_path):
    rec, reg = _manual_recorder(tmp_path, name="cliunit")
    ops = reg.counter("kt_test_cli_total", "test ops")
    for _ in range(3):
        ops.inc()
        rec.flush()
    rec.stop(final=True)

    from kubetorch_tpu.cli import cli
    runner = CliRunner()
    r = runner.invoke(cli, ["blackbox", str(tmp_path / "spool")])
    assert r.exit_code == 0, r.output
    assert "black box:" in r.output
    assert "metric movement over the final interval" in r.output

    seg = sorted(rec.dir.glob("segment-*.jsonl"))[0]
    seg.write_text(seg.read_text("utf-8").replace(
        '"kind":"snapshot"', '"kind":"snapsh0t"', 1), "utf-8")
    r = runner.invoke(cli, ["blackbox", str(tmp_path / "spool")])
    assert r.exit_code != 0
    assert "hash chain broken" in r.output


def test_obs_top_renders_pod_counts_from_status_mapping(monkeypatch):
    """/fleet/status ships pods as a per-pod mapping; the dashboard header
    must count up/down from it, not read them as pre-computed counts."""
    agg = FleetAggregator(slo_s=0.5, fast_window_s=10, slow_window_s=100)
    agg.ingest("pod-a", _stage_text("execute", {"0.5": 8, "+Inf": 10}),
               now=0.0)
    agg.ingest("pod-b", None, now=0.0)
    agg.tick(now=0.0)
    snap = agg.status()

    class _Resp:
        def raise_for_status(self):
            pass

        def json(self):
            return snap

    import requests
    monkeypatch.setattr(requests, "get", lambda *a, **k: _Resp())
    from kubetorch_tpu.cli import cli
    r = CliRunner().invoke(cli, ["obs", "top", "--url", "http://controller"])
    assert r.exit_code == 0, r.output
    assert "1 pod(s) up, 1 down" in r.output
    assert "execute" in r.output


def test_check_blackbox_invariant():
    clean = [{"index": 0, "kind": "blackbox", "armed": True, "kills": 2,
              "spools": [{"dir": "/s/rank-1", "errors": []}]}]
    assert check_blackbox(clean) == []

    broken = [{"index": 0, "kind": "blackbox", "armed": True, "kills": 1,
               "spools": [{"dir": "/s/rank-1",
                           "errors": ["segment-0: hash chain broken"]}]}]
    violations = check_blackbox(broken)
    assert len(violations) == 1
    assert violations[0].invariant == "blackbox"
    assert "hash chain broken" in violations[0].detail

    # kills fired but nothing survived: the loss window is unbounded
    silent = [{"index": 3, "kind": "blackbox", "armed": True, "kills": 2,
               "spools": []}]
    violations = check_blackbox(silent)
    assert len(violations) == 1
    assert "no flight-recorder spools" in violations[0].detail

    # recorder never armed: nothing to assert
    unarmed = [{"index": 0, "kind": "blackbox", "armed": False, "kills": 2,
                "spools": []}]
    assert check_blackbox(unarmed) == []


# ---------------------------------------------------------------------------
# docs drift gate (satellite: an undocumented live series fails the build)
# ---------------------------------------------------------------------------

def _docs_text():
    return Path(REPO, "docs", "observability.md").read_text("utf-8")


def test_observability_docs_cover_every_live_series():
    names = {telemetry.stage_histogram().name}
    for fn in (telemetry.train_metrics, telemetry.spec_metrics,
               telemetry.serve_metrics, telemetry.cold_start_metrics,
               telemetry.soak_metrics, telemetry.pipeline_metrics,
               telemetry.flywheel_metrics, telemetry.build_info_metrics,
               telemetry.fleet_metrics, telemetry.obs_metrics):
        for metric in fn().values():
            names.add(metric.name)
    text = _docs_text()
    missing = sorted(n for n in names if f"`{n}`" not in text)
    assert not missing, (f"docs/observability.md drifted — undocumented "
                         f"series: {missing}")


def test_fleet_obs_metrics_table_matches_registry_catalog():
    telemetry.build_info_metrics()
    telemetry.fleet_metrics()
    telemetry.obs_metrics()
    text = _docs_text()
    begin = text.index("<!-- kt-metrics:fleet-obs:begin -->")
    end = text.index("<!-- kt-metrics:fleet-obs:end -->")
    block = text[begin:end]
    rows = [(name, kind, labels)
            for name, kind, labels in telemetry.REGISTRY.catalog()
            if name == "kt_build_info" or name.startswith("kt_fleet_")
            or name.startswith("kt_obs_")]
    assert rows, "registry lost the fleet/obs families"
    for name, kind, labels in rows:
        line = f"| `{name}` | {kind} | {labels} |"
        assert line in block, (f"generated table drifted: regenerate the "
                               f"kt-metrics:fleet-obs block — missing "
                               f"{line!r}")

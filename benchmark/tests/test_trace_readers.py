"""The readers of the program's own spans (PR 26), over a synthetic ring:
``python -m pytest benchmark/tests/test_trace_readers.py -q``. No jax, no
service: the ring is a list of span dicts as ``telemetry.RING.snapshot()``
gives them."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as R         # noqa: E402

CTX = {"window": {"open": {"now": 100.0}, "close": {"now": 150.0}}}


def call(start, method="generate", name="client.call", **attrs):
    return {"name": name, "start_mono": start, "end_mono": start + 1.0,
            "attrs": {"fn": "ServeBench", "method": method, **attrs}}


def timed(start, queue, prefill, host, blocks, **over):
    return call(start, **{
        "server.deserialize_ms": 0.5, "server.queue_wait_ms": 1.0,
        "server.device_transfer_ms": 0.25, "server.respond_ms": 1.25,
        "server.execute_ms": 900.0, "rank.execute_ms": 890.0,
        "engine.queue_ms": queue, "engine.prefill_ms": prefill,
        "engine.host_ms": host, "engine.blocks": blocks, **over})


RING = [
    timed(90.0, 1000.0, 1000.0, 1000.0, 1),          # the ramp: before it
    timed(100.0, 200.0, 80.0, 30.0, 10),
    timed(120.0, 240.0, 90.0, 50.0, 10, **{"server.shm_copy_ms": 2.0}),
    timed(149.9, 260.0, 70.0, 40.0, 20),
    timed(150.5, 9.0, 9.0, 9.0, 9),                   # after the close
    call(125.0, method="mark"),                       # another method
    call(126.0),                                      # an older pod: bare
    {"name": "client.call", "attrs": {"method": "generate",
                                      "engine.queue_ms": 1.0}},  # no stamp
    {"name": "client.deploy", "start_mono": 1.0, "end_mono": 2.0,
     "attrs": {"service": "old", "boot.pod_boot_s": 99.0}},
    {"name": "client.deploy", "start_mono": 60.0, "end_mono": 90.0,
     "attrs": {"service": "bench", "boot.pod_boot_s": 2.0,
               "boot.pool_spawn_s": 0.5, "boot.rank_spawn_s": 1.0,
               "boot.rank_accel_s": 8.0, "boot.rank_import_s": 0.25,
               "boot.rank_init_s": 2.0, "boot.rank_warmup_s": 5.0,
               "poll_slack_s": 1.75}},
]


def metric(name):
    """The committed metric file and its reader, as run.py resolves them."""
    spec = R.read_json(os.path.join(BENCH, "metrics", name + ".json"))
    reader = R.load_file(os.path.join(BENCH, "readers",
                                      spec["reader"] + ".py"))
    return lambda ring, ctx=CTX: reader.read(ctx, ring=ring, **spec["args"])


@pytest.mark.parametrize("name, want", [
    # median of the sums of the stages other than execute: 3.0, 5.0, 3.0
    ("fabric_pod_ms.serve", 3.0),
    ("admit_queue_ms", 240.0),
    ("admit_prefill_ms", 80.0),
    # (30 + 50 + 40) ms over (10 + 10 + 20) blocks
    ("engine_host_ms_per_block", 3.0),
    ("deploy_pod_boot_s", 2.5),
    ("deploy_rank_boot_s", 16.25),
    ("deploy_poll_slack_s", 1.75),
])
def test_reader_over_a_synthetic_ring(name, want):
    assert metric(name)(RING) == pytest.approx(want)


def test_window_selection_is_by_start_mono():
    read = metric("admit_queue_ms")
    assert read(RING, {"window": {"open": {"now": 80.0},
                                  "close": {"now": 95.0}}}) == 1000.0
    assert read(RING, {"window": {"open": {"now": 150.1},
                                  "close": {"now": 151.0}}}) == 9.0
    assert read(RING, {"window": {"open": {"now": 200.0},
                                  "close": {"now": 250.0}}}) is None


@pytest.mark.parametrize("name", [
    "fabric_pod_ms.serve", "admit_queue_ms", "admit_prefill_ms",
    "engine_host_ms_per_block", "deploy_pod_boot_s", "deploy_rank_boot_s",
    "deploy_poll_slack_s"])
def test_nothing_to_read_is_none_and_never_raises(name):
    """An empty ring, a ring of other spans, and the spans of a program
    that lacks what PR 26 adds (no stamps, no attributes: the parent
    commit) all read as None."""
    read = metric(name)
    older = [{"name": "client.call", "start": 1.0, "end": 2.0,
              "attrs": {"method": "generate", "status": 200}},
             {"name": "stage.execute", "attrs": {}}, {}]
    bare = [call(120.0), {"name": "client.deploy", "start_mono": 1.0,
                          "attrs": {"service": "bench"}}]
    for ring in ([], older, bare):
        assert read(ring) is None


def test_new_metrics_are_declared_for_both_cells():
    bench = R.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    new = bench["per_layer"][-7:]
    assert [m["name"] for m in new] == [
        "fabric_pod_ms.serve", "admit_queue_ms", "admit_prefill_ms",
        "engine_host_ms_per_block", "deploy_pod_boot_s",
        "deploy_rank_boot_s", "deploy_poll_slack_s"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in new:
        assert m["source"] == "program_span" and m["moves"] in e2e
        assert m["workloads"] == ["mistral7b-chat-closed",
                                  "mixtral-chat-closed"]
        cell = R.resolve(m["workloads"][0],
                         os.path.join(ROOT, "BENCHMARK.json"), BENCH)
        assert m["name"] in {p["name"] for p in cell["per_layer"]}


def test_readers_read_the_programs_own_ring():
    """Without ``ring=`` the readers take this process's
    ``kubetorch_tpu.telemetry.RING``, where the program leaves the caller's
    spans; header values go through the program's own parser."""
    from kubetorch_tpu import telemetry
    telemetry.RING.clear()
    try:
        with telemetry.span("client.call", fn="ServeBench",
                            method="generate") as sp:
            telemetry.apply_timing(sp, telemetry.format_timing(
                {"engine.queue": 0.2, "engine.prefill": 0.08,
                 "engine.host": 0.03, "engine.blocks": 10,
                 "server.respond": 0.001}))
        ctx = {"window": {"open": {"now": sp.start_mono - 1},
                          "close": {"now": sp.end_mono}}}
        assert metric("admit_queue_ms")(None, ctx) == pytest.approx(200.0)
        assert metric("engine_host_ms_per_block")(None, ctx) == \
            pytest.approx(3.0)
        assert metric("fabric_pod_ms.serve")(None, ctx) == \
            pytest.approx(1.0)
        assert metric("deploy_poll_slack_s")(None, ctx) is None
    finally:
        telemetry.RING.clear()
    print(json.dumps({"ok": True}))

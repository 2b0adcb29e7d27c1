"""The rank's boot from inside (ISSUE 38): one span tree in place of the
laps, jax's own compile events tallied (``telemetry.JitTally``), the boot
record the pool and ``/ready`` carry, and the benchmark's four metrics that
read it off the caller's ``client.deploy`` span."""

import importlib.util
import json
import os
import queue
import sys
import threading
import time

import jax
import numpy as np
import pytest

from kubetorch_tpu import telemetry
from kubetorch_tpu.serving import process_worker
from kubetorch_tpu.serving.process_pool import (RANK_BOOT_DETAIL,
                                                RANK_BOOT_PHASES, ProcessPool)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ASSETS = os.path.join(ROOT, "tests", "assets")
BENCH = os.path.join(ROOT, "benchmark")

TRACE, LOWER, BACKEND = telemetry.JIT_EVENTS


@pytest.fixture
def tally():
    t = telemetry.JitTally()
    t.install(jax.monitoring, jax.profiler.TraceAnnotation)
    try:
        yield t
    finally:
        t.uninstall()


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent cache in a directory of the test's own, keeping
    every program (checked on the CPU with jax 0.9.0: a hit after
    ``jax.clear_caches()`` works)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(tmp_path), 0, 0)):
        jax.config.update(n, v)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


def _jit_spans(program):
    return [s for s in telemetry.RING.snapshot()
            if s["name"] == "jit" and s["attrs"]["program"] == program]


class FakeMonitoring:
    """``jax.monitoring``'s registration surface, fired by hand."""

    def __init__(self):
        self.scalar, self.spans, self.events = [], [], []

    def register_scalar_listener(self, cb):
        self.scalar.append(cb)

    def register_event_time_span_listener(self, cb):
        self.spans.append(cb)

    def register_event_listener(self, cb):
        self.events.append(cb)

    def unregister_scalar_listener(self, cb):
        self.scalar.remove(cb)

    def unregister_event_time_span_listener(self, cb):
        self.spans.remove(cb)

    def unregister_event_listener(self, cb):
        self.events.remove(cb)

    def start(self, event, t, name):
        for cb in self.scalar:
            cb(event, t, fun_name=name)

    def end(self, event, a, b, name):
        for cb in self.spans:
            cb(event, a, b, fun_name=name)

    def cache(self, verdict):
        for cb in self.events:
            cb("/jax/compilation_cache/" + verdict)


def _fake_tally():
    mon = FakeMonitoring()
    t = telemetry.JitTally()
    t.install(mon)
    return mon, t


# -- the tally, on jax's own events -------------------------------------------

def test_a_program_misses_then_is_fetched_after_clear_caches(
        persistent_cache, monkeypatch):
    monkeypatch.setenv("KT_TRACE", "1")

    def miss_then_hit(x):
        return x * 2.5 + 1

    x = np.arange(4, dtype=np.float32)        # numpy: no eager op's program
    for verdict, compiled in (("miss", 1), ("hit", 0)):
        t = telemetry.JitTally()
        t.install(jax.monitoring)
        try:
            np.asarray(jax.jit(miss_then_hit)(x))
        finally:
            t.uninstall()
        totals = t.totals()
        assert (totals["programs"], totals["programs_compiled"]) \
            == (1, compiled), verdict
        assert totals["jit_s"] > 0.0
        span = _jit_spans("miss_then_hit")[-1]["attrs"]
        assert span["cache"] == verdict
        assert span["backend_s"] > 0.0 and span["lower_s"] > 0.0
        jax.clear_caches()


def test_a_nested_jit_is_counted_once(tally):
    @jax.jit
    def inner(x):
        return x * 2

    @jax.jit
    def outer(x):
        return inner(x) * 3 + inner(x + 1)

    seen = []

    def listen(event, a, b, **kw):
        if event in telemetry.JIT_EVENTS:
            seen.append(b - a)

    jax.monitoring.register_event_time_span_listener(listen)
    try:
        t0 = time.time()
        np.asarray(outer(np.ones(4, np.float32)))
        wall = time.time() - t0
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    jit_s = tally.totals()["jit_s"]
    # inner's traces and their ops' fire inside outer's: the sum of the
    # events overstates, the union cannot exceed the call
    assert jit_s <= wall
    assert jit_s < sum(seen)
    assert tally.totals()["programs"] == 1


def test_union_and_program_spans_of_nested_events(monkeypatch):
    monkeypatch.setenv("KT_TRACE", "1")
    mon, t = _fake_tally()
    parent = telemetry.Span("boot.init", "tr", "sp", None, {})
    t.parent = parent
    mon.start(TRACE, 100.0, "f")
    mon.start(TRACE, 101.0, "inner")
    mon.end(TRACE, 101.0, 105.0, "inner")
    mon.end(TRACE, 100.0, 110.0, "f")
    mon.start(LOWER, 111.0, "jit(f)")
    mon.end(LOWER, 111.0, 112.0, "jit(f)")
    mon.start(BACKEND, 113.0, "jit(f)")
    mon.cache("compile_requests_use_cache")
    mon.cache("cache_hits")
    mon.end(BACKEND, 113.0, 115.0, "jit(f)")
    assert t.totals() == {"jit_s": 13.0, "programs": 1,
                          "programs_compiled": 0}
    span = [s for s in telemetry.RING.find("tr") if s["name"] == "jit"][-1]
    assert span["parent_id"] == "sp"
    assert span["end"] - span["start"] == 15.0
    assert span["attrs"] == {"program": "f", "cache": "hit", "trace_s": 10.0,
                             "lower_s": 1.0, "backend_s": 2.0}
    # a program lowered and never compiled (``.lower()`` alone) counts in
    # the union and not in the next program's span
    mon.start(TRACE, 120.0, "g")
    mon.end(TRACE, 120.0, 121.0, "g")
    mon.start(LOWER, 121.0, "jit(g)")
    mon.end(LOWER, 121.0, 122.0, "jit(g)")
    mon.start(TRACE, 130.0, "h")
    mon.end(TRACE, 130.0, 131.0, "h")
    mon.start(BACKEND, 132.0, "jit(h)")
    mon.end(BACKEND, 132.0, 133.0, "jit(h)")
    assert t.totals() == {"jit_s": 17.0, "programs": 2,
                          "programs_compiled": 1}
    span = [s for s in telemetry.RING.find("tr") if s["name"] == "jit"][-1]
    assert span["attrs"]["program"] == "h"
    assert span["attrs"]["cache"] == "uncached"
    assert span["attrs"]["trace_s"] == 1.0 and span["attrs"]["lower_s"] == 0
    t.uninstall()
    assert mon.scalar == mon.spans == mon.events == []


def test_a_cache_verdict_belongs_to_its_own_thread():
    """A hit fired on one thread while another thread's backend compile
    ends is not that compile's: the verdict waits, per thread, for the end
    of the backend span it fired inside."""
    mon, t = _fake_tally()
    hit_fired, other_done = threading.Event(), threading.Event()
    errors = []

    def fetched():
        try:
            mon.start(BACKEND, 0.0, "jit(a)")
            mon.cache("cache_hits")
            hit_fired.set()
            assert other_done.wait(10)
            mon.end(BACKEND, 0.0, 1.0, "jit(a)")
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    th = threading.Thread(target=fetched)
    th.start()
    assert hit_fired.wait(10)
    mon.start(BACKEND, 0.5, "jit(b)")
    mon.end(BACKEND, 0.5, 2.0, "jit(b)")       # asked no cache: compiled
    other_done.set()
    th.join(10)
    assert not th.is_alive() and not errors
    assert t.totals() == {"jit_s": 2.0, "programs": 2,
                          "programs_compiled": 1}


def test_tracing_off_keeps_the_counts_and_drops_the_spans(tally,
                                                          monkeypatch):
    monkeypatch.setenv("KT_TRACE", "0")

    def counted_untraced(x):
        return x / 4

    np.asarray(jax.jit(counted_untraced)(np.ones(3, np.float32)))
    assert tally.totals()["programs"] == 1
    assert tally.totals()["jit_s"] > 0.0
    assert _jit_spans("counted_untraced") == []
    boot = telemetry.BootTree("rank.boot")
    with boot:
        with boot.phase("init"):
            pass
    assert set(boot.seconds()) == {"init"}
    assert boot.spans() == []


# -- the boot tree, through the rank's own loop -------------------------------

def _boot_in_process(monkeypatch, cls_name):
    """``_worker_loop`` in this process, given the chip as far as its check
    can tell, until its boot is done; what it sent the pool."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("KT_TRACE", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(telemetry, "JIT", telemetry.JitTally())
    monkeypatch.syspath_prepend(ASSETS)
    requests, responses = queue.Queue(), queue.Queue()
    requests.put({"op": "shutdown"})
    pointers = {"project_root": ASSETS, "module_name": "boot_payloads",
                "file_path": "boot_payloads.py", "cls_or_fn_name": cls_name}
    import asyncio
    try:
        asyncio.run(process_worker._worker_loop(
            requests, responses, pointers, None, "spmd"))
    finally:
        telemetry.JIT.uninstall()
        sys.modules.pop("boot_payloads", None)
    out = []
    while not responses.empty():
        out.append(responses.get_nowait())
    return out


def test_the_boot_is_one_span_tree_whose_record_keeps_every_key(
        monkeypatch):
    sent = _boot_in_process(monkeypatch, "Compiles")
    done = next(m for m in sent if m.get("warmup") == "done")
    boot = done["boot"]
    # every key of before, with its meaning, and the new ones
    for key in ("rank_accel_s", "rank_import_s", "rank_init_s",
                "rank_warmup_s") + RANK_BOOT_DETAIL:
        assert key in boot, key
    assert boot["rank_accel_s"] == pytest.approx(
        boot["rank_jax_import_s"] + boot["rank_tpu_open_s"], abs=1e-9)
    # the constructor's program and the warm-up's, compiled here
    assert boot["rank_programs"] >= 2
    assert boot["rank_programs_compiled"] <= boot["rank_programs"]
    assert 0.0 < boot["rank_jit_s"] <= boot["rank_init_s"] \
        + boot["rank_warmup_s"]
    # the tree shipped to the pool before the state op that ends the boot
    spans = [m["span"] for m in sent if m.get("op") == "span"]
    assert sent.index(done) > max(i for i, m in enumerate(sent)
                                  if m.get("op") == "span")
    root = next(s for s in spans if s["name"] == "rank.boot")
    assert root["attrs"]["rank_accel_s"] == boot["rank_accel_s"]
    phases = {s["name"]: s for s in spans if s["parent_id"] == root["span_id"]}
    assert set(phases) == {"boot.jax_import", "boot.tpu_open", "boot.import",
                           "boot.init", "boot.warmup"}
    for name, s in phases.items():
        key = "rank_" + name.split(".", 1)[1] + "_s"
        assert boot[key] == pytest.approx(s["end_mono"] - s["start_mono"])
    jits = {s["attrs"]["program"]: s for s in spans if s["name"] == "jit"}
    assert jits["_built"]["parent_id"] == phases["boot.init"]["span_id"]
    assert jits["_warmed"]["parent_id"] == phases["boot.warmup"]["span_id"]


def test_boot_record_picks_the_slowest_rank_by_its_phases_only():
    class Rank:
        def __init__(self, **boot):
            self.boot = boot

    pool = ProcessPool.__new__(ProcessPool)
    slow = dict.fromkeys(RANK_BOOT_PHASES, 2.0)
    # details that overlap the phases, and counts, weigh nothing
    busy = {**dict.fromkeys(RANK_BOOT_PHASES, 1.0), "rank_jit_s": 4.0,
            "rank_jax_import_s": 0.9, "rank_tpu_open_s": 0.1,
            "rank_programs": 900, "rank_programs_compiled": 900}
    pool.workers = [Rank(**busy, ready_mono=5.0),
                    Rank(**slow, ready_mono=7.0)]
    rec = pool.boot_record()
    assert rec == {**slow, "ready_mono": 7.0}


# -- the benchmark's metrics ---------------------------------------------------

NEW_METRICS = {"deploy_jax_import_s": "boot.rank_jax_import_s",
               "deploy_tpu_open_s": "boot.rank_tpu_open_s",
               "deploy_jit_s": "boot.rank_jit_s",
               "deploy_programs_compiled": "boot.rank_programs_compiled"}


def test_deploy_metrics_are_declared_for_the_four_cells_and_read_the_span(
        monkeypatch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    declared = {m["name"]: m for m in bench["per_layer"]}
    monkeypatch.syspath_prepend(BENCH)
    spec = importlib.util.spec_from_file_location(
        "deploy_span_attr_under_test",
        os.path.join(BENCH, "readers", "deploy_span_attr.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    attrs = {attr: 1.5 + i for i, attr in enumerate(NEW_METRICS.values())}
    ring = [{"name": "client.deploy", "attrs": attrs}]
    for i, (name, attr) in enumerate(NEW_METRICS.items()):
        m = declared[name]
        assert m["workloads"] == cells
        assert (m["layer"], m["source"], m["moves"], m["better"]) == (
            "fabric: deploy", "program_span", "setup_s", "lower")
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec == {"reader": "deploy_span_attr",
                        "args": {"attrs": [attr]}}
        assert reader.read({}, **spec["args"], ring=ring) == 1.5 + i
        # a program without the attribute (the parent) reads nothing
        assert reader.read({}, **spec["args"], ring=[
            {"name": "client.deploy", "attrs": {}}]) is None

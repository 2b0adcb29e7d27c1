"""The harness's own tests: ``python -m pytest benchmark/tests -q``.

Not part of the repo's tier-1 suite. Everything here runs on the CPU at
small sizes; nothing describes a topology or touches a TPU at import time.
The rehearsals are labelled as such by ``run.py`` and print no device metric.
"""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import bench_flops      # noqa: E402
import bench_traffic    # noqa: E402
import bench_weights    # noqa: E402
import run as R         # noqa: E402
import trace_reduce     # noqa: E402


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# -- trace reduction, on a synthetic trace -----------------------------------

def synthetic_planes():
    """Two devices, times in ns. Device 0: a while (0-100) holding two
    fusions (10-40, 50-90), then an all-gather (120-160) of which 140-160
    runs beside a fusion (140-180); a kernel 200-230. Device 1: the same
    shifted by 1000."""
    def plane(name, o):
        ops = [("while.1", o + 0, 100), ("fusion.1", o + 10, 30),
               ("fusion.2", o + 50, 40), ("all-gather.3", o + 120, 40),
               ("fusion.4", o + 140, 40), ("decode_attention.7", o + 200, 30)]
        mods = [("jit__decode_block(1)", o + 0, 100),
                ("jit__prefill(2)", o + 120, 110)]
        return {"name": name, "lines": {"XLA Ops": ops, "XLA Modules": mods}}
    return [plane("/device:TPU:0", 0), plane("/device:TPU:1", 1000)]


QUERIES = [{"id": "coll", "line": "ops", "pattern": "all-gather|all-reduce"},
           {"id": "kernel", "line": "ops", "pattern": "decode_attention"},
           {"id": "decode", "line": "modules", "pattern": "_decode_block"},
           {"id": "kernel_in_prefill", "line": "ops",
            "pattern": "decode_attention", "within": "_prefill"},
           {"id": "kernel_in_decode", "line": "ops",
            "pattern": "decode_attention", "within": "_decode_block"}]


def test_interval_arithmetic():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 7)]) == \
        [[0, 3], [5, 7]]
    assert trace_reduce.length([[0, 3], [5, 7]]) == 5


def test_self_time_takes_children_out_of_their_parent():
    evs = {n: (self_ns, leaf) for n, _, _, self_ns, leaf in
           trace_reduce.self_times(synthetic_planes()[0]["lines"]["XLA Ops"])}
    assert evs["while.1"] == (30.0, False)        # 100 - 30 - 40
    assert evs["fusion.1"] == (30.0, True)


def test_busy_idle_and_kernel_matching():
    out = trace_reduce.reduce_planes(synthetic_planes(), QUERIES)
    # leaves: 10-40, 50-90, 120-180 (all-gather and fusion.4 joined), 200-230
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(160e-9)
    # first to last event over both planes: 0 .. 1230
    assert out["window_s"] == pytest.approx(1230e-9)
    q = out["queries"]
    assert q["coll"]["seconds"] == pytest.approx(40e-9)
    assert q["kernel"] == {"count": 1.0, "seconds": pytest.approx(30e-9)}
    assert q["decode"]["count"] == 1.0
    # the kernel's event starts inside the prefill's run, not the block's
    assert q["kernel_in_prefill"]["count"] == 1.0
    assert q["kernel_in_decode"]["count"] == 0.0
    assert q["decode"]["seconds"] == pytest.approx(100e-9)
    top = dict(out["breakdown"]["device_ops"])
    assert top["fusion.2"] == pytest.approx(40e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["fusion.2 -> all-gather.3"] == pytest.approx(30e-9)
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_an_operation_is_named_by_what_it_is_and_makes():
    line = ("%fusion.16 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) fusion(u32[2]"
            "{0:T(128)} %key.1), kind=kLoop, calls=%fused_computation.3")
    assert trace_reduce.short_name(line) == "%fusion.16 fusion (tuple)"
    assert trace_reduce.short_name(
        '%call.15 = bf16[16,8,128]{2,1,0} custom-call(bf16[1] %a), '
        'custom_call_target="tpu_custom_call"') == \
        "%call.15 custom-call:tpu_custom_call bf16[16,8,128]"
    assert trace_reduce.short_name("fusion.2") == "fusion.2"


def test_readers_return_nothing_without_a_trace():
    cell = R.resolve("mistral7b-chat-closed",
                     os.path.join(ROOT, "BENCHMARK.json"), BENCH)
    ctx = {"config": cell["config"], "mix": cell["mix"], "chips": 1,
           "peak": bench_flops.peaks("TPU v5 lite"), "trace": {},
           "window": {"open": {"now": 1.0, "tokens_generated": 5},
                      "close": {"now": 1.0, "tokens_generated": 5}},
           "records": [], "flops": bench_flops}
    for m in cell["per_layer"] + cell["end_to_end"]:
        assert R.load_file(m["reader_file"]).read(
            ctx, **m.get("args", {})) is None, m["name"]


def test_readers_on_a_worked_trace():
    c = cfg("mistral-7b-v0.3-l16")
    peak = bench_flops.peaks("TPU v5 lite")
    # one request: prompt 100, 11 tokens, first at t=1, one more each 0.1 s
    log = [{"t_first": 1.0, "t_out": 2.0, "n": 11, "prompt_len": 100}]
    trace = {"busy_s": 0.8, "window_s": 1.0, "log": log,
             "c0": {"now": 0.95, "decode_steps": 0, "tokens_generated": 0,
                    "admitted_total": 0, "slots": 16},
             "c1": {"now": 1.55, "decode_steps": 8, "tokens_generated": 6,
                    "admitted_total": 1, "slots": 16},
             "queries": {"decode_block": {"count": 2, "seconds": 0.32},
                         "prefill": {"count": 1, "seconds": 0.2},
                         "decode_attn": {"count": 8, "seconds": 0.001}}}
    ctx = {"config": c, "chips": 1, "peak": peak, "trace": trace,
           "window": {"open": {"now": 0.95}, "close": {"now": 1.55}},
           "records": [], "flops": bench_flops}

    def read(name, **kw):
        return R.load_file(os.path.join(BENCH, "readers", name + ".py")) \
            .read(ctx, **kw)
    assert read("idle_share") == pytest.approx(20.0)
    assert read("module_share", query="prefill") == pytest.approx(25.0)
    assert read("module_step_ms", query="decode_block",
                steps_per_run_key="decode_block") == pytest.approx(20.0)
    assert read("slots_busy") == pytest.approx(100 * 5 / (8 * 16))
    # tokens 1..5 each hold 0.1 s, with 101 .. 105 rows live
    assert read("kv_live_share") == pytest.approx(
        100 * 0.1 * (101 + 102 + 103 + 104 + 105) / (0.6 * 16 * 2048))
    # prefill at 1.0 and tokens 1..5 (at 1.1 .. 1.5) fall in [0.95, 1.55]
    flops = bench_flops.prefill_flops(c, 100) \
        + bench_flops.decode_flops(c, 100, 1, 5)
    assert read("serve_mfu") == pytest.approx(
        100 * flops / (0.6 * 197e12))
    rows = 5 * 100 + (1 + 5) * 5 / 2
    least = rows * 16 * 2 * 8 * 128 * 2 / 819e9
    assert read("decode_attn_roofline", query="decode_attn") == \
        pytest.approx(100 * (least / 0.6) / (0.001 / 1.0))


# -- operations and bytes, against hand-worked values -------------------------

def test_flops_mistral_by_hand():
    c = cfg("mistral-7b-v0.3-l16")
    # one layer: q and o 4096x4096, k and v 4096x1024, three 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert bench_flops.layer_matmul_params(c) == layer
    assert bench_weights.param_count(c) == \
        16 * (layer + 2 * 4096) + 2 * 32768 * 4096 + 4096 == 3_758_231_552
    head = 2 * 4096 * 32768
    assert bench_flops.token_flops(c, 0) == 2 * 16 * layer + head
    # attention against 1,000 rows: 4 * heads * head_dim per row and layer
    assert bench_flops.token_flops(c, 1000) - bench_flops.token_flops(c, 0) \
        == 4 * 16 * 32 * 128 * 1000
    assert bench_flops.prefill_flops(c, 3) == \
        3 * 2 * 16 * layer + 4 * 16 * 32 * 128 * (1 + 2 + 3) + head
    # tokens 1 and 2 of a 10-token prompt attend to 11 and 12 rows
    assert bench_flops.decode_flops(c, 10, 1, 2) == \
        2 * (2 * 16 * layer + head) + 4 * 16 * 32 * 128 * (11 + 12)
    cost = bench_flops.decode_attention_cost(c, 1000)
    assert cost["bytes"] == 1000 * 16 * 2 * 8 * 128 * 2 == 65_536_000
    assert bench_flops.roofline_seconds(cost, bench_flops.peaks(
        "TPU v5 lite"))[1] == "memory"


def test_flops_mixtral_by_hand():
    c = cfg("mixtral-8x7b-l4")
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    # a token passes through its two experts and the router, not all eight
    assert bench_flops.layer_matmul_params(c) == \
        attn + 2 * 3 * 4096 * 14336 + 4096 * 8
    assert bench_weights.param_count(c) == 4 * (
        attn + 8 * 3 * 4096 * 14336 + 4096 * 8 + 2 * 4096) \
        + 2 * 32000 * 4096 + 4096 == 6_067_228_672


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        bench_flops.peaks("TPU v9")
    assert bench_flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


# -- traffic -------------------------------------------------------------------

def test_closed_deck_reproduces_and_keeps_the_same_sizes():
    mix = bench_traffic.load("chat-closed", BENCH)
    def draw(seed, n=128):
        d = bench_traffic.Deck(mix, seed, 32768)
        return [d.draw() for _ in range(n)]
    a, b, c = draw(2 ** 31 + 5), draw(2 ** 31 + 5), draw(6)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    # two passes through the pool: every seed sends the same set of sizes
    assert sizes(a) == sizes(c) == sorted(bench_traffic.size_pool(mix) * 2)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] != \
        [(len(r["prompt"]), r["max_new"]) for r in c]
    assert all(4 <= len(r["prompt"]) <= 1024 and r["max_new"] >= 4
               and min(r["prompt"]) >= 1 and max(r["prompt"]) < 32768
               for r in a)
    assert max(len(r["prompt"]) + r["max_new"] for r in a) == \
        bench_traffic.longest_request(mix) <= mix["total_max"] == 2048


def test_lengths_keep_the_published_means():
    """The mix names its source's means; the pool's stratified quantiles of
    min + exponential keep them to a hundredth, and a pair over the total
    has its output cut."""
    mix = bench_traffic.load("chat-closed", BENCH)
    assert "ShareGPT" in mix["source"] and "161.31" in mix["source"]
    pool = bench_traffic.size_pool(mix)
    assert len(pool) == mix["pool"] == 64
    mean = lambda v: sum(v) / len(v)
    assert mean([p for p, _ in pool]) == pytest.approx(161.31, rel=0.01)
    assert mean([o for _, o in pool]) == pytest.approx(337.99, rel=0.01)
    q = bench_traffic.length_quantiles({"mean": 10, "min": 4, "max": 12}, 4)
    assert q == [5, 7, 10, 12]          # 4 - 6 ln(1 - u), the last one capped
    cut = bench_traffic.size_pool({"pool": 2, "total_max": 20, "prompt": {
        "mean": 10, "min": 10, "max": 10}, "output": {
        "mean": 30, "min": 30, "max": 30}})
    assert cut == [(10, 10), (10, 10)]


# -- BENCHMARK.json against the contract, and the files behind each name -----

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_to_the_contract():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in b[k]}) == len(b[k])
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in b["configs"]:
        assert c["file"].startswith("benchmark/")
        assert any(w["config"] == c["name"] for w in b["workloads"])
        assert not any(k.endswith(("_dim", "_rank")) or "size" in k
                       for k in c["reduced"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        # every cell: set-up, one more end-to-end metric, one per-layer one
        assert any(w["name"] in m.get("workloads", cells)
                   for m in b["end_to_end"] if m["name"] != "setup_s")
        assert any(w["name"] in m.get("workloads", cells)
                   for m in b["per_layer"])


@pytest.mark.parametrize("workload", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves_its_files(workload):
    """A configuration, a mix, a metric or a limit is a file found by its
    name: a later PR adds files and entries and edits nothing."""
    cell = R.resolve(workload, os.path.join(ROOT, "BENCHMARK.json"), BENCH)
    c = cell["config"]
    assert os.path.basename(cell["runner_file"]) == c["kind"] + ".py"
    assert callable(R.load_file(cell["runner_file"]).run)
    assert c["source"].startswith("https://")
    entry = next(e for e in cell["bench"]["configs"]
                 if e["name"] == cell["cell"]["config"])
    assert entry["source"] == c["source"]
    assert set(entry["reduced"]) == set(c["reduced"]) == set(c["published"])
    assert cell["mix"]["kind"] == "closed" and cell["mix"]["source"]
    assert bench_traffic.longest_request(cell["mix"]) <= c["engine"]["max_len"]
    assert cell["mix"]["prompt"]["max"] <= max(c["engine"]["prefill_buckets"])
    assert cell["per_layer"] and all(
        callable(R.load_file(m["reader_file"]).read)
        for m in cell["per_layer"] + cell["end_to_end"])
    assert {"setup_s", "serve_tok_s"} <= {m["name"]
                                          for m in cell["end_to_end"]}
    assert cell["limits"]["sample_requests"] >= 6
    for number, lim in cell["limits"]["compare"].items():
        # room on both sides, the more of it above the lower reading
        assert lim["lower"] < lim["limit"] < lim["upper"], number
        assert lim["upper"] >= 3 * lim["lower"], number


def test_a_new_metric_needs_only_new_files(tmp_path):
    """The resolution finds a cell whose files only a second data root
    holds (as the rehearsal's own are found), and a runner by the
    configuration's kind: a kind with no runner file is refused by name."""
    cell = R.resolve("tiny-dense.closed", os.path.join(
        R.REHEARSAL_DATA, "BENCHMARK.json"), R.REHEARSAL_DATA)
    assert cell["mix"]["callers"] == 4
    assert [m["name"] for m in cell["per_layer"]] == ["fabric_ms.serve"]
    with pytest.raises(R.BenchFailure):
        R.resolve("no-such-cell", os.path.join(ROOT, "BENCHMARK.json"), BENCH)
    other = json.loads(json.dumps(cell["bench"]))
    cfg_file = tmp_path / "train.json"
    cfg_file.write_text(json.dumps({**cell["config"], "kind": "train"}))
    other["configs"][0]["file"] = os.path.relpath(cfg_file, ROOT)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(other))
    with pytest.raises(R.BenchFailure, match="runners/train.py"):
        R.resolve("tiny-dense.closed", str(tmp_path / "BENCHMARK.json"),
                  R.REHEARSAL_DATA)


# -- what the window computes -------------------------------------------------

def load_runner():
    return R.load_file(os.path.join(BENCH, "runners", "serve.py"))


def test_end_to_end_readers_on_a_worked_window():
    """The rate is the counter's difference over the time between the two
    readings; a tail is the tail of all requests sent, a failed one last."""
    recs = [{"ttft": 0.1 * i, "engine_ttft": 0.05} for i in range(1, 20)]
    recs.append({"ttft": float("inf"), "engine_ttft": None})
    ctx = {"records": recs, "window": {
        "open": {"now": 10.0, "tokens_generated": 1000},
        "close": {"now": 60.5, "tokens_generated": 21200}, "setup_s": 31.5}}

    def read(name, **kw):
        return R.load_file(os.path.join(BENCH, "readers", name + ".py")) \
            .read(ctx, **kw)
    assert read("counter_rate", counter="tokens_generated") == \
        pytest.approx(400.0)
    assert read("window_value", key="setup_s") == 31.5
    q = dict(field="ttft", scale=1000.0)
    assert read("record_quantile", q=0.5, **q) == pytest.approx(1000.0)
    assert read("record_quantile", q=0.9, **q) == pytest.approx(1800.0)
    assert read("record_quantile", q=0.95, **q) == pytest.approx(1900.0)
    ctx["records"] = recs + [recs[-1]]                    # 2 of 21 failed
    assert read("record_quantile", q=0.95, **q) == float("inf")


def test_judge_holds_every_number_to_its_limit():
    lim = {"failed_requests": 0, "logit_gap_max": 0.16}
    assert R.judge({"failed_requests": 0, "logit_gap_max": 0.16}, lim)
    assert not R.judge({"failed_requests": 0, "logit_gap_max": 0.17}, lim)
    assert not R.judge({"failed_requests": 1, "logit_gap_max": 0.01}, lim)
    assert not R.judge({"failed_requests": 0,
                        "logit_gap_max": float("nan")}, lim)


def test_sample_holds_the_longest_finished_and_follows_the_seed():
    S = load_runner()
    recs = [{"k": k, "ok": True, "cut": False, "prompt": [1] * (10 + k % 7),
             "n": 5} for k in range(40)]
    recs.append({"k": 40, "ok": True, "cut": True, "prompt": [1] * 99, "n": 1})
    a = S.draw_sample(recs, 5, 6)
    assert a == S.draw_sample(recs, 5, 6) and len(a) == 6
    assert len(a[0]["prompt"]) == 16 and not any(r["cut"] for r in a)
    assert [r["k"] for r in a] != [r["k"] for r in S.draw_sample(recs, 6, 6)]


# -- the run itself, rehearsed on the CPU -------------------------------------

def rehearse(workload, seed, *more):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
         "--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", "0", *more], capture_output=True, text=True, timeout=900,
        env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("workload,seed", [
    ("tiny-dense.closed", 2 ** 31 + 12345), ("tiny-moe.closed", 7)])
def test_cpu_rehearsal_is_correct_and_prints_no_device_metric(workload, seed):
    out, err = rehearse(workload, seed)
    assert out["correct"] is True, out["compared"]
    assert out["rehearsal"] is True
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert out["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    lines = [ln for ln in err.strip().splitlines() if ln]
    assert all(ln.startswith("[REHEARSAL on the CPU") for ln in lines)
    assert lines[-1].split("] ")[1].startswith("compared lo")
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload,number", [
    ("tiny-dense-altered.closed", "logit_gap_max"),
    ("tiny-moe-altered.closed", "logit_gap_p97_decided"),
    ("tiny-dense-miscounted.closed", "token_count_gap")])
def test_a_fault_under_the_timed_path_is_not_correct(workload, number):
    """A token altered where it is produced: each serving cell's own number
    of the gap sees it. A token counter that counts a tenth too many: the
    count held against the replies' own times sees it."""
    out, _ = rehearse(workload, 9)
    assert out["correct"] is False
    c = out["compared"]
    assert c[number]["value"] > c[number]["limit"]
    assert c["failed_requests"]["value"] == 0


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files: non-zero exit, nothing on standard output."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral7b-chat-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        cwd=tmp_path, env=env)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("workload,number", [
    ("tiny-dense.closed", "logprob_err_mean"),
    ("tiny-moe.closed", "logit_gap_p97_decided")])
def test_the_int8_control_put_in_the_programs_place_is_not_correct(workload,
                                                                   number):
    """The control of "How correct is decided", at a size a test can hold,
    through the harness's own comparison: ``limits.py`` deploys the cell,
    and on each seed judges the program's numbers and the int8 control's
    with ``run.judge`` against the cell's limits. It exits 0 only if the
    program is correct on every seed and the control on none."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "limits.py"), "--rehearse",
         "--workload", workload, "--seeds", "3,2147483999,78",
         "--seconds", "2"], capture_output=True, text=True, timeout=900,
        env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    summary = rows.pop()
    assert summary["program_correct_on"] == 3 == len(rows)
    assert summary["control_correct_on"] == 0
    for r in rows:
        assert r["control"][number] > r["limits"][number] > \
            r["program"][number]
    os.remove(os.path.join(ROOT, "chiprun_out", f"limits_{workload}.json"))

"""The benchmark files that ``glm-5-ep16-l6`` brings (ISSUE 35), walked on
the CPU at tiny widths, as ``test_benchmark_mla_moe.py`` walks Kimi-VL's:
the configuration file against the catalog's row; the service class built
from a configuration dict of its kind (``rehearse``), warmed, driven past
``index_topk`` and finished against the benchmark's own copy of the
reference; the seeded weights against the reference's slices; the two new
readers fed a hand-made ``ctx``; the FLOP, byte and parameter counts against
sums written out by hand. No device metric is read here.
"""

import copy
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.level("unit")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "glm5-longdoc-closed"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench_path():
    """The benchmark's modules import each other by bare name, as its runs
    do (``run.py`` puts its directory first)."""
    sys.path.insert(0, BENCH)
    yield BENCH
    sys.path.remove(BENCH)
    for name in [n for n, m in sys.modules.items()
                 if getattr(m, "__file__", None)
                 and os.path.dirname(m.__file__) == BENCH]:
        del sys.modules[name]


@pytest.fixture(scope="module")
def full():
    with open(os.path.join(BENCH, "configs", "glm-5-ep16-l6.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(full):
    """The configuration's own keys at the widths of ``tests/test_glm5.py``:
    1 dense + 2 expert layers, experts 2..5 of 8 held, top-3, an indexer of
    8 heads that keeps 8 keys."""
    cfg = copy.deepcopy(full)
    cfg.update(hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=24,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
               index_n_heads=8, index_head_dim=16, index_topk=8,
               n_routed_experts=4, router_width=8, held_first=2,
               num_experts_per_tok=3, n_shared_experts=1,
               num_hidden_layers=3, vocab_size=256,
               engine={"slots": 4, "max_len": 64, "prefill_buckets": [16, 32],
                       "decode_block": 4})
    return cfg


# -- the configuration file ---------------------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "GLM-5")


REDUCED = {"num_hidden_layers": (78, 6), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (256, 16), "vocab_size": (154880, 19360),
           "num_nextn_predict_layers": (1, 0)}


def test_configuration_carries_the_published_widths(full):
    # every width as published, whatever the catalog file says of the rest
    assert [full[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "index_n_heads", "index_head_dim", "index_topk",
        "moe_intermediate_size", "num_experts_per_tok", "intermediate_size",
        "routed_scaling_factor", "n_shared_experts")] == [
        6144, 64, 192, 64, 256, 2048, 512, 32, 128, 2048, 2048, 8, 12288,
        2.5, 1]
    assert full["router_width"] == 256 and full["held_first"] == 0
    assert full["published"] == {k: v[0] for k, v in REDUCED.items()}
    assert {k: full[k] for k in REDUCED} == {k: v[1]
                                             for k, v in REDUCED.items()}
    assert set(full["reduced"]) == set(REDUCED)
    assert full["kind"] == "serve" and "16 chips share each layer" in \
        full["deployment"]
    assert full["engine"] == {"slots": 16, "max_len": 9728,
                              "prefill_buckets": [8192], "decode_block": 8}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["configs"][-1]
    assert entry["name"] == full["name"] == "glm-5-ep16-l6"
    assert set(entry["reduced"]) == set(REDUCED)
    assert entry["file"] == "benchmark/configs/glm-5-ep16-l6.json"
    assert entry["source"] == full["source"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, full["name"], "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    # the twelve steadiness runs left ``ttft_p50_ms`` alone of the three
    # serving metrics on the cell (PERF.md section 4), and a per-layer metric
    # lists only cells that report the end-to-end metric it moves
    moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert listed == {
        "ttft_p50_ms", "fabric_ms.serve", "fabric_pod_ms.serve",
        "admit_wait_ms", "admit_queue_ms", "admit_prefill_ms",
        "prefill_share", "deploy_pod_boot_s", "deploy_rank_boot_s",
        "deploy_poll_slack_s", "dsa_selected_share",
        "step_mfu.serve_dsa_moe", "deploy_jax_import_s", "deploy_tpu_open_s",
        "deploy_jit_s", "deploy_programs_compiled",
        "mla_prefill_attn_roofline"}
    assert {moved[name] for name in listed - {"ttft_p50_ms"}} == {
        "ttft_p50_ms", "setup_s"}
    # PR 39's kernel share: this cell alone, the last of the list
    assert bench["per_layer"][-1] == {
        "name": "mla_prefill_attn_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "ttft_p50_ms",
        "workloads": [CELL]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("dsa_selected_share")
    assert names[at + 1] == "step_mfu.serve_dsa_moe"
    assert bench["per_layer"][at] == {
        "name": "dsa_selected_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "attention: selection",
        "moves": "ttft_p50_ms", "workloads": [CELL]}


def test_configuration_is_the_catalogs_row_but_for_the_reduced_keys(full):
    row = _catalog_row()
    assert full["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if full.get(k, "?") != v}
    assert differ == set(REDUCED)
    assert {k: row["config"][k] for k in REDUCED} == full["published"]


def test_the_mix_is_one_bucket_of_long_documents(bench_path, full):
    import bench_traffic
    mix = bench_traffic.load("longdoc-closed")
    assert (mix["kind"], mix["callers"], mix["pool"]) == ("closed", 16, 32)
    pool = bench_traffic.size_pool(mix)
    assert min(p for p, _ in pool) > 6144 and max(p for p, _ in pool) == 8192
    assert all(768 <= n <= 1536 for _, n in pool)
    assert bench_traffic.longest_request(mix) == 9728 \
        == full["engine"]["max_len"]
    # every context of a decode step is past index_topk, 3 to 4.75 times
    assert min(p for p, _ in pool) > 3 * full["index_topk"]
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert limits["sample_requests"] == 8
    for spec in (limits["token_count_gap"], *limits["compare"].values()):
        assert spec["lower"] < spec["limit"] < spec["upper"] and spec["why"]


def test_bytes_and_parameters_are_the_hand_sums(bench_path, full, tiny):
    import bench_weights_dsa_moe as W
    svc = _load(os.path.join(BENCH, "services", "glm5.py"), "svc_count")
    # the published widths: ISSUE 35's arithmetic, with the norms
    attn = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
            + 16384 * 6144 + 2048 * 4096 + 6144 * 128 + 6144 * 32
            + 2048 + 512 + 2 * 6144 + 2 * 128)
    assert attn == 174_406_400
    expert = 3 * 6144 * 2048
    router = 6144 * 256 + 256
    expert_layer = attn + router + expert + 16 * expert
    dense_layer = attn + 3 * 6144 * 12288
    total = 2 * 19360 * 6144 + 6144 + dense_layer + 5 * expert_layer
    count = W.param_count(full)
    assert count["params"] == total == 4_727_340_800
    assert svc.program_config(full, 9728).param_count() == total
    b = full["bytes"]
    assert b["params"] == total and b["weights_bf16"] == 2 * total
    assert b["expert_layer"] == 2 * expert_layer == 1_635_416_064
    assert b["dense_layer"] == 2 * dense_layer
    assert b["expert_layer_whole_would_be"] == 2 * (expert_layer
                                                    + 240 * expert)
    assert b["cache_bytes"] == 16 * 9728 * 6 * (576 + 128) * 2 \
        == b["latent_bytes"] + b["index_key_bytes"]
    # tiny
    t_attn = (64 * 24 + 24 * 4 * 24 + 64 * 40 + 32 * 4 * 40 + 4 * 24 * 64
              + 24 * 8 * 16 + 64 * 16 + 64 * 8 + 24 + 32 + 128 + 32)
    t_moe = t_attn + 64 * 8 + 8 + 3 * 64 * 32 + 4 * 3 * 64 * 32
    assert W.param_count(tiny)["params"] == (
        2 * 256 * 64 + 64 + t_attn + 3 * 64 * 128 + 2 * t_moe)
    assert svc.program_config(tiny, 64).param_count() == \
        W.param_count(tiny)["params"]


def test_flops_are_the_hand_sums(bench_path, tiny):
    import bench_flops_dsa_moe as F
    attn = (64 * 24 + 24 * 4 * 24 + 64 * 40 + 32 * 4 * 40 + 4 * 24 * 64
            + 24 * 8 * 16 + 64 * 16 + 64 * 8)
    dense = attn + 3 * 64 * 128
    moe = attn + 64 * 8 + 3 * 64 * 32             # router over 8, 1 shared
    assert F.layer_matmul_params(tiny) == {"dense": dense, "moe": moe}
    stack = dense + 2 * moe
    assert F.stack_matmul_params(tiny) == stack
    assert F.routed_pair_flops(tiny) == 2 * 3 * 64 * 32
    assert F.expected_pairs_per_token(tiny) == 2 * 3 * 4 / 8
    scored, attended = F.key_flops(tiny)
    assert scored == 2 * 3 * 8 * 16 and attended == 2 * 3 * 4 * (16 + 8 + 24)
    # 5 queries seeing 6..10 keys: all scored, min(., 8) attended
    assert F.context_flops(tiny, 6, 5) == scored * (6 + 7 + 8 + 9 + 10) \
        + attended * (6 + 7 + 8 + 8 + 8)
    assert F.context_flops(tiny, 1, 3) == (scored + attended) * 6
    assert F.context_flops(tiny, 20, 2) == scored * 41 + attended * 16
    assert F.context_flops(tiny, 4, 0) == 0.0
    head = 2 * 64 * 256
    pairs = F.expected_pairs_per_token(tiny) * F.routed_pair_flops(tiny)
    assert F.prefill_flops(tiny, 12) == pytest.approx(
        12 * (2 * stack + pairs) + F.context_flops(tiny, 1, 12) + head)
    # tokens 1..3 of a request with a prompt of 5 see 6, 7, 8 positions
    assert F.decode_flops(tiny, 5, 1, 3) == 3 * (2 * stack + head) \
        + F.context_flops(tiny, 6, 3)
    assert F.decode_flops(tiny, 5, 4, 3) == 0.0


# -- weights, service, reference ----------------------------------------------

def test_seeded_tree_is_the_references_slices(bench_path, tiny):
    import jax

    import bench_weights as W0
    import bench_weights_dsa_moe as W
    from kubetorch_tpu.models.mla import mla_moe_init
    svc = _load(os.path.join(BENCH, "services", "glm5.py"), "svc_w")
    root = W0.root_key(2 ** 31 + 5)
    params = jax.jit(lambda r: W.init_params(r, tiny))(root)
    want = jax.eval_shape(lambda: mla_moe_init(
        jax.random.PRNGKey(0), svc.program_config(tiny, 64)))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(want)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    # layer 2 is expert layer 1; held expert 3 is the deployment's expert 5
    np.testing.assert_array_equal(
        params["layers"]["banks"]["w_up"][1, 3],
        W.make_slice(root, "e_up", 2, 5, tiny))
    np.testing.assert_array_equal(params["dense_layers"]["idx_wq"][0],
                                  W.make_slice(root, "idx_wq", 0, 0, tiny))
    np.testing.assert_array_equal(params["layers"]["wq_b"][1],
                                  W.make_slice(root, "wq_b", 2, 0, tiny))
    assert params["layers"]["router"].shape == (2, 64, 8)
    for name, std in (("router_bias", 0.01), ("idx_k_bias", 0.1)):
        leaf = np.asarray(params["layers"][name])
        assert leaf.dtype == np.float32 and std / 3 < leaf.std() < std * 3


def test_service_walks_warmup_generate_finish(bench_path, tiny, tmp_path):
    svc = _load(os.path.join(BENCH, "services", "glm5.py"), "svc_run")
    bench = svc.Glm5ServeBench({"config": tiny, "seed": 2 ** 31 + 77,
                                "chips": 1, "rehearse": True,
                                "run_dir": str(tmp_path)})
    try:
        bench.__kt_warmup__()
        sample = []
        for i in range(3):
            prompt = np.random.RandomState(i).randint(
                1, 256, 5 + 9 * i).tolist()
            out = bench.generate(prompt, 14)
            assert out["n"] == 14 and len(out["logprobs"]) == 14
            sample.append({"prompt": prompt, "tokens": out["tokens"],
                           "logprobs": out["logprobs"]})
        c = bench.mark()
        pairs, hits = (np.asarray(c[k]) for k in ("moe_routed_pairs",
                                                  "moe_expert_hits"))
        assert pairs.shape == hits.shape == (2, 4)      # the held experts
        assert pairs.sum() > 0 and (hits <= pairs).all()
        scored, selected = (np.asarray(c[k]) for k in (
            "dsa_rows_scored", "dsa_rows_selected"))
        assert scored.shape == selected.shape == (3,)
        assert (0 < selected).all() and (selected < scored).all()
        json.dumps(bench.report())                 # what the fabric ships
        names = ["logprob_err_p50_decided", "logit_gap_p90_decided"]
        fin = bench.finish(sample, 64, names, control=True,
                           keep_positions=True)
    finally:
        if bench.engine is not None:
            bench.engine.stop()
    check = fin["check"]
    assert check["finite"] and check["tokens_compared"] == 42
    assert set(check["numbers"]) == set(names)
    # the program in bfloat16 against float32; the int8 control is farther
    assert check["numbers"]["logprob_err_p50_decided"] < 0.05
    assert check["control"]["logprob_err_p50_decided"] \
        > check["numbers"]["logprob_err_p50_decided"]
    # positions past index_topk in 3 layers of the 3 requests
    swapped = check["swapped_keys"]
    assert swapped["pairs"] > 0 and 0 <= swapped["share_changed"] <= 1
    assert swapped["max_swapped"] <= tiny["index_topk"]
    assert "dsa_rows_scored" in fin["counters"]
    assert len(fin["log"]) == 3                    # the warm-ups are not logged


def test_reference_is_the_repos_reference(bench_path, tiny):
    """The benchmark's copy, which draws its weights a layer at a time, and
    ``tests/glm5_reference.py`` over the seeded tree: the same logits."""
    import jax
    import jax.numpy as jnp

    import bench_reference_dsa_moe as R
    import bench_weights as W0
    import bench_weights_dsa_moe as W
    from tests import glm5_reference
    svc = _load(os.path.join(BENCH, "services", "glm5.py"), "svc_ref")
    seed = 2 ** 31 + 9
    params = jax.jit(lambda r: W.init_params(r, tiny))(W0.root_key(seed))
    toks = np.random.RandomState(3).randint(1, 256, (2, 24))
    cfg = svc.program_config(tiny, 64, dtype=jnp.float32)
    head, margin, swapped = R.forward(seed, tiny, toks, probe=True)
    assert swapped.shape == (3, 2, 24) and not np.asarray(swapped)[:, :, :8].any()
    for r in range(2):
        want = glm5_reference.forward(params, toks[r], cfg)
        lp = jax.nn.log_softmax(want, -1)
        nxt = np.roll(toks[r], -1)
        np.testing.assert_allclose(
            np.asarray(head["lp_next"])[r, :-1],
            np.asarray(lp)[np.arange(24), nxt][:-1], atol=2e-4)
        np.testing.assert_array_equal(np.asarray(head["top"])[r],
                                      want.argmax(-1))
    assert np.isfinite(np.asarray(margin)).all()


def test_service_module_refuses_a_program_without_an_indexer(
        bench_path, monkeypatch, tmp_path):
    """What the driver's run of the new cell on the parent commit meets: the
    parent process loads the service's file before any deploy, and the
    parent's ``models/mla.py`` knows no indexer."""
    pkg = tmp_path / "kubetorch_tpu" / "models"
    pkg.mkdir(parents=True)
    (pkg / "mla.py").write_text("class MlaMoeConfig:\n    q_lora_rank = None\n")
    real = importlib.util.find_spec

    class Spec:
        submodule_search_locations = [str(tmp_path / "kubetorch_tpu")]

    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        Spec() if name == "kubetorch_tpu" else real(name, *a)))
    with pytest.raises(ImportError, match="index_n_heads"):
        _load(os.path.join(BENCH, "services", "glm5.py"), "svc_parent")
    monkeypatch.setattr(importlib.util, "find_spec",
                        lambda name, *a: None if name == "kubetorch_tpu"
                        else real(name, *a))
    with pytest.raises(ImportError, match="index_n_heads"):
        _load(os.path.join(BENCH, "services", "glm5.py"), "svc_none")


def test_reference_refuses_what_it_would_have_to_guess(bench_path, tiny):
    import bench_reference_dsa_moe as R
    key = dict(R.model_key(tiny))
    assert (key["n_routed_experts"], key["router_width"],
            key["held_first"]) == (4, 8, 2)
    assert key["rope_theta"] == 1000000
    for k, value in (("n_group", 8), ("scoring_func", "softmax"),
                     ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError, match=k):
            R.model_key({**tiny, k: value})


# -- the new readers, each fed a hand-made ctx --------------------------------

def _ctx(tiny, **trace):
    c0 = {"now": 100.0, "decode_steps": 40, "tokens_generated": 0,
          "moe_routed_pairs": [[0] * 4, [0] * 4],
          "dsa_rows_scored": [10, 10, 10], "dsa_rows_selected": [5, 5, 5]}
    c1 = {"now": 102.0, "decode_steps": 60, "tokens_generated": 0,
          "moe_routed_pairs": [[7, 1, 1, 1], [2, 2, 3, 3]],
          "dsa_rows_scored": [410, 410, 410],
          "dsa_rows_selected": [105, 105, 125]}
    log = [{"t_first": 100.5, "t_out": 101.5, "n": 11, "prompt_len": 5},
           {"t_first": None, "t_out": 101.0, "n": 0, "prompt_len": 9}]
    return {"config": tiny, "chips": 1,
            "peak": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8},
            "trace": {"c0": c0, "c1": c1, "log": log, "window_s": 4.0,
                      **trace}}


def _reader(name):
    return _load(os.path.join(BENCH, "readers", name + ".py"), "rd_" + name)


def test_reader_dsa_selected_share(bench_path, tiny):
    read = _reader("dsa_selected_share").read
    ctx = _ctx(tiny)
    assert read(ctx) == pytest.approx(100 * 320 / 1200)
    bare = {**ctx["trace"], "c0": {"now": 100.0}, "c1": {"now": 102.0}}
    assert read({**ctx, "trace": bare}) is None    # a program with no indexer
    assert read({**ctx, "trace": {}}) is None
    still = {**ctx["trace"], "c1": ctx["trace"]["c0"]}
    assert read({**ctx, "trace": still}) is None   # no decode step between


def test_reader_serve_mfu_dsa_moe(bench_path, tiny):
    import bench_flops_dsa_moe as F
    read = _reader("serve_mfu_dsa_moe").read
    ctx = _ctx(tiny)
    # the one request with a first token: prefilled in the window, its ten
    # decoded tokens 1..10 a tenth of a second apart; 20 routed pairs met a
    # held expert in the decode steps between the two readings
    want = (F.prefill_flops(tiny, 5) + F.decode_flops(tiny, 5, 1, 10)
            + 20 * F.routed_pair_flops(tiny))
    assert read(ctx) == pytest.approx(100 * want / (2.0 * 1e9))
    assert read({**ctx, "trace": {}}) is None
    bare = {**ctx["trace"], "c0": {"now": 100.0}, "c1": {"now": 102.0}}
    assert read({**ctx, "trace": bare}) is None    # a program with no tally


def test_the_accepted_expert_readers_read_the_held_experts(bench_path, tiny):
    """``moe_tally`` and ``moe_experts_roofline`` take the tally's own width
    and the file's D and F: over 4 held experts they keep their meaning."""
    import bench_flops
    ctx = _ctx(tiny)
    for c in (ctx["trace"]["c0"], ctx["trace"]["c1"]):
        c["moe_expert_hits"] = [[min(v, 20) for v in row]
                                for row in c["moe_routed_pairs"]]
    assert _reader("moe_tally").read(ctx, what="hit_share") == pytest.approx(
        100 * 20 / (20 * 2 * 4))
    mod = _reader("moe_experts_roofline")
    kernel = {"moe_experts": {"count": 40.0, "seconds": 0.05}}
    full_ctx = {**ctx, "flops": bench_flops,
                "trace": {**ctx["trace"], "queries": kernel}}
    cost = mod.moe_experts_cost(tiny, 20)
    assert cost["bytes"] == 20 * 3 * 64 * 32 * 2
    least = max(cost["bytes"] / 1e8, cost["flops"] / 1e9)
    assert mod.read(full_ctx, query="moe_experts") == pytest.approx(
        100 * (least / 2.0) / (0.05 / 4.0))


def test_metric_files_name_their_readers():
    for name, reader in (("dsa_selected_share", "dsa_selected_share"),
                         ("step_mfu.serve_dsa_moe", "serve_mfu_dsa_moe"),
                         ("mla_prefill_attn_roofline",
                          "mla_prefill_attn_roofline")):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == reader
        assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))


def test_every_file_of_the_cell_resolves(bench_path):
    import run
    cell = run.resolve(CELL, os.path.join(ROOT, "BENCHMARK.json"), BENCH)
    assert cell["config"]["name"] == "glm-5-ep16-l6"
    assert cell["mix"]["total_max"] == 9728
    assert os.path.basename(cell["runner_file"]) == "serve.py"
    names = {m["name"] for m in cell["per_layer"]}
    assert {"dsa_selected_share", "step_mfu.serve_dsa_moe",
            "prefill_share", "admit_prefill_ms"} <= names
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s",
                                                       "ttft_p50_ms"}

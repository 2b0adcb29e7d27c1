"""The benchmark files that ``trinity-mini-l5`` brings, walked on the CPU at
tiny widths: the configuration against the catalog row's ``config``, key for
key; the seeded weights against the reference's slices; the benchmark's own
reference against the program run in float32 over the same weights; the
service built from a configuration dict of its kind (``rehearse``), warmed,
driven and finished against that reference; the parameter count against the
configuration's ``bytes``. No device metric is read here.
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
CELL = "trinity-longdoc-closed"
SEED = 2 ** 31 + 77
WINDOW, FULL = "sliding_attention", "full_attention"


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
    with open(os.path.join(BENCH, "configs", "trinity-mini-l5.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(full):
    """The configuration's own keys at ``AfmoeConfig.tiny``'s widths, its
    layer pattern (a dense window layer, three window expert layers, a full
    one) kept, a window of 8."""
    cfg = copy.deepcopy(full)
    cfg.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               num_experts=8, num_experts_per_tok=3, sliding_window=8,
               vocab_size=256,
               engine={"slots": 4, "max_len": 80, "prefill_buckets": [32, 64],
                       "decode_block": 4})
    return cfg


# -- the configuration file ---------------------------------------------------

def _catalog_row():
    """The ``config`` of the catalog's row ``Trinity-Mini`` (the
    ``model-configs`` guide's architectures.jsonl), key for key."""
    layer_types = ([WINDOW] * 3 + [FULL]) * 8
    return {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": layer_types, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True, "vocab_size": 200192}


def test_configuration_is_the_catalog_row_but_for_the_cut(full):
    row = _catalog_row()
    cut = ("num_hidden_layers", "num_dense_layers", "layer_types")
    assert {k: full[k] for k in row if k not in cut} == {
        k: v for k, v in row.items() if k not in cut}
    assert full["published"] == {k: row[k] for k in cut}
    # the published layers 0, 4, 5, 6, 7
    assert full["layer_types"] == [row["layer_types"][i]
                                   for i in (0, 4, 5, 6, 7)]
    assert (full["num_hidden_layers"], full["num_dense_layers"]) == (5, 1)
    assert set(full["reduced"]) == set(cut)
    assert full["source"] == ("https://huggingface.co/arcee-ai/Trinity-Mini/"
                              "blob/main/config.json")
    assert full["engine"] == {"slots": 16, "max_len": 9728,
                              "prefill_buckets": [8192], "decode_block": 8}
    for part in ("qk_norm", "rope_on_window_layers_only", "attention_gate",
                 "post_branch_norms", "embedding_scale"):
        assert "afmoe modeling code, not a config key" in \
            full["assumed"][part]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == full["name"])
    assert entry["reduced"] == list(cut)
    assert entry["file"] == "benchmark/configs/trinity-mini-l5.json"
    assert entry["source"] == full["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        full["name"], "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # appended behind the five older ones
    assert bench["configs"].index(entry) == 5
    assert bench["workloads"].index(cell) == 5
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"ttft_p50_ms", "step_mfu.serve_afmoe",
            "prefill_attn_roofline.serve_afmoe", "prefill_share",
            "admit_wait_ms", "fabric_ms.serve",
            "deploy_programs_compiled"} <= listed
    for name, layer in (("step_mfu.serve_afmoe", "model step"),
                        ("prefill_attn_roofline.serve_afmoe", "kernels")):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (m["layer"], m["moves"], m["unit"], m["workloads"]) == (
            layer, "ttft_p50_ms", "%", [CELL])


def test_bytes_are_the_parameter_count(bench_path, full):
    import bench_weights_afmoe as W
    from kubetorch_tpu.models.afmoe import AfmoeConfig
    b = full["bytes"]
    assert W.param_count(full) == b["params"] == 4_241_534_720
    svc = _load(os.path.join(BENCH, "services", "trinity.py"), "svc_bytes")
    assert svc.program_config(full).param_count() == b["params"]
    assert AfmoeConfig(layer_types=tuple(full["layer_types"]),
                       num_dense_layers=1).param_count() == b["params"]
    assert b["matrices"] == 4_241_489_920
    assert b["expert_layer_matrices"] == 839_122_944
    assert b["cache_bytes"] == 16 * 9728 * 2048 + 4 * 16 * 2048 * 2048


# -- weights, the reference, the service ----------------------------------------

def test_seeded_tree_is_the_references_slices(bench_path, tiny):
    import jax

    import bench_weights_afmoe as W
    from kubetorch_tpu.models.afmoe import afmoe_init
    svc = _load(os.path.join(BENCH, "services", "trinity.py"), "svc_w")
    import bench_weights as W0
    root = W0.root_key(SEED)
    params = jax.jit(lambda r: W.init_params(r, tiny))(root)
    want = jax.eval_shape(lambda: afmoe_init(jax.random.PRNGKey(0),
                                             svc.program_config(tiny)))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    # run 1 is layers 1..3: its second layer is layer 2 of the model
    np.testing.assert_array_equal(params["runs"][1]["wq"][1],
                                  W.make_slice(root, "wq", 2, 0, tiny))
    np.testing.assert_array_equal(params["runs"][2]["banks"]["w_down"][0, 5],
                                  W.make_slice(root, "e_down", 4, 5, tiny))


def test_the_references_agree_with_the_program_in_float32(bench_path, tiny):
    """The program run in float32 over the seeded tree, the benchmark's
    reference and the tests' reference (``tests/trinity_reference.py``) give
    one forward pass: the benchmark's copy is the same mathematics."""
    import jax
    import jax.numpy as jnp

    import bench_reference_afmoe as R
    import bench_weights as W0
    import bench_weights_afmoe as W
    from kubetorch_tpu.models.afmoe import afmoe_forward
    from tests import trinity_reference as T
    svc = _load(os.path.join(BENCH, "services", "trinity.py"), "svc_ref")
    cfg = svc.program_config(tiny, dtype=jnp.float32)
    params = jax.jit(lambda r: W.init_params(r, tiny))(W0.root_key(SEED))
    toks = np.random.RandomState(4).randint(0, 256, (1, 32))
    with jax.default_matmul_precision("highest"):
        got = jax.nn.log_softmax(afmoe_forward(params, jnp.asarray(toks),
                                               cfg)[0], -1)
    ref = jax.nn.log_softmax(T.forward(cfg, params, toks[0]), -1)
    np.testing.assert_allclose(got, ref, atol=1e-4)
    out, margin = R.forward(SEED, tiny, toks)
    nxt = np.roll(toks[0], -1)
    np.testing.assert_allclose(out["lp_next"][0],
                               np.take_along_axis(np.asarray(ref),
                                                  nxt[:, None], -1)[:, 0],
                               atol=1e-4)
    assert np.isfinite(np.asarray(margin)).all()


def test_service_walks_warmup_generate_finish(bench_path, tiny, tmp_path):
    svc = _load(os.path.join(BENCH, "services", "trinity.py"), "svc_run")
    bench = svc.TrinityServeBench({"config": tiny, "seed": SEED, "chips": 1,
                                   "rehearse": True,
                                   "run_dir": str(tmp_path)})
    try:
        bench.__kt_warmup__()
        sample = []
        for i in range(3):
            prompt = np.random.RandomState(i).randint(
                0, 256, 20 + 9 * i).tolist()
            out = bench.generate(prompt, 12)
            assert out["n"] == 12 and len(out["logprobs"]) == 12
            sample.append({"prompt": prompt, "tokens": out["tokens"],
                           "logprobs": out["logprobs"]})
        c = bench.mark()
        assert c["window_rows_read"] >= 3 * 11 * 3 * 8
        assert len(c["moe_routed_pairs"]) == 4
        json.dumps(bench.report())                 # what the fabric ships
        names = ["logprob_err_p50_decided", "logit_gap_p90_decided"]
        fin = bench.finish(sample, 80, names, control=True)
    finally:
        if bench.engine is not None:
            bench.engine.stop()
    check = fin["check"]
    assert check["finite"] and check["tokens_compared"] == 36
    assert set(check["numbers"]) == set(names)
    # the program in bfloat16 against float32; the int8 control is farther
    err = "logprob_err_p50_decided"
    assert check["numbers"][err] < 0.05
    assert check["control"][err] > check["numbers"][err]
    assert "full_rows_read" in fin["counters"]
    assert len(fin["log"]) == 3              # the warm-ups are not logged


def test_service_module_refuses_a_program_without_the_family(bench_path,
                                                             monkeypatch):
    """What a run of the cell meets on a program without the family: the
    benchmark's parent process loads the service's file before any deploy,
    and it fails there at once."""
    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "kubetorch_tpu" else real(name, *a)))
    with pytest.raises(ImportError, match="models/afmoe.py"):
        _load(os.path.join(BENCH, "services", "trinity.py"), "svc_none")


@pytest.mark.parametrize("key,value", [
    ("score_func", "softmax"), ("route_norm", False), ("n_group", 2),
    ("mup_enabled", False), ("tie_word_embeddings", True),
    ("layer_types", [WINDOW, "linear_attention"] * 2 + [FULL])])
def test_reference_refuses_what_it_would_have_to_guess(bench_path, tiny, key,
                                                       value):
    import bench_reference_afmoe as R
    assert dict(R.model_key(tiny))["layer_types"][4] == FULL
    with pytest.raises(ValueError, match=key):
        R.model_key({**tiny, key: value})


# -- the reader of the prompts' sorted expert pairs --------------------------------

def test_reader_prefill_experts_roofline(bench_path, tiny):
    """The least time of the routed products of the prompts prefilled in the
    counters' window — prompt length × K × expert layers pairs for a prompt
    whose bucket sorts its pairs, 3 · D · F · 2 operations a pair, at the
    bfloat16 peak — as a rate, over the sorted kernel's self time as a rate
    over the trace's window; and the metric's entry in ``BENCHMARK.json``."""
    mod = _load(os.path.join(BENCH, "readers",
                             "prefill_experts_roofline_afmoe.py"), "rd_pexr")
    cfg = {**tiny, "engine": {**tiny["engine"],
                              "prefill_buckets": [256, 1024]}}
    log = [{"t_first": 100.5, "t_out": 101.5, "n": 11, "prompt_len": 300},
           {"t_first": 101.0, "t_out": 101.9, "n": 4, "prompt_len": 100},
           {"t_first": 99.0, "t_out": 101.0, "n": 9, "prompt_len": 700},
           {"t_first": None, "t_out": 101.0, "n": 0, "prompt_len": 900}]
    kernel = {"moe_sorted": {"count": 8.0, "seconds": 0.05}}
    trace = {"c0": {"now": 100.0}, "c1": {"now": 102.0}, "log": log,
             "window_s": 4.0, "queries": kernel}
    ctx = {"config": cfg, "chips": 1, "trace": trace,
           "peak": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}}
    # the bucket 1,024 sorts, the bucket 256 does not; one prompt before
    # the window and one that never got its first token count nothing
    assert mod.sorted_pairs(cfg, 300) == 300 * 3 * 4
    assert mod.sorted_pairs(cfg, 100) == 0
    least = 300 * 3 * 4 * 3 * 64 * 32 * 2 / 1e9
    assert mod.read(ctx, query="moe_sorted") == pytest.approx(
        100 * (least / 2.0) / (0.05 / 4.0))
    # no kernel event, no query, no prompt of the sorted path in the window,
    # no trace: nothing to read, and no error
    idle = {"moe_sorted": {"count": 0.0, "seconds": 0.0}}
    assert mod.read({**ctx, "trace": {**trace, "queries": idle}},
                    query="moe_sorted") is None
    assert mod.read({**ctx, "trace": {**trace, "queries": {}}},
                    query="moe_sorted") is None
    assert mod.read({**ctx, "trace": {**trace, "log": log[1:]}},
                    query="moe_sorted") is None
    assert mod.read({**ctx, "trace": {}}, query="moe_sorted") is None
    with open(os.path.join(BENCH, "metrics",
                           "prefill_experts_roofline.serve_afmoe.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "prefill_experts_roofline_afmoe"
    assert spec["trace_queries"] == [{"id": "moe_sorted", "line": "ops",
                                      "pattern": "kt_moe_sorted",
                                      "within": "_prefill"}]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = next(m for m in bench["per_layer"]
             if m["name"] == "prefill_experts_roofline.serve_afmoe")
    assert (m["layer"], m["moves"], m["unit"], m["source"],
            m["workloads"]) == ("kernels", "ttft_p50_ms", "%",
                                "device_trace", [CELL])

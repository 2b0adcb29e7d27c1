"""The benchmark files that ``kimi-vl-a3b-l9`` brings (ISSUE 33), walked on
the CPU at tiny widths: the service class built from a configuration dict of
its kind (``rehearse``), warmed, driven and finished against the benchmark's
own copy of the reference; the seeded weights against the reference's
slices; each new reader fed a small hand-made ``ctx``; the FLOP, byte and
parameter counts against sums written out by hand. No device metric is read
here. ``benchmark/tests`` is the harness's own suite and is left as it is.
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
    with open(os.path.join(BENCH, "configs", "kimi-vl-a3b-l9.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(full):
    """The configuration's own keys at the widths of
    ``MlaMoeConfig.tiny``: 1 dense + 2 expert layers, 8 experts top-3, 1
    shared."""
    cfg = copy.deepcopy(full)
    cfg.update(hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
               num_experts_per_tok=3, n_shared_experts=1,
               num_hidden_layers=3, vocab_size=256,
               engine={"slots": 4, "max_len": 64, "prefill_buckets": [16, 32],
                       "decode_block": 4})
    return cfg


# -- the configuration file ---------------------------------------------------

# the ``config`` of the catalog's row ``Kimi-VL-A3B-Instruct`` (the
# ``model-configs`` guide's architectures.jsonl), key for key
CATALOG = {
    "vocab_size": 163840, "max_position_embeddings": 131072,
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2, "n_routed_experts": 64,
    "ep_size": 1, "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
    "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}


def test_configuration_carries_the_published_widths(full):
    differ = {k for k, v in CATALOG.items() if full.get(k, "?") != v}
    assert differ == {"num_hidden_layers"} == set(full["reduced"])
    assert full["source"] == ("https://huggingface.co/moonshotai/"
                              "Kimi-VL-A3B-Instruct/blob/main/config.json")
    assert full["published"] == {"num_hidden_layers": 27}
    assert full["num_hidden_layers"] == 9 and full["kind"] == "serve"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == full["name"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmark/configs/kimi-vl-a3b-l9.json"
    cell = next(w for w in bench["workloads"]
                if w["name"] == "kimivl-chat-closed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        full["name"], "chat-closed", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if "kimivl-chat-closed" in m.get("workloads", ())}
    assert {"serve_tok_s", "ttft_p50_ms", "ttft_p90_ms",
            "step_mfu.serve_mla_moe", "moe_expert_hit_share",
            "moe_load_max_over_mean", "engine_host_ms_per_block",
            "decode_step_ms", "moe_experts_roofline"} <= listed
    # GQA's FLOPs and kernel are the other families'; the expert kernel's
    # roofline share came with the kernel (ISSUE 34), and the latent
    # attention, still XLA's, has none
    assert not {"step_mfu.serve", "decode_attn_roofline",
                "mla_decode_attn_roofline"} & listed
    roofline = next(m for m in bench["per_layer"]
                    if m["name"] == "moe_experts_roofline")
    assert roofline == {
        "name": "moe_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "serve_tok_s",
        "workloads": ["kimivl-chat-closed"]}


def test_bytes_and_parameters_are_the_hand_sums(bench_path, full, tiny):
    import bench_weights_mla_moe as W
    from kubetorch_tpu.models.mla import MlaMoeConfig
    # the published widths, 9 layers: ISSUE 33's arithmetic
    attn = 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304 + 4_096
    assert attn == 13_767_168
    expert_layer = attn + 64 * 8_650_752 + 17_301_504 + 131_072 + 64
    assert expert_layer == 584_847_936
    dense_layer = attn + 69_206_016
    total = 671_088_640 + 2048 + dense_layer + 8 * expert_layer
    assert W.param_count(full) == total == 5_432_847_360
    assert MlaMoeConfig(n_layers=9).param_count() == total
    b = full["bytes"]
    assert b["params"] == total and b["weights_bf16"] == 2 * total
    assert b["expert_layer"] == 2 * expert_layer
    assert b["latent_bytes"] == 9 * 16 * 2048 * 576 * 2 == 339_738_624
    assert b["per_head_kv_bytes_would_be"] == 9 * 32768 * 16 * 320 * 2
    # tiny: embedding and head, final norm, a dense layer, two expert layers
    t_attn = 64 * 4 * 24 + 64 * 40 + 32 + 32 * 4 * 32 + 4 * 16 * 64 + 128
    t_moe = t_attn + 8 * 3 * 64 * 32 + 3 * 64 * 32 + 64 * 8 + 8
    assert W.param_count(tiny) == (2 * 256 * 64 + 64 + t_attn + 3 * 64 * 128
                                   + 2 * t_moe) == 220_208


def test_flops_are_the_hand_sums(bench_path, tiny):
    import bench_flops_mla_moe as F
    attn = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64        # 16,896
    dense = attn + 3 * 64 * 128
    moe = attn + 64 * 8 + 3 * 64 * (3 * 32 + 32)
    assert F.layer_matmul_params(tiny) == {"dense": dense, "moe": moe}
    stack = dense + 2 * moe
    assert stack == F.stack_matmul_params(tiny) == 125_440
    per_pos = 2 * 3 * 4 * (16 + 8 + 16)                             # 960
    assert F.attention_flops_per_position(tiny) == per_pos
    head = 2 * 64 * 256
    assert F.token_flops(tiny, 10) == 2 * stack + per_pos * 10 + head
    assert F.token_flops(tiny, 10, head=False) == 2 * stack + per_pos * 10
    assert F.prefill_flops(tiny, 5) == 5 * 2 * stack + per_pos * 15 + head \
        == 1_301_568
    # tokens 1..3 of a request with a prompt of 5 attend to 6, 7, 8 positions
    assert F.decode_flops(tiny, 5, 1, 3) == 3 * (2 * stack + head) \
        + per_pos * (6 + 7 + 8) == 871_104
    assert F.decode_flops(tiny, 5, 4, 3) == 0.0


# -- weights, service, reference ----------------------------------------------

def test_seeded_tree_is_the_references_slices(bench_path, tiny):
    import jax

    import bench_weights as W0
    import bench_weights_mla_moe as W
    from kubetorch_tpu.models.mla import mla_moe_init
    svc = _load(os.path.join(BENCH, "services", "kimi_vl_a3b.py"), "svc_w")
    root = W0.root_key(2 ** 31 + 5)
    params = jax.jit(lambda r: W.init_params(r, tiny))(root)
    want = jax.eval_shape(lambda: mla_moe_init(
        jax.random.PRNGKey(0), svc.program_config(tiny, 64)))
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(want)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    # layer 2 is expert layer 1: counted over the whole model
    np.testing.assert_array_equal(
        params["layers"]["banks"]["w_up"][1, 5],
        W.make_slice(root, "e_up", 2, 5, tiny))
    np.testing.assert_array_equal(params["dense_layers"]["wkv_a"][0],
                                  W.make_slice(root, "wkv_a", 0, 0, tiny))
    np.testing.assert_array_equal(params["layers"]["router_bias"][0],
                                  W.make_slice(root, "router_bias", 1, 0,
                                               tiny))
    bias = np.asarray(params["layers"]["router_bias"])
    assert bias.dtype == np.float32 and 0.003 < bias.std() < 0.03
    np.testing.assert_array_equal(params["lm_head"],
                                  W0.make_slice(root, "lm_head", 0, 0, tiny))


def test_service_walks_warmup_generate_finish(bench_path, tiny, tmp_path):
    svc = _load(os.path.join(BENCH, "services", "kimi_vl_a3b.py"), "svc_run")
    bench = svc.KimiVLServeBench({"config": tiny, "seed": 2 ** 31 + 77,
                                  "chips": 1, "rehearse": True,
                                  "run_dir": str(tmp_path)})
    try:
        bench.__kt_warmup__()
        sample = []
        for i in range(3):
            prompt = np.random.RandomState(i).randint(
                0, 256, 5 + 7 * i).tolist()
            out = bench.generate(prompt, 12)
            assert out["n"] == 12 and len(out["logprobs"]) == 12
            sample.append({"prompt": prompt, "tokens": out["tokens"],
                           "logprobs": out["logprobs"]})
        c = bench.mark()
        pairs, hits = (np.asarray(c[k]) for k in ("moe_routed_pairs",
                                                  "moe_expert_hits"))
        assert pairs.shape == hits.shape == (2, 8)
        assert pairs.sum() > 0 and (hits <= pairs).all()
        json.dumps(bench.report())                 # what the fabric ships
        names = ["logprob_err_mean", "logit_gap_p97_decided",
                 "logit_gap_p99_decided"]
        fin = bench.finish(sample, 64, names, control=True)
    finally:
        if bench.engine is not None:
            bench.engine.stop()
    check = fin["check"]
    assert check["finite"] and check["tokens_compared"] == 36
    assert set(check["numbers"]) == set(names)
    # the program in bfloat16 against float32; the int8 control is farther
    assert check["numbers"]["logprob_err_mean"] < 0.05
    assert check["control"]["logprob_err_mean"] \
        > 2 * check["numbers"]["logprob_err_mean"]
    assert "moe_routed_pairs" in fin["counters"]
    assert len(fin["log"]) == 3                    # the warm-ups are not logged


def test_service_module_refuses_a_program_without_the_family(bench_path,
                                                             monkeypatch):
    """What the driver's run of the new cell on the parent commit meets: the
    parent process loads the service's file before any deploy."""
    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "kubetorch_tpu" else real(name, *a)))
    with pytest.raises(ImportError, match="models/mla.py"):
        _load(os.path.join(BENCH, "services", "kimi_vl_a3b.py"), "svc_none")


def test_reference_refuses_what_it_would_have_to_guess(bench_path, tiny):
    import bench_reference_mla_moe as R
    assert dict(R.model_key(tiny))["n_routed_experts"] == 8
    for key, value in (("n_group", 8), ("q_lora_rank", 1536),
                       ("scoring_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            R.model_key({**tiny, key: value})


# -- the new readers, each fed a hand-made ctx --------------------------------

def _ctx(tiny, **trace):
    c0 = {"now": 100.0, "decode_steps": 40, "tokens_generated": 0,
          "moe_routed_pairs": [[0] * 8, [0] * 8],
          "moe_expert_hits": [[0] * 8, [0] * 8]}
    c1 = {"now": 102.0, "decode_steps": 60, "tokens_generated": 0,
          "moe_routed_pairs": [[30, 10, 10, 10, 0, 0, 0, 0],
                               [10, 10, 10, 10, 5, 5, 5, 5]],
          "moe_expert_hits": [[20, 10, 10, 10, 0, 0, 0, 0],
                              [10, 10, 10, 10, 5, 5, 5, 5]]}
    log = [{"t_first": 100.5, "t_out": 101.5, "n": 11, "prompt_len": 5},
           {"t_first": None, "t_out": 101.0, "n": 0, "prompt_len": 9}]
    return {"config": tiny, "chips": 1,
            "peak": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8},
            "trace": {"c0": c0, "c1": c1, "log": log, "window_s": 4.0,
                      **trace}}


def _reader(name):
    return _load(os.path.join(BENCH, "readers", name + ".py"), "rd_" + name)


def test_reader_moe_tally(bench_path, tiny):
    read = _reader("moe_tally").read
    ctx = _ctx(tiny)
    # 110 (layer, expert, step) hits of 20 steps x 2 layers x 8 experts
    assert read(ctx, what="hit_share") == pytest.approx(100 * 110 / 320)
    # layer 0: 30 over a mean of 7.5; layer 1: 10 over 7.5
    assert read(ctx, what="load_max_over_mean") == pytest.approx(
        (4.0 + 10 / 7.5) / 2)
    for c in (ctx["trace"]["c0"], ctx["trace"]["c1"]):
        del c["moe_expert_hits"], c["moe_routed_pairs"]
    assert read(ctx, what="hit_share") is None     # a program with no tally
    assert read(ctx, what="load_max_over_mean") is None
    assert read({**ctx, "trace": {}}, what="hit_share") is None


def test_reader_serve_mfu_mla_moe(bench_path, tiny):
    import bench_flops_mla_moe as F
    read = _reader("serve_mfu_mla_moe").read
    ctx = _ctx(tiny)
    # the one request with a first token: prefilled in the window, and its
    # ten decoded tokens 1..10 a tenth of a second apart
    want = F.prefill_flops(tiny, 5) + F.decode_flops(tiny, 5, 1, 10)
    assert read(ctx) == pytest.approx(100 * want / (2.0 * 1e9))
    assert read({**ctx, "trace": {}}) is None


def test_reader_moe_experts_roofline(bench_path, tiny):
    """ISSUE 34's reader: the hit banks' bytes (or the slots' products
    against them, whichever takes longer at the peaks given) as a rate over
    the counters' window, over the kernel's self time as a rate over the
    trace's."""
    import bench_flops
    mod = _reader("moe_experts_roofline")
    kernel = {"moe_experts": {"count": 160.0, "seconds": 0.05}}
    ctx = {**_ctx(tiny, queries=kernel), "flops": bench_flops}
    bank = 3 * 64 * 32                       # an expert's three matrices
    cost = mod.moe_experts_cost(tiny, 110)   # 110 hits in the window
    assert cost == {"bytes": 110 * bank * 2,
                    "flops": 110 * 2.0 * bank * tiny["engine"]["slots"]}
    least = max(cost["bytes"] / 1e8, cost["flops"] / 1e9)
    assert mod.read(ctx, query="moe_experts") == pytest.approx(
        100 * (least / 2.0) / (0.05 / 4.0))
    # at the chip's peaks sixteen rows leave the kernel bound by memory
    real = bench_flops.peaks("TPU v5 lite")
    assert bench_flops.roofline_seconds(cost, real)[1] == "memory"
    # a program without the kernel leaves no event, one without the tally no
    # counter, an untraced run no trace: nothing to read, and no error
    idle = {"moe_experts": {"count": 0.0, "seconds": 0.0}}
    assert mod.read({**ctx, "trace": {**ctx["trace"], "queries": idle}},
                    query="moe_experts") is None
    assert mod.read({**ctx, "trace": {**ctx["trace"], "queries": {}}},
                    query="moe_experts") is None
    bare = {**ctx["trace"], "c0": {"now": 100.0}, "c1": {"now": 102.0}}
    assert mod.read({**ctx, "trace": bare}, query="moe_experts") is None
    assert mod.read({**ctx, "trace": {}}, query="moe_experts") is None


def test_metric_files_name_their_readers():
    for name, reader in (("step_mfu.serve_mla_moe", "serve_mfu_mla_moe"),
                         ("moe_expert_hit_share", "moe_tally"),
                         ("moe_load_max_over_mean", "moe_tally"),
                         ("moe_experts_roofline", "moe_experts_roofline")):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == reader
        assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))

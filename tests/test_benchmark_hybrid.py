"""The benchmark files that ``granite-4.0-h-micro`` brings, walked on the CPU
at tiny widths: the configuration against the catalog row's ``config``, key
for key; the seeded weights against the reference's slices; the service
built from a configuration dict of its kind (``rehearse``), warmed, driven
and finished against the benchmark's own copy of the reference; the two new
readers fed a small hand-made ``ctx``; the FLOP, byte and parameter counts
against sums written out by hand. No device metric is read here.
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
CELL = "granite4h-chat-closed-64"


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
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(full):
    """The configuration's own keys at ``HybridConfig.tiny``'s widths: ten
    layers with attention at index 5, the Granite scalars kept."""
    cfg = copy.deepcopy(full)
    cfg.update(hidden_size=64, intermediate_size=128,
               shared_intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=32,
               mamba_d_state=16, mamba_chunk_size=8, num_hidden_layers=10,
               layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
               vocab_size=256,
               engine={"slots": 4, "max_len": 64, "prefill_buckets": [16, 32],
                       "decode_block": 4})
    return cfg


# -- the configuration file ---------------------------------------------------

# the ``config`` of the catalog's row ``granite-4.0-h-micro`` (the
# ``model-configs`` guide's architectures.jsonl), key for key
LAYER_TYPES = (["mamba"] * 5 + (["attention"] + ["mamba"] * 9) * 3
               + ["attention"] + ["mamba"] * 4)
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": LAYER_TYPES,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


def test_configuration_is_the_catalog_row_key_for_key(full):
    assert {k: full.get(k, "?") for k in CATALOG} == CATALOG
    assert full["source"] == ("https://huggingface.co/ibm-granite/"
                              "granite-4.0-h-micro/blob/main/config.json")
    assert full["reduced"] == {} and full["published"] == {}
    assert full["kind"] == "serve"
    assert full["engine"] == {"slots": 64, "max_len": 2048,
                              "prefill_buckets": [256, 512, 1024],
                              "decode_block": 8}
    assert "float32" in full["assumed"]["ssm_state"]
    assert "serving 64 sequences" in full["deployment"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == full["name"])
    assert entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/granite-4.0-h-micro.json"
    assert entry["source"] == full["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        full["name"], "chat-closed-64", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # appended behind the four older configurations and cells, where they
    # stay when later ones are appended behind them
    assert bench["configs"].index(entry) == 4
    assert bench["workloads"].index(cell) == 4
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"serve_tok_s", "step_mfu.serve_hybrid",
            "decode_hbm_roofline.serve_hybrid", "decode_step_ms",
            "slots_busy_share", "device_idle_share.serve",
            "deploy_programs_compiled"} <= listed
    # GQA's FLOPs and the decode kernel's roofline are the other families'
    assert not {"step_mfu.serve", "decode_attn_roofline"} & listed
    for name in ("step_mfu.serve_hybrid", "decode_hbm_roofline.serve_hybrid"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (m["layer"], m["moves"], m["unit"], m["workloads"]) == (
            "model step", "serve_tok_s", "%", [CELL])


def test_the_mix_is_chat_closed_with_64_callers():
    with open(os.path.join(BENCH, "traffic", "chat-closed.json")) as f:
        chat = json.load(f)
    with open(os.path.join(BENCH, "traffic", "chat-closed-64.json")) as f:
        mix = json.load(f)
    assert mix["callers"] == 64
    differ = {k for k in chat if chat[k] != mix[k]}
    assert differ == {"callers", "assumed", "why"}


def test_bytes_and_parameters_are_the_hand_sums(bench_path, full, tiny):
    import bench_weights_hybrid as W
    from kubetorch_tpu.models.hybrid import HybridConfig
    mixer = 2048 * 8512 + 4 * 4352 + 4352 + 2 * 64 + 64 + 4096 + 4096 * 2048
    mlp = 3 * 2048 * 8192 + 2 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    total = 36 * (mixer + mlp) + 4 * (attn + mlp) + 100352 * 2048 + 2048
    assert W.param_count(full) == total == 3_191_396_096
    assert HybridConfig().param_count() == total
    b = full["bytes"]
    assert b["params"] == total and b["weights_bf16"] == 2 * total
    assert b["mamba_layer_params"] == mixer + mlp
    assert b["attention_layer_params"] == attn + mlp
    assert b["state_grid"] == 64 * 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert b["kv_grid"] == 2 * 4 * 64 * 2048 * 8 * 64 * 2
    t_mixer = 64 * 292 + 4 * 160 + 160 + 3 * 4 + 128 + 128 * 64
    t_mlp = 3 * 64 * 128 + 2 * 64
    t_attn = 2 * 64 * 64 + 2 * 64 * 32
    assert W.param_count(tiny) == (256 * 64 + 64 + 9 * (t_mixer + t_mlp)
                                   + (t_attn + t_mlp))


def test_flops_and_bytes_are_the_hand_sums(bench_path, tiny):
    import bench_flops_hybrid as F
    mamba = 64 * 292 + 128 * 64 + 3 * 64 * 128
    attn = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128
    assert F.layer_matmul_params(tiny) == {"mamba": mamba, "attention": attn}
    assert F.stack_matmul_params(tiny) == 9 * mamba + attn
    ssm = 9 * (2 * 4 * 160 + 5 * 4 * 32 * 16)
    assert F.ssm_flops_per_token(tiny) == ssm
    per_pos = 4 * 1 * 4 * 16
    head = 2 * 64 * 256
    layers = 2 * (9 * mamba + attn) + ssm
    assert F.token_flops(tiny, 10) == layers + per_pos * 10 + head
    assert F.prefill_flops(tiny, 5) == 5 * layers + per_pos * 15 + head
    assert F.decode_flops(tiny, 5, 1, 3) == 3 * (layers + head) \
        + per_pos * (6 + 7 + 8)
    assert F.decode_flops(tiny, 5, 4, 3) == 0.0
    state = 9 * 2 * (4 * 32 * 16 * 4 + 3 * 160 * 2)
    assert F.state_bytes_per_slot_step(tiny) == state
    assert F.kv_row_bytes(tiny) == 1 * 2 * 2 * 16 * 2
    assert F.decode_bytes(tiny, 3, 10, 100) == (
        3 * F.weight_bytes(tiny) + 10 * state + 100 * 128)


# -- weights, service, reference ----------------------------------------------

def test_seeded_tree_is_the_references_slices(bench_path, tiny):
    import jax

    import bench_weights as W0
    import bench_weights_hybrid as W
    from kubetorch_tpu.models.hybrid import hybrid_init
    svc = _load(os.path.join(BENCH, "services", "granite4h.py"), "svc_w")
    root = W0.root_key(2 ** 31 + 5)
    params = jax.jit(lambda r: W.init_params(r, tiny))(root)
    want = jax.eval_shape(lambda: hybrid_init(
        jax.random.PRNGKey(0), svc.program_config(tiny)))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    # run 2 is layers 6..9: its second layer is layer 7 of the model
    np.testing.assert_array_equal(params["runs"][2]["in_proj"][1],
                                  W.make_slice(root, "in_proj", 7, tiny))
    np.testing.assert_array_equal(params["runs"][2]["wo"][1],
                                  W.make_slice(root, "wo_ssm", 7, tiny))
    np.testing.assert_array_equal(params["runs"][1]["wk"][0],
                                  W0.make_slice(root, "wk", 5, 0, tiny))
    a_log = np.asarray(params["runs"][0]["A_log"])
    assert a_log.dtype == np.float32
    assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
    dt = np.log1p(np.exp(np.asarray(params["runs"][0]["dt_bias"])))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()


def test_service_walks_warmup_generate_finish(bench_path, tiny, tmp_path):
    svc = _load(os.path.join(BENCH, "services", "granite4h.py"), "svc_run")
    bench = svc.Granite4hServeBench({"config": tiny, "seed": 2 ** 31 + 77,
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
        assert c["ssm_state_slot_steps"] >= 3 * 11
        json.dumps(bench.report())                 # what the fabric ships
        names = ["logprob_err_p50_decided", "logit_gap_p90_decided"]
        fin = bench.finish(sample, 64, names, control=True)
    finally:
        if bench.engine is not None:
            bench.engine.stop()
    check = fin["check"]
    assert check["finite"] and check["tokens_compared"] == 36
    assert check["decided_share"] == 1.0
    assert set(check["numbers"]) == set(names)
    # the program in bfloat16 against float32; the int8 control is farther
    # (at these widths both put the reference's first token first at nine
    # positions in ten: the chip's readings set the limits)
    err = "logprob_err_p50_decided"
    assert check["numbers"][err] < 0.01
    assert check["control"][err] > check["numbers"][err]
    assert "ssm_state_slot_steps" in fin["counters"]
    assert len(fin["log"]) == 3              # the warm-ups are not logged


def test_service_module_refuses_a_program_without_the_family(bench_path,
                                                             monkeypatch):
    """What a run of the cell meets on a program without the family: the
    benchmark's parent process loads the service's file before any deploy,
    and it fails there at once."""
    real = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: (
        None if name == "kubetorch_tpu" else real(name, *a)))
    with pytest.raises(ImportError, match="models/hybrid.py"):
        _load(os.path.join(BENCH, "services", "granite4h.py"), "svc_none")


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("tie_word_embeddings", False), ("mamba_n_groups", 2),
    ("shared_intermediate_size", 4096)])
def test_reference_refuses_what_it_would_have_to_guess(bench_path, tiny, key,
                                                       value):
    import bench_reference_hybrid as R
    assert dict(R.model_key(tiny))["layer_types"][5] == "attention"
    with pytest.raises(ValueError, match="shared_intermediate_size|" + key):
        R.model_key({**tiny, key: value})


def test_the_state_control_rounds_the_state_alone(bench_path, tiny):
    """``state_bf16``: the float32 reference with its recurrent state held
    in bfloat16, the second control that ``limits_control.py`` reads. It
    moves the readings, and an attention-only model would not see it; a
    control the reference does not have is refused by name."""
    import bench_reference_hybrid as R
    seed = 2 ** 31 + 5
    toks = np.random.RandomState(3).randint(0, 256, (1, 24))
    f32 = np.asarray(R.forward(seed, tiny, toks)[0]["lp_next"])
    state = np.asarray(R.forward(seed, tiny, toks,
                                 quant="state_bf16")[0]["lp_next"])
    assert np.isfinite(state).all()
    assert 0 < np.abs(state - f32).max() < 0.5
    prompt = toks[0, :10].tolist()
    with pytest.raises(ValueError, match="no control 'fp8'"):
        R.compare(seed, tiny, [(prompt, toks[0, 10:14].tolist())],
                  [[-1.0] * 4], 32, ["logprob_err_p50_decided"],
                  control="fp8")


def test_limits_control_hands_the_named_control_to_the_runner(
        bench_path, tmp_path, monkeypatch):
    import limits
    import limits_control
    import run as R
    runner = tmp_path / "runner.py"
    runner.write_text("def measure(*a, control=False, **kw):\n"
                      "    return control\n")
    seen = {}

    def main(argv):
        mod = R.load_file(str(runner))
        seen.update(argv=argv, asked=mod.measure(None, control=True),
                    plain=mod.measure(None))
        return 0

    monkeypatch.setattr(limits, "main", main)
    monkeypatch.setattr(R, "load_file", R.load_file)   # put back after
    assert limits_control.main(["--control", "state_bf16", "--workload",
                                CELL, "--seeds", "1,2"]) == 0
    assert seen == {"argv": ["--workload", CELL, "--seeds", "1,2"],
                    "asked": "state_bf16", "plain": False}


# -- the new readers, each fed a hand-made ctx --------------------------------

def _ctx(tiny, **trace):
    c0 = {"now": 100.0, "decode_steps": 40, "tokens_generated": 0,
          "ssm_state_slot_steps": 100}
    c1 = {"now": 102.0, "decode_steps": 60, "tokens_generated": 0,
          "ssm_state_slot_steps": 160}
    log = [{"t_first": 100.5, "t_out": 101.5, "n": 11, "prompt_len": 5},
           {"t_first": None, "t_out": 101.0, "n": 0, "prompt_len": 9}]
    return {"config": tiny, "chips": 1,
            "peak": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8},
            "trace": {"c0": c0, "c1": c1, "log": log, "window_s": 4.0,
                      **trace}}


def _reader(name):
    return _load(os.path.join(BENCH, "readers", name + ".py"), "rd_" + name)


def test_reader_serve_mfu_hybrid(bench_path, tiny):
    import bench_flops_hybrid as F
    read = _reader("serve_mfu_hybrid").read
    ctx = _ctx(tiny)
    # the one request with a first token: prefilled in the window, and its
    # ten decoded tokens 1..10 a tenth of a second apart
    want = F.prefill_flops(tiny, 5) + F.decode_flops(tiny, 5, 1, 10)
    assert read(ctx) == pytest.approx(100 * want / (2.0 * 1e9))
    assert read({**ctx, "trace": {}}) is None


def test_reader_decode_hbm_roofline(bench_path, tiny):
    """The least time of the window's decode bytes at the peak, per second
    of the counters' window, over the decode blocks' share of the trace's
    window (both rates, as ``moe_experts_roofline`` reads them)."""
    import bench_flops_hybrid as F
    mod = _reader("decode_hbm_roofline_hybrid")
    blocks = {"hybrid_decode_block": {"count": 5.0, "seconds": 0.5}}
    ctx = _ctx(tiny, queries=blocks)
    # 20 steps, 60 slot-steps of state; tokens 1..10 of the request with a
    # prompt of 5 attend to 6..15 rows
    rows = sum(5 + j for j in range(1, 11))
    least = F.decode_bytes(tiny, 20, 60, rows) / 1e8
    assert mod.read(ctx, query="hybrid_decode_block") == pytest.approx(
        100 * (least / 2.0) / (0.5 / 4.0))
    # no decode block in the trace, no tally (a program without recurrent
    # state), no trace: nothing to read, and no error
    idle = {"hybrid_decode_block": {"count": 0.0, "seconds": 0.0}}
    assert mod.read({**ctx, "trace": {**ctx["trace"], "queries": idle}},
                    query="hybrid_decode_block") is None
    bare = {**ctx["trace"],
            "c0": {k: v for k, v in ctx["trace"]["c0"].items()
                   if k != "ssm_state_slot_steps"},
            "c1": {k: v for k, v in ctx["trace"]["c1"].items()
                   if k != "ssm_state_slot_steps"}}
    assert mod.read({**ctx, "trace": bare},
                    query="hybrid_decode_block") is None
    assert mod.read({**ctx, "trace": {}}, query="hybrid_decode_block") is None


def test_metric_files_name_their_readers():
    for name, reader in (("step_mfu.serve_hybrid", "serve_mfu_hybrid"),
                         ("decode_hbm_roofline.serve_hybrid",
                          "decode_hbm_roofline_hybrid")):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == reader
        assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))

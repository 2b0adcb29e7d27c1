"""``trinity-mini-l5``'s counts and readers, no jax and no service:
``python -m pytest benchmark/tests/test_afmoe_roofline.py -q``.

- ``bench_flops_afmoe``'s band count against a brute-force count of the
  masks at small sizes;
- ``step_mfu.serve_afmoe`` and ``prefill_attn_roofline.serve_afmoe`` over a
  synthetic trace summary: the values worked out by hand, and nothing to
  read without the kernel's events or without a trace;
- the configuration's ``bytes`` against the parameter count.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import bench_flops_afmoe as F        # noqa: E402
import bench_weights_afmoe as W      # noqa: E402
import run as R                      # noqa: E402

CONFIG = R.read_json(os.path.join(BENCH, "configs", "trinity-mini-l5.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def brute(first, n, window):
    """Keys seen by queries at positions first - 1 .. first + n - 2 of a
    sequence, one mask entry at a time."""
    count = 0
    for i in range(first - 1, first - 1 + n):
        for j in range(i + 1):
            if window is None or j > i - window:
                count += 1
    return count


@pytest.mark.parametrize("first,n,window", [
    (1, 1, 4), (1, 7, 4), (1, 20, 4), (3, 9, 4), (6, 5, 4), (1, 12, None),
    (5, 30, 8), (9, 3, 8), (1, 33, 33), (2, 40, 1)])
def test_the_band_count_is_the_masks(first, n, window):
    assert F.band_pairs(first, n, window) == brute(first, n, window)


def test_prompt_and_decode_attention_count_band_and_triangle():
    small = {**CONFIG, "sliding_window": 4}
    per_pair = 4 * 32 * 128
    # layers: 4 window, 1 full; a prompt of 10
    pairs = 4 * brute(1, 10, 4) + brute(1, 10, None)
    assert F.prefill_attention_flops(small, 10) == pairs * per_pair
    # decode tokens 2..4 of a request with a prompt of 10 (contexts 12..14)
    ctx_pairs = 4 * 3 * 4 + (12 + 13 + 14)
    matmul = F.stack_matmul_params(small)
    head = 2 * 2048 * 200192
    assert F.decode_flops(small, 10, 2, 4) == (
        3 * (2 * matmul + head) + ctx_pairs * per_pair)
    assert F.decode_flops(small, 10, 5, 4) == 0.0


def test_the_layers_matmul_parameters():
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert F.layer_matmul_params(CONFIG) == {
        "dense": attn + 3 * 2048 * 6144,
        "moe": attn + 2048 * 128 + 3 * 2048 * 1024 * (1 + 8)}


def test_bytes_are_the_parameter_count():
    b = CONFIG["bytes"]
    assert W.param_count(CONFIG) == b["params"]
    assert b["params"] == b["matrices"] + b["norms_and_biases"]
    assert b["matrices"] == (b["dense_layer_matrices"]
                             + 4 * b["expert_layer_matrices"]
                             + b["embedding_and_head"])
    assert b["weights_bytes"] == 2 * b["matrices"] + 2 * 4 * 2048 * 128 \
        + 4 * b["norms_and_biases"]


# -- the readers ------------------------------------------------------------------

def reader(name):
    spec = R.read_json(os.path.join(BENCH, "metrics", name + ".json"))
    mod = R.load_file(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    return spec, lambda ctx: mod.read(ctx, **spec["args"])


def ctx(queries):
    # the counters' window 100 .. 104 s; requests prefilled at 101.0 (8,000
    # rows) and 103.5 (6,500 rows) inside it, one at 99.0 before it, one
    # that never got a first token
    log = [{"t_first": 101.0, "t_out": 110.0, "n": 901, "prompt_len": 8000},
           {"t_first": 103.5, "t_out": 112.0, "n": 801, "prompt_len": 6500},
           {"t_first": 99.0, "t_out": 108.0, "n": 901, "prompt_len": 7000},
           {"t_first": None, "t_out": 104.0, "n": 0, "prompt_len": 6500}]
    return {"config": CONFIG, "chips": 1, "peak": PEAK,
            "trace": {"c0": {"now": 100.0}, "c1": {"now": 104.0},
                      "log": log, "window_s": 6.0, "queries": queries}}


def test_prefill_roofline_counts_the_band_on_window_layers():
    spec, read = reader("prefill_attn_roofline.serve_afmoe")
    assert [(q["pattern"], q["within"]) for q in spec["trace_queries"]] == [
        ("kt_flash_attention_fwd", "_prefill")]
    per_pair = 4 * 32 * 128
    work = 0
    for p in (8000, 6500):
        band = 2048 * 2049 / 2 + (p - 2048) * 2048
        work += (4 * band + p * (p + 1) / 2) * per_pair
    least = work / 197e12
    got = read(ctx({"flash_prefill": {"count": 400.0, "seconds": 0.3}}))
    assert got == pytest.approx(100 * (least / 4.0) / (0.3 / 6.0))


@pytest.mark.parametrize("queries", [
    {}, {"flash_prefill": {"count": 0.0, "seconds": 0.0}}])
def test_without_the_kernels_events_there_is_nothing_to_read(queries):
    _, read = reader("prefill_attn_roofline.serve_afmoe")
    assert read(ctx(queries)) is None
    assert read({**ctx(queries), "trace": None}) is None


def test_step_mfu_counts_prefills_and_decode_steps_in_the_window():
    _, read = reader("step_mfu.serve_afmoe")
    c = ctx({})
    total = 0.0
    for rec in c["trace"]["log"]:
        if rec["t_first"] is None:
            continue
        pre, lo, hi = F.tokens_in(rec, 100.0, 104.0)
        total += pre * F.prefill_flops(CONFIG, rec["prompt_len"])
        total += F.decode_flops(CONFIG, rec["prompt_len"], lo, hi)
    # the first request's tokens 1 .. 900 lie 0.01 s apart from 101.0: about
    # 300 of them fall before 104.0, and its prefill inside the window
    pre, lo, hi = F.tokens_in(c["trace"]["log"][0], 100.0, 104.0)
    assert pre and lo == 1 and 299 <= hi <= 300
    assert read(c) == pytest.approx(100 * total / (4.0 * 197e12))
    assert read({**c, "trace": {}}) is None

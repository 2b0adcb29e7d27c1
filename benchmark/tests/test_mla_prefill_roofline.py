"""``mla_prefill_attn_roofline`` (PR 39) over a synthetic trace summary:
nothing to read without the kernel's events, and the value worked out by
hand with them: ``python -m pytest benchmark/tests/test_mla_prefill_roofline.py
-q``. No jax, no service."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as R         # noqa: E402

CONFIG = R.read_json(os.path.join(BENCH, "configs", "glm-5-ep16-l6.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def metric():
    """The committed metric file and its reader, as run.py resolves them."""
    spec = R.read_json(os.path.join(BENCH, "metrics",
                                    "mla_prefill_attn_roofline.json"))
    assert [q["pattern"] for q in spec["trace_queries"]] == [
        "kt_mla_flash_prefill"]
    reader = R.load_file(os.path.join(BENCH, "readers",
                                      spec["reader"] + ".py"))
    return lambda ctx: reader.read(ctx, **spec["args"])


def ctx(queries):
    # the counters' window 100 .. 104 s; three requests: prefilled at
    # 101.0 (8,000 rows) and 103.5 (2,000 rows) inside it, one at 99.0
    # before it, one that never got a first token
    log = [{"t_first": 101.0, "t_out": 110.0, "n": 900, "prompt_len": 8000},
           {"t_first": 103.5, "t_out": 112.0, "n": 800, "prompt_len": 2000},
           {"t_first": 99.0, "t_out": 108.0, "n": 700, "prompt_len": 7000},
           {"t_first": None, "t_out": 104.0, "n": 0, "prompt_len": 6500}]
    return {"config": CONFIG, "chips": 1, "peak": PEAK,
            "trace": {"c0": {"now": 100.0}, "c1": {"now": 104.0},
                      "log": log, "window_s": 6.0, "queries": queries}}


def test_the_kernels_time_against_the_attended_operations():
    # a key and layer: 2 * 64 heads * (192 + 64 + 256); 6 layers
    per_key = 2 * 64 * (192 + 64 + 256) * 6
    # 8,000 rows: queries 1 .. 2,048 see all their keys, the rest 2,048;
    # 2,000 rows: every query sees all its keys
    keys = 2048 * 2049 / 2 + (8000 - 2048) * 2048 + 2000 * 2001 / 2
    least = keys * per_key / 197e12
    got = metric()(ctx({"mla_flash_prefill": {"count": 840.0,
                                              "seconds": 0.12}}))
    assert got == pytest.approx(100 * (least / 4.0) / (0.12 / 6.0))


@pytest.mark.parametrize("queries", [
    {}, {"mla_flash_prefill": {"count": 0.0, "seconds": 0.0}}])
def test_without_the_kernels_events_there_is_nothing_to_read(queries):
    """The parent's XLA einsums, and a cell whose prompts fit one score
    array, leave no ``kt_mla_flash_prefill`` event."""
    assert metric()(ctx(queries)) is None
    assert metric()({**ctx(queries), "trace": None}) is None

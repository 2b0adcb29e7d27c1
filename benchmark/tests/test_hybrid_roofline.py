"""``decode_hbm_roofline.serve_hybrid`` over a synthetic trace summary at
``granite-4.0-h-micro``'s widths: nothing to read without the decode blocks'
events or without the recurrent state's tally, and the value worked out by
hand with them: ``python -m pytest benchmark/tests/test_hybrid_roofline.py
-q``. No jax, no service."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as R         # noqa: E402

CONFIG = R.read_json(os.path.join(BENCH, "configs",
                                  "granite-4.0-h-micro.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def metric():
    """The committed metric file and its reader, as run.py resolves them."""
    spec = R.read_json(os.path.join(BENCH, "metrics",
                                    "decode_hbm_roofline.serve_hybrid.json"))
    assert [(q["line"], q["pattern"]) for q in spec["trace_queries"]] == [
        ("modules", "_decode_block")]
    reader = R.load_file(os.path.join(BENCH, "readers",
                                      spec["reader"] + ".py"))
    return lambda ctx: reader.read(ctx, **spec["args"])


def ctx(queries, tally=True):
    # the counters' window 100 .. 104 s: 160 decode steps (20 blocks of 8)
    # in which live slots advanced their state 9,600 times; one request
    # decoded its tokens 1 .. 512 at 1/128 s apart from 100.0 s, one had no
    # first token
    c0 = {"now": 100.0, "decode_steps": 1000, "ssm_state_slot_steps": 50000}
    c1 = {"now": 104.0, "decode_steps": 1160, "ssm_state_slot_steps": 59600}
    if not tally:
        for c in (c0, c1):
            del c["ssm_state_slot_steps"]
    log = [{"t_first": 100.0, "t_out": 104.0, "n": 513, "prompt_len": 100},
           {"t_first": None, "t_out": 104.0, "n": 0, "prompt_len": 90}]
    return {"config": CONFIG, "chips": 1, "peak": PEAK,
            "trace": {"c0": c0, "c1": c1, "log": log, "window_s": 6.0,
                      "queries": queries}}


# the decode blocks' events in a trace of 6 s: 5.4 s of them, the two at the
# ends cut by the trace
BLOCKS = {"hybrid_decode_block": {"count": 22.0, "seconds": 5.4}}


def test_a_steps_least_bytes_against_a_steps_device_time():
    """Per second of their own windows: the least time of the counters'
    window's decode bytes, over the decode blocks' share of the trace."""
    weights = 2 * 3_191_396_096
    state = 36 * 2 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    row = 4 * 2 * 8 * 64 * 2
    # tokens 1 .. 512 attend to 101 .. 612 rows
    rows = sum(100 + j for j in range(1, 513))
    least = (160 * weights + 9600 * state + rows * row) / 819e9
    assert metric()(ctx(BLOCKS)) == pytest.approx(
        100 * (least / 4.0) / (5.4 / 6.0))


def test_the_query_is_its_own():
    """A query id that another metric of the cell declares too is summed
    once for each (``trace_reduce.reduce_planes``): ``decode_step_ms``
    declares ``decode_block``."""
    spec = R.read_json(os.path.join(BENCH, "metrics",
                                    "decode_hbm_roofline.serve_hybrid.json"))
    others = [q["id"] for f in os.listdir(os.path.join(BENCH, "metrics"))
              if f != "decode_hbm_roofline.serve_hybrid.json"
              for q in R.read_json(os.path.join(BENCH, "metrics", f)).get(
                  "trace_queries", [])]
    assert spec["trace_queries"][0]["id"] not in others


@pytest.mark.parametrize("queries", [
    {}, {"hybrid_decode_block": {"count": 0.0, "seconds": 0.0}}])
def test_without_the_decode_blocks_there_is_nothing_to_read(queries):
    assert metric()(ctx(queries)) is None
    assert metric()({**ctx(queries), "trace": None}) is None


def test_without_the_tally_there_is_nothing_to_read():
    """The parent's program keeps no recurrent state and no such counter."""
    assert metric()(ctx(BLOCKS, tally=False)) is None

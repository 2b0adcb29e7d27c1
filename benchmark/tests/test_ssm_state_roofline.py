"""``ssm_state_roofline`` over a synthetic trace summary at
``granite-4.0-h-micro``'s widths: nothing to read without the kernel's
events or without the recurrent state's tally, and the value worked out by
hand with them: ``python -m pytest benchmark/tests/test_ssm_state_roofline.py
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
SPEC = R.read_json(os.path.join(BENCH, "metrics", "ssm_state_roofline.json"))


def metric():
    """The committed metric file and its reader, as run.py resolves them."""
    reader = R.load_file(os.path.join(BENCH, "readers",
                                      SPEC["reader"] + ".py"))
    return lambda ctx: reader.read(ctx, **SPEC["args"])


def ctx(queries, tally=True):
    # the counters' window 100 .. 104 s, in which live slots advanced their
    # state 9,600 times (150 steps of 64)
    c0 = {"now": 100.0, "decode_steps": 1000, "ssm_state_slot_steps": 50000}
    c1 = {"now": 104.0, "decode_steps": 1150, "ssm_state_slot_steps": 59600}
    if not tally:
        for c in (c0, c1):
            del c["ssm_state_slot_steps"]
    return {"config": CONFIG, "chips": 1, "peak": PEAK,
            "trace": {"c0": c0, "c1": c1, "log": [], "window_s": 6.0,
                      "queries": queries}}


# the kernel's events in a trace of 6 s: 2.7 s of them
KERNEL = {"ssm_state_step": {"count": 900.0, "seconds": 2.7}}


def test_the_query_is_the_kernels_inside_the_decode_block():
    assert SPEC["trace_queries"] == [{
        "id": "ssm_state_step", "line": "ops", "pattern": "kt_ssm_state_step",
        "within": "_decode_block"}]


def test_a_windows_state_bytes_against_the_kernels_device_time():
    """Per second of their own windows: 9,600 slot-steps of 36 layers'
    float32 state (64 × 64 × 128) read and written once, at 819 GB/s, over
    the kernel's share of the trace."""
    state = 36 * 2 * 64 * 64 * 128 * 4                 # 151 MB a slot-step
    least = 9600 * state / 819e9
    assert metric()(ctx(KERNEL)) == pytest.approx(
        100 * (least / 4.0) / (2.7 / 6.0))
    assert metric()(ctx(KERNEL)) == pytest.approx(98.33, abs=0.01)


def test_the_query_is_its_own():
    """A query id that another metric of the cell declares too is summed
    once for each (``trace_reduce.reduce_planes``)."""
    others = [q["id"] for f in os.listdir(os.path.join(BENCH, "metrics"))
              if f != "ssm_state_roofline.json"
              for q in R.read_json(os.path.join(BENCH, "metrics", f)).get(
                  "trace_queries", [])]
    assert SPEC["trace_queries"][0]["id"] not in others


@pytest.mark.parametrize("queries", [
    {}, {"ssm_state_step": {"count": 0.0, "seconds": 0.0}}])
def test_without_the_kernels_events_there_is_nothing_to_read(queries):
    """The parent's program advances the state through XLA's fusions."""
    assert metric()(ctx(queries)) is None
    assert metric()({**ctx(queries), "trace": None}) is None


def test_without_the_tally_there_is_nothing_to_read():
    assert metric()(ctx(KERNEL, tally=False)) is None

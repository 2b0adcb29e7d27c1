"""``scripts/closed_loop_sim.py``: the closed loop of the benchmark's
``chat-closed`` deck walked through the engine's pass on the CPU, which
``PERF.md`` section 6 (PR 33's fix round) uses to say where a cell's
time-to-first-token quantiles fall. Tier-1: it runs, it reads quantiles as the
benchmark's reader does, and it shows what ``_admit_late`` changes."""

import importlib.util
import os
import sys

import pytest

pytestmark = pytest.mark.level("unit")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sim():
    spec = importlib.util.spec_from_file_location(
        "closed_loop_sim", os.path.join(ROOT, "scripts", "closed_loop_sim.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_quantile_is_the_benchmarks_nearest_rank(sim):
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "readers"))
    import record_quantile
    values = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    ctx = {"records": [{"ttft": v} for v in values]}
    for q in (0.5, 0.9, 1.0):
        assert sim.quantile(values, q) == record_quantile.read(ctx, "ttft", q)


@pytest.mark.parametrize("seed", (2147499301, 2147499304))
def test_late_arrivals_are_seated_at_the_boundary(sim, seed):
    """With ``_admit_late`` the callers that return while a boundary's prefill
    runs are seated at once: more requests under 100 ms, fewer a block late,
    the median inside the first mode, and as many requests served."""
    kw = dict(window_ms=20000.0, jitter=0.0, path_jitter=0.0)
    late, rate_late = sim.simulate(seed, admit_late=True, **kw)
    closed, rate_closed = sim.simulate(seed, admit_late=False, **kw)

    def under(tt, ms):
        return sum(x < ms for x in tt) / len(tt)
    assert abs(len(late) - len(closed)) <= 3
    assert abs(rate_late - rate_closed) < 0.02 * rate_closed
    assert under(late, 100) > under(closed, 100) + 0.05
    m = sim.quantile(late, 0.5)
    assert 0.4 < sum(m - 4 <= x < m + 4 for x in late) / len(late)
    assert sim.quantile(late, 0.9) <= sim.quantile(closed, 0.9)
    # one block of 8 steps, less the reply's way back, plus the boundary's
    # host work and a bucket-256 prefill
    assert 130 < m < 150


def test_cli_prints_a_labelled_line_a_seed(sim, capsys):
    assert sim.main(["--seeds", "7", "--decode-block", "4"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("[SIMULATION on the CPU")
    assert out[1].startswith("seed 7: ") and "ttft p50" in out[1]


def test_cli_takes_another_cells_mix_buckets_and_times(sim, capsys):
    """The long-document mix through one bucket of 8,192: every prompt's
    prefill is one mode, and the defaults still print ``chat-closed``'s
    line (the case above)."""
    assert sim.main(["--seeds", "7", "--mix", "longdoc-closed", "--buckets",
                     "8192", "--prefill-ms", "900", "--step-ms", "20",
                     "--ramp-s", "12", "--window-s", "30"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[1].startswith("seed 7: ")
    p50 = float(out[1].split("ttft p50 ")[1].split()[0])
    assert p50 > 900                      # no first token before a prefill
    with pytest.raises(SystemExit):
        sim.main(["--seeds", "7", "--buckets", "256,512", "--prefill-ms",
                  "16.6"])
    tt, _ = sim.simulate(7, mix="longdoc-closed", buckets=(8192,),
                         prefill={8192: 900.0}, step=20.0, ramp_ms=12000.0,
                         window_ms=30000.0)
    assert sim.quantile(tt, 0.5) == pytest.approx(p50, abs=0.01)

"""Mamba-2 layers beside attention layers (``models/hybrid.py``,
``serve/state_cache.py``) at a tiny size on the CPU, against the plain
float32 reference in ``tests/granite4h_reference.py``, whose recurrence runs
a token at a time:

(a) the chunked scan of a prompt, at lengths on and across chunk boundaries
    and stopped at a true length inside a padded bucket, against the
    sequential recurrence: outputs, and the state and window it hands back;
(b) the engine's prefill, then decode blocks through the K/V grid and the
    state grid, against the reference's full forward, three prompts of
    different lengths in one batch;
(c) a slot's next occupant starts from its own prompt's state: the same
    log-probabilities as on a fresh engine, also after a block run ahead
    over the slot once its request had finished;
(d) the parameter count at the published widths and the FLOP sums;
(e) every mechanism the state cache does not carry raises its typed error;
(f) nothing of the family is on another family's serving path.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import granite4h_reference as R
from kubetorch_tpu.exceptions import UnsupportedMechanismError
from kubetorch_tpu.models.hybrid import (HybridConfig, hybrid_forward,
                                         hybrid_init, mamba_rows_mix,
                                         mamba_step)
from kubetorch_tpu.serve import GenerationEngine

pytestmark = pytest.mark.level("unit")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny():
    """Ten layers, attention at index 5, Granite's scalars, float32 so that
    the comparison sees the algorithm and not bfloat16's rounding."""
    cfg = HybridConfig.tiny(dtype=jnp.float32)
    return hybrid_init(jax.random.PRNGKey(0), cfg), cfg


def _tokens(seed, n, vocab=256):
    return np.random.RandomState(seed).randint(0, vocab, n).tolist()


def _mamba_layer(params, cfg):
    """The first Mamba-2 layer's weights, as the program and the reference
    take them."""
    lw = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0])
    return lw, R._f32(lw)


# -- (a) the chunked scan against the sequential recurrence -------------------

@pytest.mark.parametrize("length,bucket", [(8, 8), (13, 16), (16, 16),
                                           (21, 32), (2, 8), (1, 8)])
def test_the_chunked_scan_is_the_recurrence_stopped_at_the_true_length(
        tiny, length, bucket):
    params, cfg = tiny
    lw, lw32 = _mamba_layer(params, cfg)
    h = jax.random.normal(jax.random.PRNGKey(length), (1, bucket, cfg.dim))
    with jax.default_matmul_precision("highest"):
        y, (conv, ssm) = mamba_rows_mix(cfg, jnp.int32(length))(h, lw, None)
    # the reference's run is over the true length alone, unpadded
    want, s, window = R.mamba(cfg, h[0, :length], lw32)
    np.testing.assert_allclose(y[0, :length], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ssm[0], s, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(conv[0], window, rtol=1e-6, atol=1e-6)


def test_forward_matches_the_reference(tiny):
    params, cfg = tiny
    toks = _tokens(1, 24)
    with jax.default_matmul_precision("highest"):
        got = hybrid_forward(params, jnp.asarray([toks]), cfg)[0]
    np.testing.assert_allclose(got, R.forward(cfg, params, toks),
                               rtol=1e-4, atol=1e-5)


# -- (b) prefill then decode through both grids -------------------------------

def _engine(tiny, **kw):
    params, cfg = tiny
    kw = {"slots": 3, "max_len": 64, "prefill_buckets": (16, 32),
          "decode_block": 4, **kw}
    return GenerationEngine(params, cfg, **kw)


def _ref_logprobs(tiny, prompt, served):
    params, cfg = tiny
    lp = jax.nn.log_softmax(R.forward(cfg, params, prompt + served), -1)
    return [float(lp[len(prompt) - 1 + i, t]) for i, t in enumerate(served)]


def test_engine_matches_the_reference_position_by_position(tiny):
    eng = _engine(tiny)
    prompts = [_tokens(i, n) for i, n in enumerate((5, 13, 20))]
    handles = [eng.submit(p, max_new_tokens=20) for p in prompts]
    while eng.step():
        pass
    for p, h in zip(prompts, handles):
        served = h.result(timeout=0)
        assert len(served) == 20
        np.testing.assert_allclose(h.logprobs, _ref_logprobs(tiny, p, served),
                                   atol=2e-4)
    # three live slots, 20 tokens each: 19 decode steps a request advanced
    # its state (the first token is the prefill's); blocks of 4 run whole
    s = eng.stats()
    assert 3 * 19 <= s.ssm_state_slot_steps <= 3 * 20
    assert eng.__kt_metrics__()["engine_ssm_state_slot_steps_total"] == \
        s.ssm_state_slot_steps


def test_the_state_tally_carries_into_its_high_word():
    """The running sum of slot-steps is kept as two int32 words, as the
    sparse attention's rows are: a step's count lands in the low one and
    carries over, and the engine reads the two back as one number."""
    from kubetorch_tpu.serve import engine as E
    word = E._DSA_WORD
    tally = {"ssm_state_slot_steps": jnp.asarray([5, (1 << word) - 3],
                                                 jnp.int32)}
    out = E._tally_add(tally, {"ssm_state_slot_steps": jnp.int32(64)})
    assert out["ssm_state_slot_steps"].tolist() == [6, 61]
    assert int(E._from_words(np.asarray(out["ssm_state_slot_steps"],
                                        np.int64))) == (6 << word) + 61
    assert E._init_tally(HybridConfig.tiny())[
        "ssm_state_slot_steps"].shape == (2,)


# -- (b') the state's precision -------------------------------------------------

def test_a_bfloat16_model_keeps_its_state_in_float32():
    """The configuration states the recurrent state in float32 whatever the
    model's dtype: the slot grid, a prompt's state and a decode step's
    update are float32, and the update reads the state's low bits (a state
    rounded to bfloat16 first gives another result)."""
    cfg = HybridConfig.tiny()
    assert cfg.dtype == jnp.bfloat16
    params = hybrid_init(jax.random.PRNGKey(1), cfg)
    eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                           prefill_buckets=(16,), decode_block=2)
    h = eng.submit(_tokens(3, 7), max_new_tokens=5)
    while eng.step():
        pass
    assert len(h.result(timeout=0)) == 5
    assert eng._cache.ssm.dtype == jnp.float32
    assert eng._cache.conv.dtype == jnp.bfloat16
    lw = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.dim), cfg.dtype)
    _, (_, state) = mamba_rows_mix(cfg, jnp.int32(6))(x, lw, None)
    assert state.dtype == jnp.float32
    conv = jnp.zeros((2, cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype)
    ssm = jax.random.normal(jax.random.PRNGKey(3), (2, cfg.mamba_n_heads,
                                                    cfg.mamba_d_head,
                                                    cfg.mamba_d_state))
    _, _, new = mamba_step(cfg, x[:, 0], lw, conv, ssm)
    _, _, rounded = mamba_step(cfg, x[:, 0], lw, conv,
                               ssm.astype(jnp.bfloat16).astype(jnp.float32))
    assert new.dtype == jnp.float32
    assert not np.array_equal(np.asarray(new), np.asarray(rounded))


# -- (c) a slot's next occupant -----------------------------------------------

def test_a_slots_next_occupant_does_not_inherit_its_state(tiny):
    """One slot, so the second request sits where the first did; with
    decode blocks of 4 and a first request of 6 tokens, the blocks that ran
    past its end (the run-ahead block among them, which may be dispatched
    while it is still live) advanced the slot's state with garbage before
    the second was seated."""
    eng = _engine(tiny, slots=1)
    first = eng.submit(_tokens(7, 9), max_new_tokens=6)
    second_prompt = _tokens(8, 11)
    while eng.step():
        pass
    first.result(timeout=0)
    second = eng.submit(second_prompt, max_new_tokens=10)
    while eng.step():
        pass
    fresh = _engine(tiny, slots=1)
    again = fresh.submit(second_prompt, max_new_tokens=10)
    while fresh.step():
        pass
    assert second.result(timeout=0) == again.result(timeout=0)
    np.testing.assert_allclose(second.logprobs, again.logprobs, atol=1e-6)
    np.testing.assert_allclose(
        second.logprobs,
        _ref_logprobs(tiny, second_prompt, second.result(timeout=0)),
        atol=2e-4)


def test_a_block_run_ahead_over_a_finished_slot_leaves_its_successor_alone(
        tiny):
    """Two slots, both busy, so blocks run ahead; the shorter request ends
    inside a block that was dispatched ahead, and a third request takes its
    slot at the next boundary."""
    eng = _engine(tiny, slots=2)
    a = eng.submit(_tokens(9, 6), max_new_tokens=5)
    b = eng.submit(_tokens(10, 7), max_new_tokens=30)
    third = _tokens(11, 12)
    c = None
    while eng.step():
        if c is None and a._req.life is not None:
            c = eng.submit(third, max_new_tokens=12)
    assert eng.stats().blocks_run_ahead > 0
    for h, p in ((a, _tokens(9, 6)), (b, _tokens(10, 7)), (c, third)):
        served = h.result(timeout=0)
        np.testing.assert_allclose(h.logprobs,
                                   _ref_logprobs(tiny, p, served), atol=2e-4)


# -- (d) sizes ----------------------------------------------------------------

def test_the_published_widths_hold_3_19_billion_parameters():
    cfg = HybridConfig()
    assert cfg.runs == (("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 9),
                        ("attention", 1, 1), ("mamba", 14, 9),
                        ("attention", 2, 1), ("mamba", 23, 9),
                        ("attention", 3, 1), ("mamba", 32, 4))
    assert (cfg.n_mamba, cfg.n_attention, cfg.head_dim) == (36, 4, 64)
    assert (cfg.in_proj_dim, cfg.conv_dim) == (4096 + 4352 + 64, 4352)
    mixer = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096
             + 4096 * 2048)
    mlp = 3 * 2048 * 8192 + 2 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert mixer + mlp == 76_182_976
    assert attn + mlp == 60_821_504
    total = 36 * (mixer + mlp) + 4 * (attn + mlp) + 100352 * 2048 + 2048
    assert cfg.param_count() == total == 3_191_396_096
    params = jax.eval_shape(lambda: hybrid_init(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(params)) == total


# -- (e) what the state cache does not carry ----------------------------------

@pytest.mark.parametrize("mechanism,kwargs", [
    ("quantize_kv", {"quantize_kv": True}),
    ("prefill_chunk", {"prefill_chunk": 8}),
    ("auto_prefix", {"auto_prefix": True}),
    ("aot_cache", {"aot_cache": object()}),
])
def test_engine_construction_refuses_by_name(tiny, mechanism, kwargs):
    with pytest.raises(UnsupportedMechanismError) as e:
        _engine(tiny, **kwargs)
    assert mechanism in e.value.mechanism
    assert e.value.cache_kind == "hybrid"


def test_engine_construction_refuses_a_mesh(tiny, cpu_mesh_devices):
    from kubetorch_tpu.parallel.mesh import build_mesh
    from kubetorch_tpu.parallel.mesh_context import use_mesh
    mesh = build_mesh({"tensor": 2}, devices=cpu_mesh_devices[:2])
    with use_mesh(mesh), pytest.raises(UnsupportedMechanismError) as e:
        _engine(tiny)
    assert "mesh" in e.value.mechanism


@pytest.mark.parametrize("mechanism", [
    "register_prefix", "register_adapter", "SpeculativeEngine",
    "EngineSessionBinder", "generate"])
def test_calls_refuse_by_name(tiny, mechanism):
    params, cfg = tiny
    with pytest.raises(UnsupportedMechanismError) as e:
        if mechanism == "register_prefix":
            _engine(tiny).register_prefix([1, 2, 3])
        elif mechanism == "register_adapter":
            _engine(tiny).register_adapter({}, None)
        elif mechanism == "SpeculativeEngine":
            from kubetorch_tpu.serve import SpeculativeEngine
            SpeculativeEngine(params, cfg, params, cfg, slots=2, max_len=32,
                              prefill_buckets=(8,))
        elif mechanism == "EngineSessionBinder":
            from kubetorch_tpu.serve import EngineSessionBinder
            EngineSessionBinder(_engine(tiny))
        else:
            from kubetorch_tpu.models.generate import generate
            generate(params, jnp.asarray([[1, 2, 3]]), cfg, max_new_tokens=2)
    assert mechanism in e.value.mechanism
    assert "hybrid" in str(e.value)


@pytest.mark.parametrize("field,value", [("mamba_n_groups", 2),
                                         ("position_embedding_type", "rope"),
                                         ("layer_types", ("mamba", "moe"))])
def test_config_refuses_what_it_would_have_to_guess(field, value):
    with pytest.raises(UnsupportedMechanismError) as e:
        HybridConfig.tiny(**{field: value})
    assert e.value.cache_kind == "hybrid"


# -- (f) the other families' path ---------------------------------------------

def test_a_dense_engine_imports_nothing_of_the_family():
    """A Mistral-shaped rank imports the serving package and builds its
    engine; neither the family's model, nor its cache module, nor its state
    kernel is loaded."""
    code = (
        "import sys, jax\n"
        "from kubetorch_tpu.serve import GenerationEngine\n"
        "from kubetorch_tpu.models.llama import LlamaConfig, llama_init\n"
        "cfg = LlamaConfig.tiny(attn_impl='xla', remat=False)\n"
        "GenerationEngine(llama_init(jax.random.PRNGKey(0), cfg), cfg,\n"
        "                 slots=2, max_len=32, prefill_buckets=(8,))\n"
        "print(sorted(m for m in sys.modules if m.endswith(\n"
        "    ('models.hybrid', 'serve.state_cache', 'ops.ssm_state'))))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"

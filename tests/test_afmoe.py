"""Window and full attention layers with fine-grained experts
(``models/afmoe.py``, ``serve/window_cache.py``, the window operand of
``ops/attention.py``) at a tiny size on the CPU, against the plain float32
reference in ``tests/trinity_reference.py``, which masks whole T x T score
matrices and keeps no cache:

(a) the plain forward; a window no shorter than the sequence is plain
    causal attention;
(b) the flash prefill with a window, interpreted, against the masked
    einsums, for a band the blocks divide and one they do not;
(c) a prompt's ring: row r holds the last position congruent to r;
(d) the engine's prefill, then decode blocks through the full grid and the
    rings, against the reference's full forward, with every ring wrapped
    (a window of 8, prompts of 20-40); the decode kernel reading a ring's
    live rows; a slot whose previous occupant filled its ring; the tallies;
(e) the tolerance: a ring whose rows are rounded to bfloat16 misses it;
(f) sizes at the published widths; every mechanism the window cache does
    not carry raises its typed error; nothing of the family is on another
    family's serving path.

The comparisons run the model in float32, so they see the algorithm and not
bfloat16's rounding: 2e-4 on a log-probability is float32 arithmetic in a
different order (the engine's blocks, the reference's whole rows) with
room (the engine reads about 1e-6), and under a fifth of what rounding a
prompt's ring rows alone to bfloat16 moves (9.6e-4).
"""

import contextlib
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import trinity_reference as R
from kubetorch_tpu.exceptions import UnsupportedMechanismError
from kubetorch_tpu.models.afmoe import (FULL, WINDOW, AfmoeConfig,
                                        afmoe_forward, afmoe_init,
                                        band_attention)
from kubetorch_tpu.ops.attention import flash_attention
from kubetorch_tpu.serve import GenerationEngine
from kubetorch_tpu.serve import engine as E
from kubetorch_tpu.serve import window_cache as WC

pytestmark = pytest.mark.level("unit")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the largest log-probability error float32 arithmetic in another order
# leaves, with room (module docstring)
ATOL = 2e-4


@pytest.fixture(scope="module")
def tiny():
    """Five layers: a dense window layer, then expert layers window, full,
    window, full (two runs of each kind); a window of 8; float32."""
    cfg = AfmoeConfig.tiny(dtype=jnp.float32)
    return afmoe_init(jax.random.PRNGKey(0), cfg), cfg


def _tokens(seed, n, vocab=256):
    return np.random.RandomState(seed).randint(0, vocab, n).tolist()


def _ref_logprobs(tiny, prompt, served):
    params, cfg = tiny
    lp = jax.nn.log_softmax(R.forward(cfg, params, prompt + served), -1)
    return [float(lp[len(prompt) - 1 + i, t]) for i, t in enumerate(served)]


# -- (a) the plain forward ------------------------------------------------------

def test_forward_matches_the_reference(tiny):
    params, cfg = tiny
    toks = _tokens(1, 30)
    with jax.default_matmul_precision("highest"):
        got = afmoe_forward(params, jnp.asarray([toks]), cfg)[0]
    np.testing.assert_allclose(got, R.forward(cfg, params, toks),
                               rtol=1e-4, atol=1e-4)


def test_a_window_no_shorter_than_the_sequence_is_causal_attention(tiny):
    params, cfg = tiny
    toks = _tokens(2, 24)
    wide = AfmoeConfig.tiny(dtype=jnp.float32, sliding_window=24)
    with jax.default_matmul_precision("highest"):
        got = afmoe_forward(params, jnp.asarray([toks]), wide)[0]
    causal = R.forward(cfg, params, toks, window=False)
    np.testing.assert_allclose(got, causal, rtol=1e-4, atol=1e-4)
    # and the window of 8 is not: the band binds at 24 positions
    banded = R.forward(cfg, params, toks)
    assert float(jnp.max(jnp.abs(banded - causal))) > 1e-2
    q = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 4, 32))
    k, v = (jax.random.normal(jax.random.PRNGKey(s), (2, 24, 2, 32))
            for s in (4, 5))
    np.testing.assert_array_equal(band_attention(q, k, v, 24, 0.2),
                                  band_attention(q, k, v, None, 0.2))


# -- (b) the flash prefill's window -------------------------------------------

@pytest.mark.parametrize("t,window,block", [
    (512, 256, 128),      # the band's edge on block boundaries
    (512, 200, 128),      # ragged: the edge inside blocks
    (256, 8, 128),        # narrower than a block
    (512, 1024, 128),     # wider than the sequence: causal
])
def test_the_windowed_flash_prefill_is_the_masked_einsum(t, window, block):
    ks = jax.random.split(jax.random.PRNGKey(t + window), 3)
    q = jax.random.normal(ks[0], (1, t, 4, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, t, 2, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, t, 2, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = flash_attention(q, k, v, causal=True, scale=0.1, block_q=block,
                              block_k=block, interpret=True, window=window)
        want = band_attention(q, k, v, window, 0.1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_window_skips_key_blocks_below_the_band():
    """Blocks wholly below the band are not computed: garbage in them does
    not reach the output."""
    t, w, blk = 512, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, t, 2, 128))
    k = jax.random.normal(ks[1], (1, t, 2, 128))
    v = jax.random.normal(ks[2], (1, t, 2, 128))
    poisoned = v.at[:, :128].set(jnp.nan)
    got = flash_attention(q, k, poisoned, scale=0.1, block_q=blk,
                          block_k=blk, interpret=True, window=w)
    # queries from 256 on see no key under 129: their blocks never read it
    assert bool(jnp.all(jnp.isfinite(got[:, 256:])))
    assert not bool(jnp.all(jnp.isfinite(got[:, :256])))


# -- (c) a prompt's ring ----------------------------------------------------------

@pytest.mark.parametrize("true_len,t", [(21, 32), (8, 16), (5, 16), (32, 32)])
def test_a_prompts_ring_holds_its_last_positions(true_len, t):
    rows = jnp.arange(t, dtype=jnp.float32)[None, :, None, None]
    ring = np.asarray(WC.ring_rows(rows, jnp.int32(true_len), 8))[0, :, 0, 0]
    for r, p in enumerate(ring):
        if r < true_len:
            assert p % 8 == r and true_len - 8 <= p < true_len


# -- (d) the engine ----------------------------------------------------------------

def _engine(tiny, **kw):
    params, cfg = tiny
    kw = {"slots": 3, "max_len": 64, "prefill_buckets": (32, 64),
          "decode_block": 4, **kw}
    return GenerationEngine(params, cfg, **kw)


def test_engine_matches_the_reference_with_every_ring_wrapped(tiny):
    eng = _engine(tiny)
    assert eng._cache.k.shape == (2, 3, 2, 64, 32)      # full layers
    assert eng._cache.wk.shape == (3, 3, 2, 8, 32)      # window rings of 8
    prompts = [_tokens(i, n) for i, n in enumerate((20, 33, 40))]
    handles = [eng.submit(p, max_new_tokens=14) for p in prompts]
    while eng.step():
        pass
    for p, h in zip(prompts, handles):
        served = h.result(timeout=0)
        assert len(served) == 14
        np.testing.assert_allclose(h.logprobs,
                                   _ref_logprobs(tiny, p, served), atol=ATOL)
    s = eng.stats()
    # the tallies: a request's decode steps (its first token is the
    # prefill's), each reading 8 ring rows a window layer and pos + 1 rows
    # a full layer. The three retire in the same block of 4, and a slot
    # stays live to the end of the block it retires in
    params, cfg = tiny
    steps = s.decode_steps
    assert 13 <= steps <= 16
    assert s.window_rows_read == 3 * steps * 3 * 8
    assert s.full_rows_read == 2 * sum(
        sum(range(len(p) + 1, len(p) + steps + 1)) for p in prompts)
    assert (s.window_pairs_prefilled, s.full_pairs_prefilled) == tuple(
        map(sum, zip(*(cfg.prompt_pairs(len(p)) for p in prompts))))
    assert s.moe_routed_pairs.shape == (4, 8)
    assert int(s.moe_routed_pairs.sum()) == 3 * steps * 4 * 3
    m = eng.__kt_metrics__()
    assert m["engine_window_rows_read_total"] == s.window_rows_read


def test_the_prompts_sorted_pairs_are_counted_at_admission(tiny):
    """``moe_sorted_pairs``: a prompt whose bucket has more rows than
    ``experts.DENSE_ROWS_MAX`` (lowered to 32 here, for the programs traced
    inside) sends its real tokens' K choices through every expert layer's
    sorted path; one of 20 rows (bucket 32) takes the other path and counts
    nothing."""
    from kubetorch_tpu.models import experts
    programs = (E._prefill, E._decode_step, E._decode_block)
    prompts = [_tokens(30 + i, n) for i, n in enumerate((20, 33, 40))]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(experts, "DENSE_ROWS_MAX", 32)
        for f in programs:
            f.clear_cache()
        eng = _engine(tiny)
        hs = [eng.submit(p, max_new_tokens=2) for p in prompts]
        while eng.step():
            pass
    for f in programs:
        f.clear_cache()
    _, cfg = tiny
    for p, h in zip(prompts, hs):
        served = h.result(timeout=0)
        np.testing.assert_allclose(h.logprobs,
                                   _ref_logprobs(tiny, p, served), atol=ATOL)
    s = eng.stats()
    assert s.moe_sorted_pairs == (33 + 40) * cfg.experts_per_token * 4
    m = eng.__kt_metrics__()
    assert m["engine_moe_sorted_pairs_total"] == s.moe_sorted_pairs


def test_the_decode_kernel_reads_a_rings_live_rows():
    """Heads of 128, so the grid's attention takes the decode kernel
    (interpreted here): a slot 30 positions in reads its ring's 8 rows, one
    at position 5 its first 6."""
    cfg = AfmoeConfig.tiny(dtype=jnp.float32, head_dim=128)
    params = afmoe_init(jax.random.PRNGKey(4), cfg)
    prompts = [_tokens(20, 29), _tokens(21, 5)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(E, "_decode_kernel_wanted", lambda: True)
        for f in (E._decode_step, E._decode_block):
            f.clear_cache()
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(32,), decode_block=2)
        hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        while eng.step():
            pass
    for f in (E._decode_step, E._decode_block):
        f.clear_cache()
    for p, h in zip(prompts, hs):
        served = h.result(timeout=0)
        np.testing.assert_allclose(
            h.logprobs, _ref_logprobs((params, cfg), p, served), atol=ATOL)


def test_a_slot_whose_ring_a_longer_request_filled_reads_none_of_it(tiny):
    """One slot: the first request (30 + 12 tokens) fills all 8 ring rows;
    the second's prompt of 5 leaves rows 5-7 holding the first's keys until
    its own decode steps write them, and no step reads them before."""
    eng = _engine(tiny, slots=1)
    first = eng.submit(_tokens(7, 30), max_new_tokens=12)
    while eng.step():
        pass
    first.result(timeout=0)
    second_prompt = _tokens(8, 5)
    second = eng.submit(second_prompt, max_new_tokens=12)
    while eng.step():
        pass
    fresh = _engine(tiny, slots=1)
    again = fresh.submit(second_prompt, max_new_tokens=12)
    while fresh.step():
        pass
    assert second.result(timeout=0) == again.result(timeout=0)
    np.testing.assert_allclose(second.logprobs, again.logprobs, atol=1e-6)
    np.testing.assert_allclose(
        second.logprobs,
        _ref_logprobs(tiny, second_prompt, second.result(timeout=0)),
        atol=ATOL)


# -- (e) the tolerance ---------------------------------------------------------------

@contextlib.contextmanager
def _rings_rounded_to_bfloat16():
    """A prompt's ring rows rounded to bfloat16 before the splice: below
    the float32 the tiny configuration states."""
    programs = (E._prefill, E._decode_step, E._decode_block)
    ring_rows = WC.ring_rows
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(WC, "ring_rows", lambda rows, *a: ring_rows(
            rows.astype(jnp.bfloat16).astype(rows.dtype), *a))
        for f in programs:
            f.clear_cache()
        yield
    for f in programs:
        f.clear_cache()


def test_a_ring_rounded_to_bfloat16_misses_the_tolerance(tiny):
    prompt = _tokens(12, 36)
    with _rings_rounded_to_bfloat16():
        eng = _engine(tiny, slots=1)
        h = eng.submit(prompt, max_new_tokens=10)
        while eng.step():
            pass
    served = h.result(timeout=0)
    err = np.abs(np.asarray(h.logprobs)
                 - np.asarray(_ref_logprobs(tiny, prompt, served)))
    assert err.max() > 2 * ATOL


# -- (f) sizes, refusals, the other families' path ---------------------------------

def test_the_published_widths_and_the_cut():
    whole = AfmoeConfig()
    assert (whole.n_layers, whole.n_window, whole.n_full) == (32, 24, 8)
    assert whole.runs[:4] == ((WINDOW, "dense", 0, 2), (WINDOW, "moe", 2, 1),
                              (FULL, "moe", 0, 1), (WINDOW, "moe", 3, 3))
    cut = AfmoeConfig(layer_types=(WINDOW,) * 4 + (FULL,), num_dense_layers=1)
    assert cut.runs == ((WINDOW, "dense", 0, 1), (WINDOW, "moe", 1, 3),
                        (FULL, "moe", 0, 1))
    attn = 8_388_608 * 3 + 1_048_576 * 2
    assert attn == 27_262_976
    assert attn + 3 * 2048 * 6144 == 65_011_712
    expert_layer = attn + 262_144 + 6_291_456 + 128 * 6_291_456
    assert expert_layer == 839_122_944
    matrices = 65_011_712 + 4 * expert_layer + 819_986_432
    assert matrices == 4_241_489_920
    # norms: four a layer of 2,048, q and k of 128; the final norm; biases
    norms = 5 * (4 * 2048 + 2 * 128) + 2048 + 4 * 128
    assert cut.param_count() == matrices + norms == 4_241_534_720
    params = jax.eval_shape(lambda: afmoe_init(jax.random.PRNGKey(0), cut))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(params)) == cut.param_count()


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
    assert e.value.cache_kind == "window"


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
    assert "window" in str(e.value)


@pytest.mark.parametrize("field,value", [
    ("score_func", "softmax"), ("n_group", 2),
    ("layer_types", (WINDOW, "linear_attention"))])
def test_config_refuses_what_it_would_have_to_guess(field, value):
    with pytest.raises(UnsupportedMechanismError) as e:
        AfmoeConfig.tiny(**{field: value})
    assert e.value.cache_kind == "window"


def test_a_dense_engine_imports_nothing_of_the_family():
    """A Mistral-shaped rank builds its engine without loading the family's
    model or its cache module."""
    code = (
        "import sys, jax\n"
        "from kubetorch_tpu.serve import GenerationEngine\n"
        "from kubetorch_tpu.models.llama import LlamaConfig, llama_init\n"
        "cfg = LlamaConfig.tiny(attn_impl='xla', remat=False)\n"
        "GenerationEngine(llama_init(jax.random.PRNGKey(0), cfg), cfg,\n"
        "                 slots=2, max_len=32, prefill_buckets=(8,))\n"
        "print(sorted(m for m in sys.modules if m.endswith(\n"
        "    ('models.afmoe', 'serve.window_cache'))))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"

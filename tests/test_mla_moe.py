"""Latent attention and fine-grained experts (``models/mla.py``,
``serve/latent_cache.py``; ISSUE 33) at tiny widths on the CPU, against the
plain float32 reference in ``tests/mla_reference.py``:

(a) the program's full forward against the reference's logits;
(b) the engine's prefill at a padded bucket, then block decode through the
    latent cache, against the reference's full forward position by position;
    and the absorbed form of the attention against the expanded form alone;
(c) the router on crafted scores: the bias changes which experts are chosen
    and not their weights; the weights are normalised and scaled; group-
    limited selection over more than one group is refused;
(d) no token is dropped, however many an expert gets; padding claims nothing;
(g) every mechanism the latent cache does not carry raises its typed error;
and the routing tally of ``EngineStats``. (e) and (f) are cases of
``test_engine_runahead.py``, ``test_block.py`` and
``test_decode_block_compiles.py``.
"""

import contextlib
import dataclasses
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import mla_reference as R
from kubetorch_tpu.exceptions import UnsupportedMechanismError
from kubetorch_tpu.models import experts, mla
from kubetorch_tpu.models.block import decoder_block
from kubetorch_tpu.models.generate import ffn_block
from kubetorch_tpu.models.llama import rope_freqs
from kubetorch_tpu.models.mla import (MlaMoeConfig, mla_moe_forward,
                                      mla_moe_init)
from kubetorch_tpu.ops.moe_experts import moe_experts_supported
from kubetorch_tpu.serve import GenerationEngine

pytestmark = pytest.mark.level("unit")

F32 = dict(dtype=jnp.float32)


@contextlib.contextmanager
def dense_rows_max(n):
    """``experts.DENSE_ROWS_MAX`` set to ``n`` for the programs traced inside
    (0: every call sorts its pairs by expert). The constant is read at trace
    time and is no part of a jit's key, so the engine's programs, the jits
    that trace the expert layer, are dropped on the way in and out."""
    from kubetorch_tpu.serve import engine as E
    programs = (E._prefill, E._decode_step, E._decode_block)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(experts, "DENSE_ROWS_MAX", n)
        for f in programs:
            f.clear_cache()
        yield
    for f in programs:
        f.clear_cache()


@pytest.fixture(scope="module", params=["dense", "sorted"])
def tiny(request):
    """The tiny model under both formulations of the routed experts: every
    expert over every row (``experts.DENSE_ROWS_MAX`` rows at most), and pairs
    sorted by expert through the grouped matmul (forced here by a maximum of
    0)."""
    cfg = MlaMoeConfig.tiny(**F32)
    with dense_rows_max(512 if request.param == "dense" else 0):
        yield mla_moe_init(jax.random.PRNGKey(0), cfg), cfg


def _tokens(seed, n, vocab=256):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         vocab))


def _drive(eng):
    while eng.step():
        pass
    return eng


# -- (a) the full forward -----------------------------------------------------

@pytest.mark.parametrize("seed,length", [(1, 9), (2, 24), (3, 40)])
def test_forward_matches_the_reference(tiny, seed, length):
    params, cfg = tiny
    toks = _tokens(seed, length)
    got = np.asarray(mla_moe_forward(params, toks[None], cfg)[0])
    want = R.forward(params, toks, cfg)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_forward_in_bfloat16_stays_near_the_reference():
    cfg = MlaMoeConfig.tiny()
    params = mla_moe_init(jax.random.PRNGKey(0), cfg)
    toks = _tokens(4, 24)
    got = np.asarray(mla_moe_forward(params, toks[None], cfg)[0])
    want = R.forward(params, toks, cfg)
    # a router near a tie may flip in bfloat16: most positions agree closely
    err = np.abs(got - want).max(-1)
    assert np.median(err) < 0.08, err


# -- (b) prefill at a padded bucket, block decode through the latent cache ----

@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("plen", [5, 16, 19])
def test_engine_matches_the_reference_position_by_position(tiny, block, plen):
    params, cfg = tiny
    prompt = [int(t) for t in _tokens(10 + plen, plen)]
    eng = GenerationEngine(params, cfg, slots=3, max_len=64,
                           prefill_buckets=(16, 32), decode_block=block)
    h = eng.submit(prompt, max_new_tokens=14)
    _drive(eng)
    out = h.result(0)
    logits = R.forward(params, np.asarray(prompt + out), cfg)
    at = logits[plen - 1:-1]                     # predicts out[0], out[1], …
    assert out == [int(t) for t in at.argmax(-1)]
    lp = jax.nn.log_softmax(at, -1)[np.arange(len(out)), out]
    np.testing.assert_allclose(h.logprobs, lp, atol=1e-4)


def test_neighbouring_slots_do_not_see_each_other(tiny):
    params, cfg = tiny
    prompts = [[int(t) for t in _tokens(30 + i, 4 + 5 * i)] for i in range(3)]
    eng = GenerationEngine(params, cfg, slots=3, max_len=64,
                           prefill_buckets=(16,), decode_block=2)
    hs = [eng.submit(p, max_new_tokens=9 + i) for i, p in enumerate(prompts)]
    _drive(eng)
    for p, h in zip(prompts, hs):
        out = h.result(0)
        want = R.forward(params, np.asarray(p + out), cfg)[len(p) - 1:-1]
        assert out == [int(t) for t in want.argmax(-1)]


def test_absorbed_attention_is_the_expanded_attention(tiny):
    """The same layer, the same tokens: attention over expanded heads for the
    whole sequence, and the last token alone against the cached rows with
    ``W_kvb`` absorbed into the query and the output."""
    params, cfg = tiny
    lw = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    t = 13
    h = jax.random.normal(jax.random.PRNGKey(5), (2, t, cfg.dim), jnp.float32)
    freqs = rope_freqs(cfg, t)
    expanded, (rows,) = mla.expanded_mix(cfg, freqs)(h, lw, None)
    q_nope, q_pe, row, _ = mla.mla_project(cfg, h[:, -1:], lw,
                                           freqs[t - 1][None, None])
    np.testing.assert_allclose(row[:, 0], rows[:, -1, 0], atol=1e-5)
    # rows beyond the frontier hold another request's values: masked out
    padded = jnp.concatenate(
        [rows[:, :, 0], 7.0 * jnp.ones((2, 5, cfg.latent_dim))], axis=1)
    absorbed = mla.absorbed_attention(
        cfg, q_nope[:, 0], q_pe[:, 0], lw["wkv_b"], padded,
        jnp.full((2,), t - 1, jnp.int32))
    np.testing.assert_allclose(absorbed, expanded[:, -1], atol=1e-4)


# -- (c) the router -----------------------------------------------------------

def _router_case(bias):
    """8 experts, top 3: the scores are the sigmoid of the hidden state's
    first 8 values (an identity router)."""
    cfg = MlaMoeConfig.tiny(**F32)
    lw = {"router": jnp.eye(cfg.dim, cfg.n_experts, dtype=jnp.float32),
          "router_bias": jnp.asarray(bias, jnp.float32)}
    logit = np.zeros((2, cfg.dim), np.float32)
    logit[0, :8] = [3.0, 2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0]
    logit[1, :8] = [-3.0, 0.2, 0.1, 2.5, 0.0, 0.3, -0.1, 1.0]
    return cfg, lw, jnp.asarray(logit)


def test_router_weights_are_normalised_and_scaled():
    cfg, lw, h = _router_case(np.zeros(8))
    w, idx = experts.route(cfg, h, lw)
    assert sorted(idx[0].tolist()) == [0, 1, 2]
    assert sorted(idx[1].tolist()) == [3, 5, 7]
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.446, rtol=1e-6)
    s = 1.0 / (1.0 + np.exp(-np.asarray([3.0, 2.0, 1.0])))
    got = dict(zip(idx[0].tolist(), np.asarray(w[0]).tolist()))
    np.testing.assert_allclose([got[0], got[1], got[2]],
                               2.446 * s / s.sum(), rtol=1e-6)
    dense, ref_idx = R.route(cfg, h, lw["router"], lw["router_bias"])
    assert sorted(ref_idx[0].tolist()) == [0, 1, 2]
    np.testing.assert_allclose(np.asarray(dense)[0, :3], 2.446 * s / s.sum(),
                               rtol=1e-6)


def test_router_bias_changes_the_choice_and_not_the_weights():
    bias = np.zeros(8)
    bias[7] = 1.0                  # lifts the last expert over the third
    cfg, lw, h = _router_case(bias)
    w, idx = experts.route(cfg, h, lw)
    assert sorted(idx[0].tolist()) == [0, 1, 7]
    s = 1.0 / (1.0 + np.exp(-np.asarray([3.0, 2.0, -3.0])))    # unbiased
    got = dict(zip(idx[0].tolist(), np.asarray(w[0]).tolist()))
    np.testing.assert_allclose([got[0], got[1], got[7]],
                               2.446 * s / s.sum(), rtol=1e-5)
    dense, _ = R.route(cfg, h, lw["router"], lw["router_bias"])
    np.testing.assert_allclose(np.asarray(dense)[0, [0, 1, 7]],
                               2.446 * s / s.sum(), rtol=1e-5)
    assert np.asarray(dense)[0, 2] == 0.0


@pytest.mark.parametrize("field,value", [
    ("n_group", 8), ("topk_group", 4),
    ("scoring_func", "softmax"), ("topk_method", "greedy")])
def test_config_refuses_what_it_would_have_to_guess(field, value):
    with pytest.raises(UnsupportedMechanismError) as e:
        MlaMoeConfig.tiny(**{field: value})
    assert field in e.value.mechanism


# -- (d) dropless; padding claims nothing -------------------------------------

@pytest.mark.parametrize("rows_max", [0, 512])
def test_an_overfull_expert_drops_nothing(rows_max):
    """Every token's first choice is expert 0: P tokens where a capacity of
    1.25·P·K/E would keep 1.25·P·3/8."""
    cfg = MlaMoeConfig.tiny(**F32)
    params = mla_moe_init(jax.random.PRNGKey(3), cfg)
    bias = np.asarray(params["layers"]["router_bias"]).copy()
    bias[:, 0] = 5.0
    params["layers"]["router_bias"] = jnp.asarray(bias)
    toks = _tokens(6, 32)
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 32, cfg.dim))
    lw = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    with dense_rows_max(rows_max):
        _, tally = experts.moe_ffn_dropless(cfg, h, lw)
        got = np.asarray(mla_moe_forward(params, toks[None], cfg)[0])
    assert int(tally[0, 0]) == 32 > 1.25 * 32 * 3 / 8
    np.testing.assert_allclose(got, R.forward(params, toks, cfg), atol=2e-4)


def test_padding_claims_no_expert_and_counts_nothing(tiny):
    params, cfg = tiny
    lw = jax.tree_util.tree_map(lambda a: a[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 16, cfg.dim))
    real = 11
    mask = (jnp.arange(16) < real)[None]
    padded, tally = ffn_block(cfg, h, lw, token_mask=mask)
    alone, tally_alone = ffn_block(cfg, h[:, :real], lw)
    np.testing.assert_allclose(padded[:, :real], alone, atol=1e-5)
    np.testing.assert_array_equal(tally, tally_alone)
    assert int(tally[0].sum()) == real * cfg.experts_per_token
    # a padded position gets the shared expert alone: nothing was routed
    shared = experts.dense_ffn(h[0, real:], lw["shared"])[0]
    np.testing.assert_allclose(padded[0, real:], shared, atol=1e-5)


@pytest.mark.parametrize("plen", [3, 16])
def test_prefill_at_a_padded_bucket_is_the_unpadded_prompt(tiny, plen):
    from kubetorch_tpu.serve import engine as E
    params, cfg = tiny
    prompt = _tokens(40 + plen, plen)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :plen] = prompt
    first, rows, v, lps = E._prefill(
        params, jnp.asarray(padded), jnp.int32(plen), jax.random.PRNGKey(0),
        jnp.zeros((1,), jnp.float32), cfg)
    assert v is None
    assert rows.shape == (cfg.n_layers, 1, 16, 1, cfg.latent_dim)
    want = R.forward(params, prompt, cfg)[-1]
    assert int(first[0]) == int(want.argmax())
    np.testing.assert_allclose(
        float(lps[0]), float(jax.nn.log_softmax(want)[int(first[0])]),
        atol=1e-4)


# -- the block is still one ---------------------------------------------------

def test_the_layer_goes_through_the_one_block(tiny, monkeypatch):
    """Both layer kinds of the plain forward, the prefill and the decode step
    enter ``models.block.decoder_block``."""
    from kubetorch_tpu.serve import engine as E
    params, cfg = tiny
    seen = []

    def spy(cfg_, x, lw, mix, ffn, **kw):
        seen.append("router" in lw)
        return decoder_block(cfg_, x, lw, mix, ffn, **kw)

    monkeypatch.setattr(mla, "decoder_block", spy)
    monkeypatch.setattr(E, "decoder_block", spy)
    mla_moe_forward(params, _tokens(1, 6)[None], cfg)
    assert seen == [False, True]                 # one body a layer kind
    del seen[:]
    eng = GenerationEngine(params, dataclasses.replace(cfg, max_seq_len=96),
                           slots=2, max_len=32, prefill_buckets=(8,),
                           decode_block=2)
    eng.submit([1, 2, 3], max_new_tokens=3)
    _drive(eng)
    assert seen == [False, True, False, True]    # prefill, then the block


# -- the routing tally --------------------------------------------------------

def test_stats_tally_the_routed_pairs_of_live_slots(tiny):
    params, cfg = tiny
    eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                           prefill_buckets=(8,), decode_block=4)
    assert eng.stats().moe_routed_pairs.shape == (cfg.n_moe_layers,
                                                  cfg.n_experts)
    assert eng.stats().moe_routed_pairs.sum() == 0
    hs = [eng.submit([5, 6, 7 + i], max_new_tokens=9) for i in range(2)]
    _drive(eng)
    assert [len(h.result(0)) for h in hs] == [9, 9]
    s = eng.stats()
    # 9 tokens: one from the prefill, eight from two blocks of four; two of
    # four slots live, the idle ones route nothing
    steps, live, k = 8, 2, cfg.experts_per_token
    assert s.decode_steps == steps
    assert s.moe_routed_pairs.sum(-1).tolist() == [steps * live * k] * 2
    assert (s.moe_expert_hits <= steps).all()
    assert (s.moe_expert_hits <= s.moe_routed_pairs).all()
    assert ((s.moe_expert_hits > 0) == (s.moe_routed_pairs > 0)).all()
    m = eng.__kt_metrics__()
    assert m["engine_moe_routed_pairs_total"] == 2 * steps * live * k
    assert m["engine_moe_expert_hits_total"] == s.moe_expert_hits.sum()
    assert m["engine_moe_layer0_load_max_over_mean"] >= 1.0


def test_a_scrape_beside_the_loop_never_fetches_the_tally(tiny):
    params, cfg = tiny
    eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                           prefill_buckets=(8,), decode_block=2).start()
    try:
        eng.generate([1, 2, 3], max_new_tokens=7, timeout=120)
        seen = {}
        t = threading.Thread(target=lambda: seen.update(
            other=eng.stats().moe_routed_pairs.sum()))
        t.start()
        t.join(30)
        at_boundary = eng.at_batch_boundary(
            lambda: eng.stats().moe_routed_pairs.sum(), timeout=30)
        assert at_boundary == 3 * 2 * cfg.experts_per_token * 2
        assert seen["other"] in (0, at_boundary)     # the last reading
        assert eng.stats().moe_routed_pairs.sum() == at_boundary
    finally:
        eng.stop()


@pytest.mark.parametrize("widths", ["tiny", "lane-aligned"])
def test_the_two_formulations_of_the_experts_agree(tiny, widths):
    """Rows past ``experts.DENSE_ROWS_MAX`` are sorted by expert, the rest go
    through every expert: one layer, the same rows, the same output. Where
    the grouped kernel runs (``ops.moe_experts``: the chip's branch, chosen
    here and interpreted; the tiny model's widths are not lane-aligned) it is
    the third formulation: the same output again, and it ran."""
    params, cfg = tiny
    if widths == "lane-aligned":
        cfg = dataclasses.replace(cfg, dim=128, moe_ffn_dim=128)
        params = mla_moe_init(jax.random.PRNGKey(0), cfg)
    lw = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    for rows in (2, 16, 40):
        h = jax.random.normal(jax.random.PRNGKey(rows), (1, rows, cfg.dim))
        outs, kernel_rows = [], []
        # sorted runs; every expert, as einsums; the same through the kernel
        for dense, on_chip in ((0, False), (20, False), (512, False),
                               (20, True)):
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(experts, "DENSE_ROWS_MAX", dense)     # eager: no jit
                if on_chip:
                    mp.setattr(experts, "moe_experts_auto", moe_experts_supported)
                kernel = experts.moe_experts
                mp.setattr(experts, "moe_experts", lambda x, *a: (
                    kernel_rows.append(x.shape[0]), kernel(x, *a))[1])
                outs.append(experts.moe_ffn_dropless(cfg, h, lw)[0])
        for out in outs[1:]:
            np.testing.assert_allclose(outs[0], out, atol=1e-5)
        took_kernel = widths == "lane-aligned" and rows <= 20
        assert kernel_rows == ([rows] if took_kernel else [])


def test_dense_and_mixtral_engines_keep_no_tally():
    from kubetorch_tpu.models.llama import LlamaConfig, llama_init
    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    eng = GenerationEngine(llama_init(jax.random.PRNGKey(0), cfg), cfg,
                           slots=2, max_len=32, prefill_buckets=(8,))
    s = eng.stats()
    assert s.moe_routed_pairs is None and s.moe_expert_hits is None
    assert not any("moe" in k for k in eng.__kt_metrics__())


# -- (g) what the latent cache does not carry yet -----------------------------

def _refused_engine(tiny, **kw):
    params, cfg = tiny
    return GenerationEngine(params, cfg, slots=2, max_len=32,
                            prefill_buckets=(8,), **kw)


@pytest.mark.parametrize("mechanism,kwargs", [
    ("quantize_kv", {"quantize_kv": True}),
    ("prefill_chunk", {"prefill_chunk": 8}),
    ("auto_prefix", {"auto_prefix": True}),
    ("aot_cache", {"aot_cache": object()}),
])
def test_engine_construction_refuses_by_name(tiny, mechanism, kwargs):
    with pytest.raises(UnsupportedMechanismError) as e:
        _refused_engine(tiny, **kwargs)
    assert mechanism in e.value.mechanism
    assert e.value.cache_kind == "latent"
    assert isinstance(e.value, NotImplementedError)


def test_engine_construction_refuses_a_mesh(tiny, cpu_mesh_devices):
    from kubetorch_tpu.parallel.mesh import build_mesh
    from kubetorch_tpu.parallel.mesh_context import use_mesh
    mesh = build_mesh({"tensor": 2}, devices=cpu_mesh_devices[:2])
    with use_mesh(mesh), pytest.raises(UnsupportedMechanismError) as e:
        _refused_engine(tiny)
    assert "mesh" in e.value.mechanism


@pytest.mark.parametrize("mechanism", ["register_prefix", "register_adapter",
                                       "SpeculativeEngine", "generate"])
def test_calls_refuse_by_name(tiny, mechanism):
    params, cfg = tiny
    with pytest.raises(UnsupportedMechanismError) as e:
        if mechanism == "register_prefix":
            _refused_engine(tiny).register_prefix([1, 2, 3])
        elif mechanism == "register_adapter":
            _refused_engine(tiny).register_adapter({}, None)
        elif mechanism == "SpeculativeEngine":
            from kubetorch_tpu.serve import SpeculativeEngine
            SpeculativeEngine(params, cfg, params, cfg, slots=2, max_len=32,
                              prefill_buckets=(8,))
        else:
            from kubetorch_tpu.models.generate import generate
            generate(params, jnp.asarray([[1, 2, 3]]), cfg, max_new_tokens=2)
    assert mechanism in e.value.mechanism
    assert "latent" in str(e.value)


def test_the_aot_key_takes_the_cache_kind(tiny):
    from kubetorch_tpu.models.llama import LlamaConfig, llama_init
    from kubetorch_tpu.serve.aot_cache import AOTKey
    from kubetorch_tpu.serve.engine import GRID_LAYOUT
    latent = AOTKey.for_engine(_refused_engine(tiny))
    cfg = LlamaConfig.tiny(attn_impl="xla", remat=False)
    dense = AOTKey.for_engine(GenerationEngine(
        llama_init(jax.random.PRNGKey(0), cfg), cfg, slots=2, max_len=32,
        prefill_buckets=(8,)))
    assert dense.grid_layout == GRID_LAYOUT
    assert latent.grid_layout.startswith("latent:")
    assert latent.digest() != dense.digest()

"""GLM-5's layer through ``models/mla.py`` and the engine (ISSUE 35) at tiny
widths on the CPU, against the plain float32 reference in
``tests/glm5_reference.py``: a query rank, the sparse-attention indexer with
a cache leaf of its own, and a share of the routed experts. ``index_topk`` is
8 and contexts run to 24-40, so the selection really drops rows.

(a) the program's full forward against the reference's logits, untiled and
    with a prompt's queries tiled;
(b) the engine's prefill, then decode through both leaves, against the
    reference position by position: blocks of 1 and 8, a prompt shorter and
    one longer than ``index_topk``;
(c) the selected sets, a prompt's mask and decode's gathered rows, equal the
    reference's wherever its own margin is clear; the sort-free threshold;
(d) the shares add up: the sum over all shares of a layer, the shared expert
    counted once, is the uncut reference's layer, on every path of the
    routed experts;
(e) the routing tally and the ``dsa_*`` counters count live slots only;
(f) what the two-leaf cache does not carry raises its typed error.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import glm5_reference as R
from tests.test_mla_moe import _drive, dense_rows_max
from kubetorch_tpu.exceptions import UnsupportedMechanismError
from kubetorch_tpu.models import mla
from kubetorch_tpu.models.llama import rope_freqs
from kubetorch_tpu.models.mla import (MlaMoeConfig, mla_moe_forward,
                                      mla_moe_init)
from kubetorch_tpu.serve import GenerationEngine
from kubetorch_tpu.serve import engine as E
from kubetorch_tpu.serve import latent_cache as LC

pytestmark = pytest.mark.level("unit")

TOPK = 8
# GLM-5's shape in small: a query rank, values wider than the un-rotated
# key, an indexer of 8 heads (with few heads many scores are exactly 0, every
# head's product negative, and tie), and experts 2..5 of 8 held
GLM = dict(q_lora_rank=24, v_head_dim=24, index_n_heads=8, index_head_dim=16,
           index_topk=TOPK, dtype=jnp.float32)


def glm_tiny(**kw):
    return MlaMoeConfig.tiny(**{**GLM, **kw})


@pytest.fixture(scope="module")
def tiny():
    cfg = glm_tiny(held=(2, 4))
    return mla_moe_init(jax.random.PRNGKey(0), cfg), cfg


def _tokens(seed, n, vocab=256):
    return np.random.RandomState(seed).randint(1, vocab, n)


# -- (a) forward ---------------------------------------------------------------

@pytest.mark.parametrize("seed,length,tile_bytes", [
    (1, 7, None), (2, 24, None), (3, 40, None),
    (4, 32, 4 * 4 * 8 * 32),        # 8 queries a tile of 32 keys, 4 heads
    (5, 24, 1)])                    # a query a tile
def test_forward_matches_the_reference(tiny, seed, length, tile_bytes,
                                       monkeypatch):
    params, cfg = tiny
    if tile_bytes:
        monkeypatch.setattr(mla, "SCORE_TILE_BYTES", tile_bytes)
        assert mla._query_tile(cfg, length) == (8 if length == 32 else 1)
    toks = _tokens(seed, length)
    got = mla_moe_forward(params, jnp.asarray(toks)[None], cfg)[0]
    np.testing.assert_allclose(got, R.forward(params, toks, cfg),
                               atol=2e-4, rtol=1e-4)


def test_the_selection_changes_the_answer(tiny):
    """The tiny model is no fixed point of the mechanism: with the indexer
    switched off in the REFERENCE the logits past ``index_topk`` differ."""
    params, cfg = tiny
    toks = _tokens(9, 24)
    sparse = R.forward(params, toks, cfg)
    full = R.forward(params, toks, dataclasses.replace(cfg, index_n_heads=0))
    np.testing.assert_allclose(sparse[:TOPK], full[:TOPK], atol=1e-5)
    assert np.abs(sparse[TOPK:] - full[TOPK:]).max() > 1e-3


def test_forward_in_bfloat16_stays_near_the_reference():
    cfg = glm_tiny(held=(2, 4), dtype=jnp.bfloat16)
    params = mla_moe_init(jax.random.PRNGKey(0), cfg)
    toks = _tokens(2, 24)
    got = np.asarray(mla_moe_forward(params, jnp.asarray(toks)[None], cfg)[0])
    want = R.forward(params, toks, cfg)
    assert np.abs(got - want).mean() < 0.05


def test_the_query_tile_follows_the_score_bytes():
    cfg = MlaMoeConfig(n_heads=64, n_layers=2)
    assert mla._query_tile(cfg, 8192) == 256       # 64 x 256 x 8192 x 4 B
    assert mla._query_tile(cfg, 1024) == 1024
    kimi = MlaMoeConfig(n_layers=2)
    assert [mla._query_tile(kimi, t) for t in (256, 512, 1024)] == [
        256, 512, 1024]                            # its buckets: one array


# -- (b) the engine, both leaves -------------------------------------------------

@pytest.mark.parametrize("block", [1, 8])
@pytest.mark.parametrize("plen", [5, 19])
def test_engine_matches_the_reference_position_by_position(tiny, block, plen):
    params, cfg = tiny
    eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                           prefill_buckets=(8, 32), decode_block=block)
    prompt = _tokens(plen, plen).tolist()
    h = eng.submit(prompt, max_new_tokens=17)
    _drive(eng)
    out = h.result(0)
    want = R.forward(params, np.asarray(prompt + out), cfg)[plen - 1:-1]
    assert out == [int(t) for t in want.argmax(-1)]
    served = np.asarray(h.logprobs)
    ref = jax.nn.log_softmax(want, -1)[np.arange(len(out)), out]
    np.testing.assert_allclose(served, ref, atol=2e-4)


def test_the_cache_has_a_leaf_for_the_indexers_keys(tiny):
    params, cfg = tiny
    eng = GenerationEngine(params, cfg, slots=3, max_len=64,
                           prefill_buckets=(16,), decode_block=4)
    assert isinstance(eng._cache, LC.IndexedLatentCache)
    assert eng._cache.c.shape == (3, 3, 1, 64, cfg.latent_dim)
    assert eng._cache.ki.shape == (3, 3, 1, 64, cfg.index_head_dim)
    prompt = _tokens(3, 11).tolist()
    h = eng.submit(prompt, max_new_tokens=6)
    _drive(eng)
    assert len(h.result(0)) == 6
    keys = np.asarray(eng._cache.ki)
    slot = int(np.abs(keys).sum((0, 2, 3, 4)).argmax())
    # 11 prompt rows spliced (the bucket's padding behind them), 5 decoded
    # in two blocks of 4: rows 11 .. 18
    assert (np.abs(keys[:, slot, 0, :19]).sum(-1) > 0).all()
    assert not keys[:, slot, 0, 19:].any()
    # a config without an indexer keeps the one-leaf cache
    plain = MlaMoeConfig.tiny()
    assert isinstance(LC.init_grid(plain, 2, 8), LC.LatentCache)
    assert LC.grid_layout(plain) == LC.GRID_LAYOUT
    assert "index" in LC.grid_layout(cfg)


def test_neighbouring_slots_do_not_see_each_other(tiny):
    params, cfg = tiny
    eng = GenerationEngine(params, cfg, slots=3, max_len=64,
                           prefill_buckets=(8, 32), decode_block=4)
    prompts = [_tokens(20 + i, n).tolist() for i, n in enumerate((4, 13, 23))]
    hs = [eng.submit(p, max_new_tokens=9 + i) for i, p in enumerate(prompts)]
    _drive(eng)
    for p, h in zip(prompts, hs):
        out = h.result(0)
        want = R.forward(params, np.asarray(p + out), cfg)[len(p) - 1:-1]
        assert out == [int(t) for t in want.argmax(-1)]


# -- (c) the selection -----------------------------------------------------------

def _layer_inputs(tiny, t, seed=5):
    params, cfg = tiny
    lw = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, t, cfg.dim),
                          jnp.float32)
    return cfg, lw, h


def test_a_prompts_mask_is_the_references_selection(tiny):
    cfg, lw, h = _layer_inputs(tiny, 40)
    freqs = rope_freqs(cfg, 40)
    _, _, _, cq = mla.mla_project(cfg, h, lw, freqs)
    q, k, w = mla.index_project(cfg, h, cq, lw, freqs)
    scores = mla.index_scores(q, w, k)[0]
    want_scores = R.index_scores(
        cfg, h[0], R._norm(R._mm(h[0], lw["wq_a"]), lw["q_norm"],
                           cfg.norm_eps), lw)
    np.testing.assert_allclose(scores, want_scores, atol=1e-5)
    causal = jnp.tril(jnp.ones((40, 40), bool))
    got = np.asarray(mla.select_mask(cfg, scores, causal))
    want, margin = (np.asarray(a) for a in R.selection(cfg, want_scores))
    clear = margin > 1e-6
    assert clear.sum() >= 32                    # nearly every query
    np.testing.assert_array_equal(got[clear], want[clear])
    assert (got.sum(-1) == np.minimum(np.arange(40) + 1, TOPK)).all()


def test_decodes_rows_are_the_references_selection(tiny):
    """The gathered form: row numbers, with ``ok`` false where a slot has
    fewer cached rows than ``index_topk``; reserved rows past the frontier
    hold another request's keys and are never chosen."""
    cfg, _, _ = _layer_inputs(tiny, 1)
    rng = np.random.RandomState(0)
    scores = jnp.asarray(rng.randn(3, 32).astype(np.float32)) + 50.0 * (
        jnp.arange(32) >= 20)                   # stale rows score highest
    pos = jnp.asarray([3, 19, 11], jnp.int32)
    rows, ok = (np.asarray(a) for a in mla.select_rows(cfg, scores, pos))
    assert rows.shape == ok.shape == (3, TOPK)
    for b, p in enumerate([3, 19, 11]):
        want = set(np.argsort(-np.asarray(scores)[b, :p + 1])[:TOPK].tolist())
        assert set(rows[b][ok[b]].tolist()) == want
        assert ok[b].sum() == min(p + 1, TOPK)


@pytest.mark.parametrize("k", [1, 5, 64])
def test_kth_largest_is_the_sorted_rows_kth(k):
    rng = np.random.RandomState(k)
    x = (rng.randn(6, 64) * 10.0 ** rng.randint(-3, 4, (6, 1))).astype(
        np.float32)
    x[0, :40] = mla.NEG_INF                     # masked keys
    x[1] = -np.abs(x[1])                        # all negative
    x[2, 7] = x[2, 9]                           # a tie
    x[3, :3] = [0.0, -0.0, 1e-30]
    got = np.asarray(mla._kth_largest(jnp.asarray(x), k))
    want = np.sort(x, axis=-1)[:, ::-1][:, k - 1:k]
    np.testing.assert_array_equal(got, want)


# -- (d) the shares add up -------------------------------------------------------

@pytest.mark.parametrize("path", ["dense", "sorted", "sorted-chunked"])
def test_the_shares_of_a_layer_add_up_to_the_whole(path, monkeypatch):
    """An 8-expert layer cut into 4 shares of 2: every share routes over all
    8, adds its own experts' products and the shared expert; their sum less
    three shared experts is the uncut layer of the reference."""
    whole = glm_tiny()
    params = mla_moe_init(jax.random.PRNGKey(3), whole)
    lw = jax.tree_util.tree_map(lambda a: a[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole.dim),
                          jnp.float32)
    if path == "sorted-chunked":                # 48 rows x 3: 9 chunks of 16
        monkeypatch.setattr(mla, "HELD_PAIRS_CHUNK", 16)
    total, pairs = 0.0, 0
    with dense_rows_max(512 if path == "dense" else 0):
        for first in (0, 2, 4, 6):
            cfg = glm_tiny(held=(first, 2))
            share = {**lw, "banks": {k: v[first:first + 2]
                                     for k, v in lw["banks"].items()}}
            out, tally = mla.moe_ffn_dropless(cfg, h, share)
            assert tally.shape == (2, 2)
            total, pairs = total + out, pairs + int(tally[0].sum())
    assert pairs == 48 * whole.experts_per_token        # each pair once
    flat = h.reshape(48, -1)
    shared = R._swiglu(flat, lw["shared"])
    gates, _ = R.route(whole, flat, lw["router"], lw["router_bias"])
    want = shared + sum(
        gates[:, e:e + 1] * R._swiglu(flat, {k: w[e] for k, w in
                                             lw["banks"].items()})
        for e in range(8))
    np.testing.assert_allclose((total - 3 * shared.reshape(total.shape)),
                               want.reshape(total.shape), atol=2e-5)


def test_a_share_is_the_references_share(tiny):
    """One layer whole, with its held run: the program's expert layer and
    the reference's, which loops over the held banks by their global
    number."""
    params, cfg = tiny
    lw = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(8), (30, cfg.dim), jnp.float32)
    want = R.layer(cfg, x, lw)
    none_held = R.layer(cfg, x, lw, held=(0, 0))
    assert np.abs(want - none_held).max() > 1e-3        # the held ones matter
    from kubetorch_tpu.models.block import decoder_block
    from kubetorch_tpu.models.generate import ffn_block
    from functools import partial
    got, _, _ = decoder_block(cfg, x[None], lw,
                              mla.expanded_mix(cfg, rope_freqs(cfg, 30)),
                              partial(ffn_block, cfg))
    np.testing.assert_allclose(got[0], want, atol=2e-4)


# -- (e) counters ----------------------------------------------------------------

def test_stats_count_the_rows_of_live_slots(tiny):
    params, cfg = tiny
    eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                           prefill_buckets=(8, 16), decode_block=4)
    s = eng.stats()
    assert s.dsa_rows_scored.shape == s.dsa_rows_selected.shape == (3,)
    assert s.moe_routed_pairs.shape == (cfg.n_moe_layers, 4)     # the held
    assert s.dsa_rows_scored.sum() == s.moe_routed_pairs.sum() == 0
    plens = (3, 12)
    hs = [eng.submit(_tokens(i, n).tolist(), max_new_tokens=9)
          for i, n in enumerate(plens)]
    _drive(eng)
    assert [len(h.result(0)) for h in hs] == [9, 9]
    s = eng.stats()
    # 9 tokens: one from the prefill, eight from two blocks of four. The
    # step that makes token j reads the rows 0 .. plen + j - 1; two of four
    # slots live, the idle ones count nothing
    assert s.decode_steps == 8
    scored = sum(p + j for p in plens for j in range(1, 9))
    selected = sum(min(p + j, TOPK) for p in plens for j in range(1, 9))
    assert s.dsa_rows_scored.tolist() == [scored] * 3
    assert s.dsa_rows_selected.tolist() == [selected] * 3
    # of the 8 x 2 x K routed pairs a layer, those that met a held expert
    k = cfg.experts_per_token
    assert (0 < s.moe_routed_pairs.sum(-1)).all()
    assert (s.moe_routed_pairs.sum(-1) < 8 * 2 * k).all()
    assert (s.moe_expert_hits <= 8).all()
    m = eng.__kt_metrics__()
    assert m["engine_dsa_rows_scored_total"] == 3 * scored
    assert m["engine_dsa_rows_selected_total"] == 3 * selected


def test_the_row_counters_do_not_wrap():
    """The device keeps the running sum as two words: a step's count lands
    in the low one and carries over."""
    word = E._DSA_WORD
    tally = {"dsa": jnp.asarray([[[3, (1 << word) - 5], [0, 7]]], jnp.int32)}
    out = E._tally_add(tally, {"dsa": jnp.asarray([[11, 1]], jnp.int32)})
    assert out["dsa"].tolist() == [[[4, 6], [0, 8]]]


def test_engines_without_an_indexer_count_no_rows():
    cfg = MlaMoeConfig.tiny(dtype=jnp.float32)
    eng = GenerationEngine(mla_moe_init(jax.random.PRNGKey(0), cfg), cfg,
                           slots=2, max_len=32, prefill_buckets=(8,))
    s = eng.stats()
    assert s.dsa_rows_scored is None and s.dsa_rows_selected is None
    assert s.moe_routed_pairs is not None
    assert not any("dsa" in k for k in eng.__kt_metrics__())
    assert E._tally_shapes(cfg) == {"moe": (2, 2, 8)}


# -- (f) refusals, and what stopped being refused ----------------------------------

def test_a_query_rank_is_served_and_an_indexer_needs_one():
    assert MlaMoeConfig.tiny(q_lora_rank=24).q_lora_rank == 24
    with pytest.raises(ValueError, match="q_lora_rank"):
        MlaMoeConfig.tiny(index_n_heads=2)
    with pytest.raises(ValueError, match="held"):
        glm_tiny(held=(6, 4))
    cfg = glm_tiny(held=(2, 4))
    assert (cfg.n_held, cfg.held_first, cfg.n_experts) == (4, 2, 8)
    assert cfg.routed_tally_shape == (2, 2, 4)
    assert cfg.dsa_tally_shape == (3, 2)
    shapes = jax.eval_shape(lambda: mla_moe_init(jax.random.PRNGKey(0), cfg))
    assert shapes["layers"]["banks"]["w_up"].shape == (2, 4, 64, 32)
    assert shapes["layers"]["router"].shape == (2, 64, 8)
    assert "wq" not in shapes["layers"]
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert cfg.param_count() == n


@pytest.mark.parametrize("field,value", [
    ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
    ("topk_method", "greedy")])
def test_config_refuses_what_it_would_have_to_guess(field, value):
    with pytest.raises(UnsupportedMechanismError) as e:
        glm_tiny(**{field: value})
    assert field in e.value.mechanism


@pytest.mark.parametrize("mechanism,kwargs", [
    ("quantize_kv", {"quantize_kv": True}),
    ("prefill_chunk", {"prefill_chunk": 8}),
    ("auto_prefix", {"auto_prefix": True}),
    ("aot_cache", {"aot_cache": object()}),
    ("register_prefix", None), ("register_adapter", None),
    ("SpeculativeEngine", None), ("generate", None), ("mesh", None)])
def test_the_two_leaf_cache_refuses_by_name(tiny, mechanism, kwargs,
                                            cpu_mesh_devices):
    params, cfg = tiny

    def engine(**kw):
        return GenerationEngine(params, cfg, slots=2, max_len=32,
                                prefill_buckets=(8,), **kw)

    with pytest.raises(UnsupportedMechanismError) as e:
        if kwargs is not None:
            engine(**kwargs)
        elif mechanism == "register_prefix":
            engine().register_prefix([1, 2, 3])
        elif mechanism == "register_adapter":
            engine().register_adapter({}, None)
        elif mechanism == "SpeculativeEngine":
            from kubetorch_tpu.serve import SpeculativeEngine
            SpeculativeEngine(params, cfg, params, cfg, slots=2, max_len=32,
                              prefill_buckets=(8,))
        elif mechanism == "generate":
            from kubetorch_tpu.models.generate import generate
            generate(params, jnp.asarray([[1, 2, 3]]), cfg, max_new_tokens=2)
        else:
            from kubetorch_tpu.parallel.mesh import build_mesh
            from kubetorch_tpu.parallel.mesh_context import use_mesh
            with use_mesh(build_mesh({"tensor": 2},
                                     devices=cpu_mesh_devices[:2])):
                engine()
    assert mechanism in e.value.mechanism
    assert e.value.cache_kind == "latent"


def test_the_aot_key_takes_the_second_leaf(tiny):
    from kubetorch_tpu.serve.aot_cache import AOTKey
    params, cfg = tiny
    plain = MlaMoeConfig.tiny(dtype=jnp.float32)

    def key(p, c):
        return AOTKey.for_engine(GenerationEngine(
            p, c, slots=2, max_len=32, prefill_buckets=(8,)))

    two = key(params, cfg)
    one = key(mla_moe_init(jax.random.PRNGKey(0), plain), plain)
    assert two.grid_layout.startswith(one.grid_layout)
    assert "index" in two.grid_layout and two.digest() != one.digest()

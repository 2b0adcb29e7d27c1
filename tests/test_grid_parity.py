"""The engine's head-major slot grid against a plain float32 forward
(ROADMAP S2's gate that costs no chip time).

Tiny widths on the CPU, both attention paths (the Pallas kernel in
interpret mode and the masked einsum): prefill → ``_splice_slot`` → two
decode blocks through the grid (L, SLOTS, NKV, S_max, Hd) must say what a
float32 full forward of the same tokens says, for dense and MoE models over
a bfloat16 and an int8 grid; ``spec_engine._grid_ingest`` is held to the
same reference; and a slot that reaches row S_max−1 in the middle of a block
(``dynamic_update_slice`` clamps its overshoot writes) keeps its tokens and
leaves its neighbours' rows alone.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu.models.llama import LlamaConfig, llama_forward, llama_init
from kubetorch_tpu.models.moe import MoeConfig, moe_forward, moe_init
from kubetorch_tpu.serve import engine as E
from kubetorch_tpu.serve import spec_engine
from kubetorch_tpu.serve.kv_quant import init_quant_cache

pytestmark = pytest.mark.level("unit")

SLOTS, S_MAX, BUCKET, BLOCK = 3, 32, 8, 4
PROMPTS = {0: [5, 17, 42, 7, 99], 2: [9, 8, 200, 31, 77, 12, 3]}  # 1: idle


@pytest.fixture(scope="module")
def models():
    """(cfg, params) in bfloat16, and the same weights in float32 with the
    plain full forward: the reference."""
    tiny = dict(attn_impl="xla", dtype=jnp.bfloat16, remat=False)
    # capacity 4.0: no expert overflows, so routing a prompt in one piece, a
    # token at a time and the whole sequence at once agree
    cfgs = {"dense": (LlamaConfig.tiny(**tiny), llama_init, llama_forward),
            "moe": (MoeConfig.tiny(capacity_factor=4.0, **tiny), moe_init,
                    lambda *a: moe_forward(*a)[0])}
    out = {}
    for kind, (cfg, init, forward) in cfgs.items():
        params = init(jax.random.PRNGKey(0), cfg)
        cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
        params32 = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params)

        def reference(seq, forward=forward, params32=params32, cfg32=cfg32):
            """float32 logits (T, V) of one token sequence."""
            logits = forward(params32, jnp.asarray([seq], jnp.int32), cfg32)
            return np.asarray(logits[0], np.float32)
        out[kind] = (cfg, params, reference)
    return out


@pytest.fixture
def attention_path(request, monkeypatch):
    """Steer ``_decode_layer`` onto one attention path. The flag freezes at
    import and is no part of a jit's key, so the decode programs traced under
    the other setting are dropped."""
    monkeypatch.setattr(E, "_DECODE_KERNEL_FLAG",
                        {"einsum": "0", "kernel": "1"}[request.param])
    E._decode_block.clear_cache()
    yield request.param
    E._decode_block.clear_cache()


def _grid(cfg, quant, slots=SLOTS, s_max=S_MAX):
    return (init_quant_cache if quant else E.init_grid_cache)(cfg, slots,
                                                              s_max)


def _admit(params, cfg, cache, slot, prompt, bucket=BUCKET):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    first, k_new, v_new, lps = E._prefill(
        params, jnp.asarray(padded), jnp.int32(len(prompt)),
        jax.random.PRNGKey(1), jnp.zeros((1,), jnp.float32), cfg)
    assert k_new.shape == (cfg.n_layers, 1, bucket, cfg.n_kv_heads,
                           cfg.head_dim)           # row-major, as generate's
    cache = E._splice_slot(cache, jnp.int32(slot), k_new, v_new)
    return cache, int(first[0]), float(lps[0])


def _blocks(params, cfg, cache, pos, toks, n_blocks=2, n_steps=BLOCK):
    toks_all, lps_all = [], []
    temps = jnp.zeros((len(pos),), jnp.float32)
    pos, toks = jnp.asarray(pos, jnp.int32), jnp.asarray(toks, jnp.int32)
    for _ in range(n_blocks):
        cache, pos, toks, toks_k, lps_k, _ = E._decode_block(
            params, cache, pos, toks, jax.random.PRNGKey(2), temps, cfg,
            n_steps=n_steps)
        toks_all.append(np.asarray(toks_k))
        lps_all.append(np.asarray(lps_k))
    return cache, np.concatenate(toks_all), np.concatenate(lps_all)


def _log_softmax(x):
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


@pytest.mark.parametrize("attention_path", ["einsum", "kernel"],
                         indirect=True)
@pytest.mark.parametrize("grid", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_grid_decode_matches_float32_forward(models, kind, grid,
                                             attention_path):
    cfg, params, reference = models[kind]
    cache = _grid(cfg, grid == "int8")
    pos, toks, served = [0] * SLOTS, [0] * SLOTS, {}
    for slot, prompt in PROMPTS.items():
        cache, first, lp = _admit(params, cfg, cache, slot, prompt)
        pos[slot], toks[slot] = len(prompt), first
        served[slot] = ([first], [lp])
    assert cache[0].shape == (cfg.n_layers, SLOTS, cfg.n_kv_heads, S_MAX,
                              cfg.head_dim)
    cache, toks_k, lps_k = _blocks(params, cfg, cache, pos, toks)
    assert toks_k.shape == (2 * BLOCK, SLOTS)

    # bfloat16 weights and activations against float32 ones: what the model's
    # own rounding leaves. A wrong row, head, slot or layer read reads 1-5.
    lp_tol, gap_tol = (0.12, 0.25) if grid == "int8" else (0.08, 0.2)
    for slot, prompt in PROMPTS.items():
        new = served[slot][0] + toks_k[:, slot].tolist()
        got_lps = served[slot][1] + lps_k[:, slot].tolist()
        seq = prompt + new
        ref = _log_softmax(reference(seq[:-1]))
        for i, (tok, lp) in enumerate(zip(new, got_lps)):
            row = ref[len(prompt) - 1 + i]
            assert abs(lp - row[tok]) < lp_tol, (slot, i, lp, row[tok])
            assert row.max() - row[tok] < gap_tol, (slot, i)


@pytest.mark.parametrize("grid", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_grid_ingest_matches_float32_forward(models, kind, grid):
    """The speculative engine's window forward writes W rows a slot into the
    same grid and attends them there."""
    cfg, params, reference = models[kind]
    cache = _grid(cfg, grid == "int8")
    starts = {}
    for slot, prompt in PROMPTS.items():
        cache, _, _ = _admit(params, cfg, cache, slot, prompt[:-3])
        starts[slot] = len(prompt) - 3
    w = 4
    blocks = np.zeros((SLOTS, w), np.int32)
    true_len = np.zeros((SLOTS,), np.int32)
    for slot, prompt in PROMPTS.items():
        blocks[slot, :3] = prompt[-3:]
        true_len[slot] = 3
    start = np.asarray([starts.get(s, 0) for s in range(SLOTS)], np.int32)
    logits, cache = spec_engine._grid_ingest(
        params, cache, jnp.asarray(blocks), jnp.asarray(start),
        jnp.asarray(true_len), cfg)
    tol = 0.2 if grid == "int8" else 0.12
    for slot, prompt in PROMPTS.items():
        want = reference(prompt)[-3:]
        got = np.asarray(logits[slot, :3], np.float32)
        assert np.max(np.abs(got - want)) < tol, slot


@pytest.mark.parametrize("attention_path", ["einsum", "kernel"],
                         indirect=True)
def test_slot_reaching_the_last_row_mid_block(models, attention_path):
    """Slot 0 sits at row S_max−3 when a block of 4 starts: its third step
    writes row S_max−1, its fourth overshoots and is clamped onto that row
    again. Against the same block cut to 3 steps: the three kept tokens of
    every slot are the same, and no row but each slot's own fourth-step row
    (slot 0: its last row, rewritten) has changed."""
    cfg, params, _ = models["dense"]
    s_max = 16
    prompts = {0: list(range(3, 3 + s_max - 3)), 1: [9, 8, 200]}

    def run(n_steps):
        cache = _grid(cfg, False, slots=2, s_max=s_max)
        pos, toks = [0, 0], [0, 0]
        for slot, prompt in prompts.items():
            cache, first, _ = _admit(params, cfg, cache, slot, prompt,
                                     bucket=s_max)
            pos[slot], toks[slot] = len(prompt), first
        cache, toks_k, lps_k = _blocks(params, cfg, cache, pos, toks,
                                       n_blocks=1, n_steps=n_steps)
        return np.asarray(cache.k, np.float32), toks_k, lps_k

    k4, toks4, lps4 = run(4)
    k3, toks3, lps3 = run(3)
    np.testing.assert_array_equal(toks4[:3], toks3)
    np.testing.assert_array_equal(lps4[:3], lps3)
    assert np.isfinite(lps4).all()
    changed = np.argwhere(np.any(k4 != k3, axis=(0, 2, 4)))     # (slot, row)
    assert {tuple(c) for c in changed} <= {(0, s_max - 1), (1, 3 + 3)}, changed
    assert (1, 3 + 3) in {tuple(c) for c in changed}

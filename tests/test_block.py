"""The one decoder block (ROADMAP D1): ``models/block.py`` is the only place
where a layer is spelled out, and its four ``attend`` operations meet here.

- ``apply_rope`` over the three position shapes the callers hand it, against
  the three rotary embeddings it replaced (kept below as the reference);
- the same tokens through a training forward, a from-zero
  ``forward_with_cache`` prefill, single-token grid decode steps and one
  ``_grid_ingest`` window: one block, four cache-and-attention operations,
  the same last-position logits;
- a source check: a layer's norms are applied in ``models/block.py`` and
  nowhere else in the package.
"""

import dataclasses
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from kubetorch_tpu.models.block import apply_rope
from kubetorch_tpu.models.generate import forward_with_cache, init_cache
from kubetorch_tpu.models.llama import (LlamaConfig, llama_forward,
                                        llama_init, rope_freqs)
from kubetorch_tpu.models.moe import MoeConfig, moe_forward, moe_init
from kubetorch_tpu.serve import engine as E
from kubetorch_tpu.serve import spec_engine

pytestmark = pytest.mark.level("unit")

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "kubetorch_tpu"


# --- the three rotary embeddings that were, as the reference ----------------

def _rope_table(x, freqs):
    """``models.llama.apply_rope`` as it was: x (B, S, N, Hd), one (S, Hd/2)
    table for the batch."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    xc = lax.complex(xf[..., 0], xf[..., 1])
    rotated = xc * freqs[None, :, None, :]
    out = jnp.stack([jnp.real(rotated), jnp.imag(rotated)], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_slot(x, freqs):
    """``serve.engine._rope_slot`` as it was: x (B, N, Hd), freqs
    (B, Hd/2)."""
    b, n, hd = x.shape
    xf = x.astype(jnp.float32).reshape(b, n, hd // 2, 2)
    xc = lax.complex(xf[..., 0], xf[..., 1])
    rotated = xc * freqs[:, None, :]
    out = jnp.stack([jnp.real(rotated), jnp.imag(rotated)], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_grid(x, freqs):
    """``serve.spec_engine._rope_grid`` as it was: x (B, W, N, Hd), freqs
    (B, W, Hd/2)."""
    b, w, n, hd = x.shape
    xf = x.astype(jnp.float32).reshape(b, w, n, hd // 2, 2)
    xc = lax.complex(xf[..., 0], xf[..., 1])
    rotated = xc * freqs[:, :, None, :]
    out = jnp.stack([jnp.real(rotated), jnp.imag(rotated)], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@pytest.mark.parametrize("positions", ["(T,)", "(B, 1)", "(B, W)"])
def test_apply_rope_is_the_three_ropes_it_replaced(positions):
    cfg = LlamaConfig.tiny()
    b, w, n, hd = 3, 5, cfg.n_heads, cfg.head_dim
    table = rope_freqs(cfg, 64)
    if positions == "(T,)":
        pos = 7 + jnp.arange(w)
        reference = _rope_table
    elif positions == "(B, 1)":
        pos = jnp.asarray([[0], [41], [9]])
        w = 1

        def reference(x, freqs):
            return _rope_slot(x[:, 0], freqs[:, 0])[:, None]
    else:
        pos = jnp.asarray([0, 41, 9])[:, None] + jnp.arange(w)[None, :]
        reference = _rope_grid
    x = jax.random.normal(jax.random.PRNGKey(0), (b, w, n, hd), jnp.bfloat16)
    freqs = table[pos]
    assert freqs.shape == pos.shape + (hd // 2,)
    got = apply_rope(x, freqs)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(reference(x, freqs), np.float32))
    # and it rotates: position 0 is the identity, another position is not
    if positions != "(T,)":
        np.testing.assert_array_equal(np.asarray(got[0, 0], np.float32),
                                      np.asarray(x[0, 0], np.float32))
        assert np.any(np.asarray(got[1], np.float32)
                      != np.asarray(x[1], np.float32))


# --- four attend operations, one block --------------------------------------

SEQ = [5, 17, 42, 7, 99, 9, 8, 200]
SLOTS, S_MAX = 2, 16                                   # slot 1 stays idle


def _models(kind):
    tiny = dict(attn_impl="xla", dtype=jnp.bfloat16, remat=False)
    if kind == "dense":
        cfg, init, forward = LlamaConfig.tiny(**tiny), llama_init, llama_forward
    else:
        # capacity 4.0: no expert overflows, so routing the sequence at once,
        # as one prefill, as a window and a token at a time agree
        cfg, init = MoeConfig.tiny(capacity_factor=4.0, **tiny), moe_init

        def forward(*a):
            return moe_forward(*a)[0]
    return cfg, init(jax.random.PRNGKey(0), cfg), forward


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_four_attend_operations_agree_through_one_block(kind):
    cfg, params, forward = _models(kind)
    t = len(SEQ)
    tokens = jnp.asarray([SEQ], jnp.int32)

    # the reference tests/test_grid_parity.py holds the grid to: the same
    # weights and the plain full forward in float32
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    want = np.asarray(forward(params32, tokens, cfg32)[0, -1], np.float32)
    want_lp = want - want.max() - np.log(np.exp(want - want.max()).sum())

    logits = {}
    # 1. training: self-attention, no cache (models.llama.self_attend)
    logits["training"] = forward(params, tokens, cfg)[0, -1]
    # 2. prefill from zero into a row-major cache (generate.cache_attend)
    last, cache = forward_with_cache(params, tokens, init_cache(cfg, 1, S_MAX),
                                     0, cfg)
    assert cache.k.shape == (cfg.n_layers, 1, S_MAX, cfg.n_kv_heads,
                             cfg.head_dim)
    logits["prefill"] = last[0]
    # 3. a window a slot into the head-major grid (spec_engine.window_attend)
    blocks = np.zeros((SLOTS, t), np.int32)
    blocks[0] = SEQ
    win, grid_w = spec_engine._grid_ingest(
        params, E.init_grid_cache(cfg, SLOTS, S_MAX), jnp.asarray(blocks),
        jnp.zeros((SLOTS,), jnp.int32), jnp.asarray([t, 0], jnp.int32), cfg)
    logits["window"] = win[0, t - 1]
    for name, got in logits.items():
        got = np.asarray(got, np.float32)
        assert np.max(np.abs(got - want)) < 0.12, name

    # 4. a token a step into the same grid (engine.grid_attend): the step
    # hands back the greedy token and its log-probability, not the logits
    grid = E.init_grid_cache(cfg, SLOTS, S_MAX)
    temps = jnp.zeros((SLOTS,), jnp.float32)
    for i, tok in enumerate(SEQ):
        grid, nxt, lps = E._decode_step(
            params, grid, jnp.asarray([i, 0], jnp.int32),
            jnp.asarray([tok, 0], jnp.int32), jax.random.PRNGKey(2), temps,
            cfg)
    tok, lp = int(nxt[0]), float(lps[0])
    assert abs(lp - want_lp[tok]) < 0.08, (lp, want_lp[tok])
    assert want.max() - want[tok] < 0.2
    for name, got in logits.items():
        got = np.asarray(got, np.float32)
        assert got.max() - got[tok] < 0.2, name

    # the step-by-step grid and the window's hold the same rows for slot 0
    np.testing.assert_allclose(
        np.asarray(grid.k[:, 0, :, :t], np.float32),
        np.asarray(grid_w.k[:, 0, :, :t], np.float32), atol=0.05)
    # and they are the prefill's rows, head-major
    np.testing.assert_allclose(
        np.asarray(grid.k[:, 0, :, :t], np.float32),
        np.asarray(jnp.swapaxes(cache.k[:, 0, :t], 1, 2), np.float32),
        atol=0.05)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_mix_operations_agree_through_one_block(dtype):
    """The second mixing operation (``models.mla``), three ways through the
    one block: attention over expanded heads (the plain forward), a bucketed
    prefill that hands back latent rows, and a token a step into the latent
    grid with the up-projection absorbed."""
    from kubetorch_tpu.models.mla import (MlaMoeConfig, mla_moe_forward,
                                          mla_moe_init)
    cfg = MlaMoeConfig.tiny(dtype=jnp.dtype(dtype))
    params = mla_moe_init(jax.random.PRNGKey(0), cfg)
    t = len(SEQ)
    tokens = jnp.asarray([SEQ], jnp.int32)
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    want = np.asarray(mla_moe_forward(params32, tokens, cfg32)[0, -1])
    want_lp = want - want.max() - np.log(np.exp(want - want.max()).sum())
    tol = 1e-3 if dtype == "float32" else 0.12

    got = np.asarray(mla_moe_forward(params, tokens, cfg)[0, -1], np.float32)
    assert np.max(np.abs(got - want)) < tol
    padded = np.zeros((1, S_MAX), np.int32)
    padded[0, :t] = SEQ
    first, rows, _v, flp = E._prefill(
        params, jnp.asarray(padded), jnp.int32(t), jax.random.PRNGKey(0),
        jnp.zeros((1,), jnp.float32), cfg)
    assert abs(float(flp[0]) - want_lp[int(first[0])]) < tol
    assert want.max() - want[int(first[0])] < 2 * tol

    grid = E._cache_ops(cfg).init_grid(cfg, SLOTS, S_MAX)
    assert grid.c.shape == (cfg.n_layers, SLOTS, 1, S_MAX, cfg.latent_dim)
    temps = jnp.zeros((SLOTS,), jnp.float32)
    for i, tok in enumerate(SEQ):
        grid, nxt, lps = E._decode_step(
            params, grid, jnp.asarray([i, 0], jnp.int32),
            jnp.asarray([tok, 0], jnp.int32), jax.random.PRNGKey(2), temps,
            cfg)
    assert abs(float(lps[0]) - want_lp[int(nxt[0])]) < tol
    assert want.max() - want[int(nxt[0])] < 2 * tol
    # the step-by-step grid holds the prefill's rows, one head of them
    np.testing.assert_allclose(
        np.asarray(grid.c[:, 0, 0, :t], np.float32),
        np.asarray(rows[:, 0, :t, 0], np.float32), atol=tol)


# --- a layer is spelled out in one file --------------------------------------

def test_a_layers_norms_are_applied_in_block_py_only():
    norm_call = re.compile(
        r'rmsnorm\(\s*[^()]*?lw\["(?:attn|ffn)_norm"\]', re.DOTALL)
    sites, old_ropes = {}, []
    for path in sorted(PACKAGE.rglob("*.py")):
        text = path.read_text()
        name = path.relative_to(PACKAGE).as_posix()
        n = len(norm_call.findall(text))
        if n:
            sites[name] = n
        if re.search(r"\b_rope_(?:slot|grid)\b", text):
            old_ropes.append(name)
    assert sites == {"models/block.py": 2}, sites
    assert old_ropes == [], old_ropes

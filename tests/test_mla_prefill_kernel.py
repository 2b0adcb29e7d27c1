"""The latent-attention prefill kernel (``ops/mla_prefill_attention.py``,
``kt_mla_flash_prefill``) against the XLA form it replaces
(``models.mla.expanded_attention``, what ``expanded_mix``'s ``attend`` runs),
interpreted on the CPU; its gradient through ``mla._flash_attention``'s
``custom_vjp``; ``expanded_mix`` with the kernel against itself without; and
the choice of the kernel, from the backend and the score array's size alone.

The compile-side guard (Kimi-VL's bucket-1,024 prefill holds no call of the
kernel; the kernel at GLM-5's widths compiles for a described v5e) is in
``tests/test_decode_block_compiles.py``, the one file that describes the chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu.models import mla
from kubetorch_tpu.models.llama import rope_freqs
from kubetorch_tpu.models.mla import MlaMoeConfig, mla_moe_init
from kubetorch_tpu.ops.mla_prefill_attention import (causal_pairs,
                                                     mla_flash_prefill)

pytestmark = pytest.mark.level("unit")

# (q_nope, q_pe, v) widths: GLM-5's and Kimi-VL's
WIDTHS = {"glm5": (192, 64, 256), "kimi": (128, 64, 128)}


def _operands(b, t, n, widths, seed=0):
    dn, dr, dv = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    f = jnp.float32
    return (jax.random.normal(ks[0], (b, t, n, dn), f),
            jax.random.normal(ks[1], (b, t, n, dr), f),
            jax.random.normal(ks[2], (b, t, n, dn), f),
            jax.random.normal(ks[3], (b, t, dr), f),
            jax.random.normal(ks[4], (b, t, n, dv), f))


def _selection(b, t, topk, seed=1):
    """(B, T, T) bool: each query's ``topk`` causal keys of largest score
    (random scores), through the program's own ``select_mask``."""
    cfg = MlaMoeConfig.tiny(index_topk=topk)
    scores = jax.random.normal(jax.random.PRNGKey(seed), (b, t, t))
    causal = jnp.tril(jnp.ones((t, t), bool))[None]
    return mla.select_mask(cfg, scores, causal)


def test_the_work_list_is_the_causal_blocks():
    qb, kb = causal_pairs(512, 128, 128)
    assert list(zip(qb, kb)) == [(i, j) for i in range(4)
                                 for j in range(i + 1)]
    qb, kb = causal_pairs(512, 256, 128)             # blocks of two sizes
    assert list(zip(qb, kb)) == [(0, 0), (0, 1), (1, 0), (1, 1), (1, 2),
                                 (1, 3)]


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("select", ["causal", "topk_under_t", "topk_over_t"])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)])
def test_the_kernel_is_the_xla_form(widths, select, blocks):
    """T of four key blocks, a batch of 2, two heads; no selection, a
    selection that drops keys (``index_topk`` < T) and one that keeps every
    causal key (``index_topk`` >= T, handed over as an operand all the
    same)."""
    b, t, n = 2, 512, 2
    ops = _operands(b, t, n, WIDTHS[widths])
    scale = sum(WIDTHS[widths][:2]) ** -0.5
    if select == "causal":
        mask, operand = jnp.tril(jnp.ones((t, t), bool))[None], None
    else:
        mask = _selection(b, t, 48 if select == "topk_under_t" else t)
        operand = mask.astype(jnp.int8)
    got = mla_flash_prefill(*ops, operand, scale=scale, block_q=blocks[0],
                            block_k=blocks[1])
    want = mla.expanded_attention(*ops, mask, scale).reshape(b, t, -1)
    assert got.shape == (b, t, n * WIDTHS[widths][2])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_the_gradient_is_the_xla_forms():
    """Through the ``custom_vjp``: the kernel forward, the XLA form's
    backward a tile of queries at a time, against ``jax.grad`` of the XLA
    form over the whole prompt."""
    b, t, n = 1, 256, 2
    ops = _operands(b, t, n, WIDTHS["kimi"], seed=3)
    scale = 192 ** -0.5
    mask = _selection(b, t, 40, seed=4)

    def loss_kernel(*xs):
        return jnp.sum(mla._flash_attention(
            *xs, mask.astype(jnp.int8), scale, 64) ** 2)

    def loss_xla(*xs):
        return jnp.sum(mla.expanded_attention(*xs, mask, scale) ** 2)

    got = jax.grad(loss_kernel, argnums=range(5))(*ops)
    want = jax.grad(loss_xla, argnums=range(5))(*ops)
    for g, w, name in zip(got, want, ("q_nope", "q_pe", "k_nope", "k_pe",
                                      "v")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-4,
                                   atol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("indexed", [True, False])
def test_expanded_mix_with_the_kernel_is_the_mix_without(indexed,
                                                         monkeypatch):
    """A tiny layer whose 256 queries tile (16 a tile, in the runs of
    ``KEY_EXTENT_RUNS``): the selection made a tile at a time and handed to
    the kernel as one int8 operand gives what the XLA tiles give; without an
    indexer the kernel is causal by position."""
    kw = dict(q_lora_rank=24, index_n_heads=8, index_head_dim=16,
              index_topk=8) if indexed else {}
    cfg = MlaMoeConfig.tiny(v_head_dim=24, max_seq_len=256,
                            dtype=jnp.float32, **kw)
    params = mla_moe_init(jax.random.PRNGKey(0), cfg)
    lw = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    t = 256
    h = jax.random.normal(jax.random.PRNGKey(5), (2, t, cfg.dim))
    monkeypatch.setattr(mla, "SCORE_TILE_BYTES", 4 * cfg.n_heads * t * 16)
    assert mla._query_tile(cfg, t) == 16
    mix = mla.expanded_mix(cfg, rope_freqs(cfg, t))
    want, want_leaves = mix(h, lw, None)
    monkeypatch.setattr(mla, "flash_prefill_auto", lambda cfg, t: True)
    got, got_leaves = mix(h, lw, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _glm5(n_layers=2):
    return MlaMoeConfig(
        vocab_size=19360, dim=6144, n_layers=n_layers, n_heads=64,
        kv_lora_rank=512, q_lora_rank=2048, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, ffn_dim=12288, moe_ffn_dim=2048,
        n_experts=256, held=(0, 16), experts_per_token=8, n_shared_experts=1,
        routed_scaling_factor=2.5, max_seq_len=9728, rope_theta=1e6,
        index_n_heads=32, index_head_dim=128, index_topk=2048)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_the_kernel_is_chosen_by_the_score_arrays_size(backend, monkeypatch):
    """GLM-5's bucket of 8,192 with 64 heads tiles its queries and takes the
    kernel on the TPU backend; Kimi-VL's buckets of 256 / 512 / 1,024 with 16
    heads are one score array each, and so are the tiny configs' prompts:
    they never do, on any backend. On the CPU nothing does."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    glm, kimi = _glm5(), MlaMoeConfig(n_layers=2)
    assert mla.flash_prefill_auto(glm, 8192) is (backend == "tpu")
    assert mla.flash_prefill_supported(glm, 8192)
    for t in (256, 512, 1024):
        assert not mla.flash_prefill_auto(kimi, t)
        assert not mla.flash_prefill_supported(kimi, t)
    for tiny in (MlaMoeConfig.tiny(), MlaMoeConfig.tiny(
            q_lora_rank=24, index_n_heads=8, index_head_dim=16,
            index_topk=8)):
        assert not any(mla.flash_prefill_auto(tiny, t)
                       for t in (8, 32, 128, 256))

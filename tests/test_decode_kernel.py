"""Flash-decode kernel (ops/decode_attention.py) — interpret mode on CPU,
the same code path the TPU runs compiled (mirrors test_flash_attention.py).

Contracts: numerically equal to the masked-einsum reference for any
per-slot position vector, reading its layer IN PLACE out of a stacked
head-major grid (L, B, NKV, S, Hd) by a non-zero layer index (the other
layers hold other numbers), and the ENGINE produces identical tokens with
the kernel forced on (KT_DECODE_KERNEL=1 in a subprocess, since the flag
freezes at import)."""

import functools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu.ops import decode_attention as kernel_mod
from kubetorch_tpu.ops.decode_attention import decode_attention, decode_plan

pytestmark = pytest.mark.level("unit")

# heads per grid step (conftest's ``hold_heads``): one, the largest proper
# divisor of the KV heads there are, all of them
PER_STEP = ("one", "divisor", "all")


def _einsum_ref(q, ck, cv, pos, scale):
    b, nh, hd = q.shape
    s, nkv = ck.shape[1], ck.shape[2]
    g = nh // nkv
    qg = q.reshape(b, nkv, g, hd)
    logits = (jnp.einsum("bkgh,bskh->bkgs", qg, ck).astype(jnp.float32)
              * scale)
    mask = jnp.arange(s)[None, :] <= pos[:, None]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1).astype(cv.dtype)
    return jnp.einsum("bkgs,bskh->bkgh", probs, cv).reshape(b, nh, hd)


def _stacked(rows, layer, n_layers=3):
    """A row-major layer (B, S, NKV, Hd) as layer ``layer`` of a head-major
    grid (L, B, NKV, S, Hd) whose other layers hold other numbers: reading
    the wrong layer cannot pass."""
    head_major = rows.transpose(0, 2, 1, 3)
    return jnp.stack([head_major if l == layer else head_major[::-1] + 1 + l
                      for l in range(n_layers)])


def _decode(q, ck, cv, pos, layer, n_layers=3, **kw):
    return decode_attention(q, _stacked(ck, layer, n_layers),
                            _stacked(cv, layer, n_layers), pos, layer, **kw)


class TestKernel:
    @pytest.mark.parametrize("shape", [
        (4, 256, 8, 4, 128),     # multi-tile, GQA group 2
        (2, 512, 4, 1, 64),      # MQA, group 4
        (3, 128, 6, 2, 128),     # odd batch, group 3 (padded rows)
        (1, 64, 8, 8, 64),       # group 1 (pure MHA)
    ])
    @pytest.mark.parametrize("per_step", PER_STEP)
    def test_matches_einsum(self, shape, per_step, hold_heads):
        b, s, nh, nkv, hd = shape
        hold_heads(per_step, b, nkv, s, hd, 4, 128)
        rng = np.random.default_rng(hash(shape) % 2**31)
        q = jnp.asarray(rng.standard_normal((b, nh, hd)), jnp.float32)
        ck = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.float32)
        cv = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.float32)
        pos = jnp.asarray(rng.integers(0, s, b), jnp.int32)
        got = _decode(q, ck, cv, pos, 1 + b % 2, block_k=128)
        want = _einsum_ref(q, ck, cv, pos, hd ** -0.5)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5

    @pytest.mark.parametrize("per_step", PER_STEP)
    def test_edge_positions(self, per_step, hold_heads):
        """pos at row 0 (only the fresh token visible) and at the last row
        (whole cache visible)."""
        b, s, nh, nkv, hd = 2, 128, 8, 4, 64
        hold_heads(per_step, b, nkv, s, hd, 4, 64)
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((b, nh, hd)), jnp.float32)
        ck = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.float32)
        cv = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.float32)
        pos = jnp.asarray([0, s - 1], jnp.int32)
        got = _decode(q, ck, cv, pos, jnp.int32(2), block_k=64)
        want = _einsum_ref(q, ck, cv, pos, hd ** -0.5)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5

    @pytest.mark.parametrize("per_step", PER_STEP)
    def test_bf16_inputs(self, per_step, hold_heads):
        b, s, nh, nkv, hd = 2, 256, 8, 4, 128
        hold_heads(per_step, b, nkv, s, hd, 2, 128)
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((b, nh, hd)), jnp.bfloat16)
        ck = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.bfloat16)
        cv = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.bfloat16)
        pos = jnp.asarray([100, 255], jnp.int32)
        got = jax.jit(functools.partial(decode_attention, block_k=128))(
            q, _stacked(ck, 1), _stacked(cv, 1), pos,
            jnp.int32(1))                             # a traced layer index
        want = _einsum_ref(q.astype(jnp.float32), ck.astype(jnp.float32),
                           cv.astype(jnp.float32), pos, hd ** -0.5)
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.02

    def test_cells_shape_mixed_fill(self):
        """The benchmark cells' call: 16 slots × 2,048 rows, 32 heads over 8
        KV heads of 128, bf16, default tile; one slot at row 0, one at the
        last row, the rest between, tiles' edges among them."""
        b, s, nh, nkv, hd = 16, 2048, 32, 8, 128
        assert decode_plan(b, nkv, s, hd, 2).grid == (16, 4)
        rng = np.random.default_rng(30)
        q = jnp.asarray(rng.standard_normal((b, nh, hd)), jnp.bfloat16)
        ck = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.bfloat16)
        cv = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.bfloat16)
        pos = jnp.asarray([0, s - 1, 511, 512, 1023, 1024, 1535, 1536,
                           *rng.integers(1, s - 1, b - 8)], jnp.int32)
        got = _decode(q, ck, cv, pos, 1, n_layers=2)
        want = _einsum_ref(q.astype(jnp.float32), ck.astype(jnp.float32),
                           cv.astype(jnp.float32), pos, hd ** -0.5)
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.02


class TestPlan:
    """``decode_plan``: the grid and tile the wrapper launches, from shapes
    alone (no chip, nothing traced)."""

    def test_cells_shape(self):
        plan = decode_plan(16, 8, 2048, 128, 2)
        assert plan == (8, 512, (16, 4), 2 * 2 ** 20)
        # the int8 grid's tiles are half as large: the same grid
        assert decode_plan(16, 8, 2048, 128, 1)[:3] == (8, 512, (16, 4))

    @pytest.mark.parametrize("nkv,itemsize,heads", [
        (8, 4, 4),        # float32 tiles are twice as large: half the heads
        (32, 2, 8),       # MHA at 32 heads: a quarter of them
        (12, 2, 6),       # the largest divisor that fits, not a power of two
        (7, 4, 1),        # a prime the budget cannot hold whole
        (7, 1, 7),        # the same heads as int8 fit
    ])
    def test_falls_to_a_divisor(self, nkv, itemsize, heads):
        plan = decode_plan(16, nkv, 2048, 128, itemsize)
        assert plan.heads == heads and nkv % plan.heads == 0
        assert plan.grid == (16 * nkv // heads, 4)
        assert plan.tile_pair_bytes == 2 * heads * 512 * 128 * itemsize
        assert 2 * plan.tile_pair_bytes <= kernel_mod.KV_VMEM_BUDGET

    @pytest.mark.parametrize("nkv", [1, 2, 4, 8])
    def test_local_heads_under_a_mesh(self, nkv):
        """Under ``shard_map`` the wrapper sees the device's own heads: all
        of them go into one grid step at the cells' tile."""
        assert decode_plan(16, nkv, 2048, 128, 2).grid == (16, 4)
        assert decode_plan(16, nkv, 2048, 128, 2).heads == nkv

    def test_one_head_is_taken_whatever_it_weighs(self):
        plan = decode_plan(2, 3, 8192, 256, 4, block_k=4096)
        assert kernel_mod.KV_VMEM_BUDGET < 2 * plan.tile_pair_bytes
        assert plan.heads == 1 and plan.grid == (6, 2)

    def test_tile_divides_the_cache(self):
        assert decode_plan(1, 2, 96, 64, 4, block_k=64).block_k == 32
        assert decode_plan(1, 2, 64, 64, 4).block_k == 64


@pytest.mark.slow
def test_engine_tokens_identical_with_kernel_forced():
    """The engine with KT_DECODE_KERNEL=1 (kernel, interpret mode) emits
    exactly the tokens of the default einsum path — run in a subprocess
    because the dispatch flag freezes at import."""
    code = r"""
import numpy as np, jax, jax.numpy as jnp
from kubetorch_tpu.models.llama import LlamaConfig, llama_init
from kubetorch_tpu.serve import GenerationEngine

cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
params = llama_init(jax.random.PRNGKey(0), cfg)
eng = GenerationEngine(params, cfg, slots=2, max_len=32, prefill_buckets=(4,))
hs = [eng.submit(p, max_new_tokens=6) for p in ([5, 17, 42], [9, 8])]
while eng.step():
    pass
print([h.result(timeout=0) for h in hs])
"""
    outs = {}
    for flag in ("0", "1"):
        env = {**os.environ, "KT_DECODE_KERNEL": flag,
               "JAX_PLATFORMS": "cpu"}
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        outs[flag] = r.stdout.strip().splitlines()[-1]
    assert outs["0"] == outs["1"], outs

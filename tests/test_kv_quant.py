"""int8 KV cache (serve/kv_quant.py + the quant flash-decode kernel).

Contracts: (1) per-row absmax quantization bounds relative error by the
row peak / 127; (2) the Pallas quant kernel is BIT-compatible with the
fold-in einsum reference (same fp32 math, scales on logits columns / probs);
(3) an engine with ``quantize_kv=True`` runs the full continuous-batching
protocol with logits close to the fp engine's — and half the cache bytes.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu.models.llama import LlamaConfig, llama_init
from kubetorch_tpu.serve import GenerationEngine
from kubetorch_tpu.serve.kv_quant import (QuantKVCache, dequantize_rows,
                                          init_quant_cache, quantize_rows)

pytestmark = [pytest.mark.level("unit"), pytest.mark.slow]


@pytest.fixture(scope="module")
def dense():
    cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return params, cfg


class TestRowQuant:
    def test_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 2, 64),
                              jnp.float32) * 3.0
        q, s = quantize_rows(x)
        assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
        back = dequantize_rows(q, s)
        # |err| <= scale/2 = row_absmax / 254 per element
        bound = (jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 254.0) + 1e-7
        assert jnp.all(jnp.abs(back - x) <= bound)

    def test_zero_rows_stay_zero(self):
        q, s = quantize_rows(jnp.zeros((2, 3, 8)))
        assert jnp.all(q == 0) and jnp.all(s == 0)
        assert jnp.all(dequantize_rows(q, s) == 0)

    def test_cache_is_half_size(self, dense):
        _, cfg = dense
        from kubetorch_tpu.serve.engine import init_grid_cache
        fp = init_grid_cache(dataclasses.replace(cfg, dtype=jnp.bfloat16),
                             4, 256)
        qc = init_quant_cache(cfg, 4, 256)
        assert qc.kq.shape == fp.k.shape == (
            cfg.n_layers, 4, cfg.n_kv_heads, 256, cfg.head_dim)
        assert qc.ks.shape == fp.k.shape[:-1]
        fp_bytes = sum(a.size * a.dtype.itemsize for a in fp)
        q_bytes = sum(a.size * a.dtype.itemsize for a in qc)
        # per bf16 row of Hd values (2·Hd bytes): Hd int8 + 4 scale bytes
        hd = cfg.head_dim
        assert q_bytes == pytest.approx(fp_bytes * (hd + 4) / (2 * hd))
        # at serving head dims the stream halves outright
        assert (128 + 4) / (2 * 128) < 0.52


def _quant_einsum_reference(q, kq, ks, vq, vs, pos, scale):
    """The fold-in math of serve.engine._einsum_attention, standalone and
    over row-major (B, S, NKV, ...) rows."""
    b, nh, hd = q.shape
    s, nkv = kq.shape[1], kq.shape[2]
    group = nh // nkv
    qg = q.reshape(b, nkv, group, hd).astype(jnp.float32)
    logits = jnp.einsum("bkgh,bskh->bkgs", qg,
                        kq.astype(jnp.float32)) * scale
    logits = logits * ks.transpose(0, 2, 1)[:, :, None, :]
    mask = jnp.arange(s)[None, :] <= pos[:, None]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = probs * vs.transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum("bkgs,bskh->bkgh", probs,
                      vq.astype(jnp.float32)).reshape(b, nh, hd)


def _grid(rows, layer=1):
    """Row-major rows (B, S, NKV, ...) as layer ``layer`` of a two-layer
    head-major grid (L, B, NKV, S, ...), the engine's layout."""
    return jnp.stack([rows if l == layer else jnp.zeros_like(rows)
                      for l in range(2)]).swapaxes(2, 3)


class TestQuantKernel:
    @pytest.mark.parametrize("per_step", ["one", "divisor", "all"])
    @pytest.mark.parametrize("shape", [
        (2, 8, 2, 64, 256, 512),   # b, nh, nkv, hd, s, block_k
        (3, 4, 4, 32, 1024, 256),
        (16, 32, 8, 128, 2048, 512),   # the benchmark cells' call
    ])
    def test_kernel_matches_einsum_reference(self, shape, per_step,
                                             hold_heads):
        from kubetorch_tpu.ops.decode_attention import decode_attention_quant
        b, nh, nkv, hd, s, bk = shape
        hold_heads(per_step, b, nkv, s, hd, 1, bk)
        rng = jax.random.PRNGKey(1)
        kf = jax.random.normal(rng, (b, s, nkv, hd), jnp.float32)
        vf = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd),
                               jnp.float32)
        q = jax.random.normal(jax.random.PRNGKey(3), (b, nh, hd),
                              jnp.float32)
        kq, ks = quantize_rows(kf)
        vq, vs = quantize_rows(vf)
        # a slot at the last row, one near row 0, the rest between
        pos = jnp.array(([s - 1, 5, s // 2] + [0] + [
            (37 * i * i) % s for i in range(4, b)])[:b], jnp.int32)
        got = decode_attention_quant(q, _grid(kq), _grid(ks), _grid(vq),
                                     _grid(vs), pos, 1, scale=hd ** -0.5,
                                     block_k=bk, interpret=True)
        want = _quant_einsum_reference(q, kq, ks, vq, vs, pos, hd ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_quant_attention_close_to_fp(self):
        """Quantization error itself is small: the int8 path tracks fp
        attention within the absmax-int8 budget."""
        from kubetorch_tpu.ops.decode_attention import decode_attention
        b, nh, nkv, hd, s = 2, 4, 2, 64, 256
        kf = jax.random.normal(jax.random.PRNGKey(1), (b, s, nkv, hd),
                               jnp.float32)
        vf = jax.random.normal(jax.random.PRNGKey(2), (b, s, nkv, hd),
                               jnp.float32)
        q = jax.random.normal(jax.random.PRNGKey(3), (b, nh, hd),
                              jnp.float32)
        pos = jnp.array([s - 1, 100], jnp.int32)
        fp = decode_attention(q, _grid(kf), _grid(vf), pos, 1,
                              interpret=True)
        kq, ks = quantize_rows(kf)
        vq, vs = quantize_rows(vf)
        want = _quant_einsum_reference(q, kq, ks, vq, vs, pos, hd ** -0.5)
        np.testing.assert_allclose(np.asarray(want), np.asarray(fp),
                                   rtol=0.05, atol=0.05)


class TestQuantEngine:
    def test_quantized_engine_full_protocol(self, dense):
        """Admission, interleaved decode, retirement, slot reuse — the whole
        continuous-batching protocol on the int8 grid; tokens match the fp
        engine greedy-for-greedy on a well-separated tiny model."""
        params, cfg = dense
        prompts = [[5, 17, 42], [9, 9, 2, 30], [1, 2]]
        ns = [6, 8, 4]
        fp = GenerationEngine(params, cfg, slots=4, max_len=64,
                              prefill_buckets=(8,))
        want = []
        for p, n in zip(prompts, ns):
            h = fp.submit(p, max_new_tokens=n)
            while fp.step():
                pass
            want.append(h.result(timeout=0))
        eng = GenerationEngine(params, cfg, slots=4, max_len=64,
                               prefill_buckets=(8,), quantize_kv=True)
        assert isinstance(eng._cache, QuantKVCache)
        handles = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, ns)]
        while eng.step():
            pass
        got = [h.result(timeout=0) for h in handles]
        assert got == want

    def test_quantized_with_prefix_and_lora(self, dense):
        """int8 cache composes with the other serving switches: cached
        prefixes (fp rows quantize at the splice) and multi-LoRA."""
        from kubetorch_tpu.models.lora import LoraConfig, lora_init
        params, cfg = dense
        lcfg = LoraConfig(rank=4)
        adap = lora_init(jax.random.PRNGKey(5), params, lcfg)
        keys = jax.random.split(jax.random.PRNGKey(6), len(adap["layers"]))
        adap["layers"] = {
            k: (v if k.endswith("__a")
                else jax.random.normal(kk, v.shape, v.dtype) * 0.05)
            for kk, (k, v) in zip(keys, sorted(adap["layers"].items()))}
        eng = GenerationEngine(params, cfg, slots=2, max_len=64,
                               prefill_buckets=(8,), quantize_kv=True)
        aid = eng.register_adapter(adap, lcfg)
        pid = eng.register_prefix([11, 12, 13])
        h1 = eng.submit([60, 61], max_new_tokens=4, prefix_id=pid)
        h2 = eng.submit([4, 4], max_new_tokens=5, adapter_id=aid)
        while eng.step():
            pass
        assert len(h1.result(timeout=0)) == 4
        assert len(h2.result(timeout=0)) == 5


def test_quant_engine_tokens_identical_with_kernel_forced():
    """The int8 engine with KT_DECODE_KERNEL=1 (quant kernel, interpret
    mode) emits exactly the einsum fold-in path's tokens — subprocess per
    flag because dispatch freezes at import."""
    import os
    import subprocess
    import sys

    code = r"""
import numpy as np, jax, jax.numpy as jnp
from kubetorch_tpu.models.llama import LlamaConfig, llama_init
from kubetorch_tpu.serve import GenerationEngine

cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False)
params = llama_init(jax.random.PRNGKey(0), cfg)
eng = GenerationEngine(params, cfg, slots=2, max_len=32,
                       prefill_buckets=(4,), quantize_kv=True)
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


class TestQuantSharded:
    def test_quantized_engine_under_tensor_sharded_mesh(self,
                                                        cpu_mesh_devices):
        """The int8 grid shards like the fp one: NKV over ``tensor``
        (values AND their per-row scales share the head axis), slots over
        data — multi-chip quantized serving matches the single-device
        quantized run token-for-token."""
        from kubetorch_tpu.parallel.mesh import build_mesh
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree

        cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               remat=False)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        prompts = [[5, 17, 42], [9, 9, 9, 9]]

        solo = GenerationEngine(params, cfg, slots=4, max_len=32,
                                prefill_buckets=(4,), quantize_kv=True)
        want = []
        for p in prompts:
            h = solo.submit(p, max_new_tokens=6)
            while solo.step():
                pass
            want.append(h.result(timeout=0))

        mesh = build_mesh({"data": 2, "tensor": 2},
                          devices=cpu_mesh_devices[:4])
        sharded = shard_pytree(params, LLAMA_RULES, mesh)
        with use_mesh(mesh):
            eng = GenerationEngine(sharded, cfg, slots=4, max_len=32,
                                   prefill_buckets=(4,), quantize_kv=True)
            handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
            while eng.step():
                pass
        got = [h.result(timeout=0) for h in handles]
        assert got == want

"""int8 KV cache for the serving engine.

Decode reads the ENTIRE cache every step — at serving lengths the K/V
stream is the HBM bill of the latency-critical op, twice the size of the
weights stream once contexts are long. Quantizing cache rows to int8 with
one fp32 scale per written row halves that stream (and the grid's HBM
footprint): Hd=128 bf16 rows go 256B → 132B per head.

Scheme — symmetric per-row-per-head absmax: a row ``x`` (one token's
(NKV, Hd) K or V values) stores ``round(x / s)`` int8 with
``s = max|x| / 127`` kept per (slot, pos, head). Dequantization folds into
the attention math WITHOUT materializing fp rows or transposing scales:

    logits_j = (q · k_j) * scale * ks_j        # ks scales logits COLUMNS
    out      = Σ_j (p_j * vs_j) · v_j          # vs folds into the probs

so the Pallas kernel streams int8 tiles plus one (1, block_k) scale row
per tile, and the einsum fallback is the same math in fp32 — the two are
asserted bit-compatible (tests/test_kv_quant.py).

Accuracy: absmax-int8 keeps per-row relative error ≤ 1/254 of the row's
peak; serving quality loss is negligible next to bf16 attention itself.
Opt in per engine: ``GenerationEngine(params, cfg, quantize_kv=True)``.

Reference analog: none (the reference has no serving engine) — part of
the beyond-parity serving stack, like int8 WEIGHT quantization
(``models.quant``), which composes with this (quantized weights +
quantized cache are independent switches).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class QuantKVCache(NamedTuple):
    """Slot-grid cache in int8, head-major like the engine's fp grid (the
    decode kernel's layout, read in place): values (L, B, NKV, S, Hd) int8,
    scales (L, B, NKV, S) fp32 — one scale per written row per head."""
    kq: jax.Array
    ks: jax.Array
    vq: jax.Array
    vs: jax.Array


def init_quant_cache(cfg, batch: int, max_len: int) -> QuantKVCache:
    """Zeroed int8 slot grid: values (L, B, NKV, S_max, Hd), scales
    (L, B, NKV, S_max)."""
    vshape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    sshape = vshape[:-1]
    return QuantKVCache(kq=jnp.zeros(vshape, jnp.int8),
                        ks=jnp.zeros(sshape, jnp.float32),
                        vq=jnp.zeros(vshape, jnp.int8),
                        vs=jnp.zeros(sshape, jnp.float32))


def quantize_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(..., Hd) → (int8 (..., Hd), fp32 scale (...,)). All-zero rows
    (unwritten cache, padding) keep scale 0 → dequantize back to exact
    zeros."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = absmax / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def grid_rows(grid, k: jax.Array, v: jax.Array) -> tuple:
    """New rows as ``grid``'s leaves take them: (k, v) for a
    full-precision K/V grid, (kq, ks, vq, vs) for an int8 one (four leaves),
    (k,) for a latent grid (``latent_cache``: one leaf, and its prefill
    hands back no ``v``)."""
    if len(grid) == 4:
        return (*quantize_rows(k), *quantize_rows(v))
    return (k,) if v is None else (k, v)


def dequantize_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """fp32 rows back; exact inverse of the fold-into-attention math for
    callers that need plain rows (tests, debugging)."""
    return q.astype(jnp.float32) * scale[..., None]

"""The decoder block: norm → q/k/v → rope → attention → wo → norm → ffn.

Written once. Every stack in the package (training, pipelined, scanned
``generate``, the engine's bucketed prefill, its slot-grid decode, the
speculative window) calls :func:`decoder_block` and hands it the two things
that differ between them, as operations:

- ``attend(q, k, v) -> (attn, cache)`` is the ONLY code that knows a cache
  layout or picks an attention kernel, and lives in the module that owns
  that cache: ``models.llama.self_attend`` (no cache), ``models.generate.
  cache_attend`` (row-major rows), ``serve.engine.grid_attend`` (one row a
  slot into the stacked head-major grid), ``serve.spec_engine.
  window_attend`` (a window a slot into one layer of it).
- ``ffn(h, lw) -> (out, aux)``: ``dense_ffn`` here, ``models.moe.moe_ffn``
  with its mesh axes bound, or ``models.generate.ffn_block`` with the
  caller's routing masks bound. A tensor-parallel FFN reduces its own output.

The block never asks which caller it serves; a new layer kind is a new
``attend`` or ``ffn`` beside the cache it needs, not a sixth copy of this
file.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .lora import lora_proj
from .quant import dequant_layer, wdot


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * weight).astype(x.dtype)


def apply_rope(x: jax.Array, freqs: jax.Array) -> jax.Array:
    """x: (B, T, N, Hd); ``freqs``: complex rotations that broadcast against
    (B, T, Hd/2) — (T, Hd/2) one table for the batch (training, prefill),
    (B, 1, Hd/2) a position a slot (grid decode), (B, W, Hd/2) a window a
    slot. Rotate pairs in fp32, return in x.dtype.

    A degenerate T stays outside the rotation, and comes back after the
    cast: XLA:TPU folds the pair split of a (B, 1, N, Hd/2, 2) array into the
    projection before it and pays with a relayout of ``wq`` and ``wk`` in
    every layer of a decode step (the compile census in CHANGES.md, PR 31)."""
    shape = x.shape
    if shape[1] == 1:
        x, freqs = x.reshape(shape[0], *shape[2:]), freqs.reshape(-1, shape[-1] // 2)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    xc = lax.complex(xf[..., 0], xf[..., 1])
    rotated = xc * freqs[..., None, :]
    out = jnp.stack([jnp.real(rotated), jnp.imag(rotated)], axis=-1)
    return out.reshape(x.shape).astype(x.dtype).reshape(shape)


def dense_ffn(h: jax.Array, lw: Dict[str, Any],
              reduce: Optional[Callable] = None) -> Tuple[jax.Array, None]:
    """SwiGLU. ``reduce``: the tensor-parallel sum of the row-sharded
    ``w_down`` product, inside ``shard_map``."""
    out = wdot(jax.nn.silu(wdot(h, lw["w_gate"])) * wdot(h, lw["w_up"]),
               lw["w_down"])
    return (reduce(out) if reduce else out), None


def decoder_block(cfg, x: jax.Array, lw: Dict[str, Any], freqs: jax.Array,
                  attend: Callable, ffn: Callable, *, lora=None,
                  reduce: Optional[Callable] = None):
    """One decoder layer over x (B, T, D) → (x', cache, aux), ``cache`` and
    ``aux`` being whatever ``attend`` and ``ffn`` return beside their output.

    ``lw`` is this layer's weights; int8 leaves (``models.quant``) are
    dequantized here, inside the caller's scan body, so only the current
    layer materializes in the compute dtype, and packed-int4 leaves go
    through ``wdot``. Head counts come from the projections' own widths:
    inside ``shard_map`` the leaves are the LOCAL column shards (this
    device's ``n_heads/tp`` query and ``n_kv_heads/tp`` kv heads; GQA
    grouping survives as long as tp | n_kv_heads), and ``reduce`` is the sum
    over the tensor axis of the row-sharded ``wo`` product. ``lora``: None,
    or (adapters_by_target, scale) with this layer's factors
    (``models.lora.lora_proj``), applied to wq/wk/wv/wo.

    The named scopes are metadata on the ops, for a device trace to group
    time by; ``kt.cache_update`` and ``kt.attention`` sit inside ``attend``.
    """
    lw = dequant_layer(lw, cfg.dtype)
    b, t, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("kt.qkv_rope"):
        h = rmsnorm(x, lw["attn_norm"], cfg.norm_eps)
        q = lora_proj(h, lw["wq"], lora, "wq").reshape(b, t, -1, hd)
        k = lora_proj(h, lw["wk"], lora, "wk").reshape(b, t, -1, hd)
        v = lora_proj(h, lw["wv"], lora, "wv").reshape(b, t, -1, hd)
        q, k = apply_rope(q, freqs), apply_rope(k, freqs)
    attn, cache = attend(q, k, v)
    with jax.named_scope("kt.out_proj"):
        out = lora_proj(attn.reshape(b, t, -1), lw["wo"], lora, "wo")
        x = x + (reduce(out) if reduce else out)
    with jax.named_scope("kt.ffn"):
        out, aux = ffn(rmsnorm(x, lw["ffn_norm"], cfg.norm_eps), lw)
        return x + out, cache, aux

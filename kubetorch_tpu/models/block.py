"""The decoder block: norm → token mixing → wo → norm → ffn.

Written once. Every stack in the package (training, pipelined, scanned
``generate``, the engine's bucketed prefill, its slot-grid decode, the
speculative window) calls :func:`decoder_block` and hands it the two things
that differ between them, as operations:

- ``mix(h, lw, lora) -> (attn, cache)``: from the normed input to the
  per-head attention output, (B, T, N·Hv). Two exist. :func:`qkv_attend`
  (here) is q/k/v through ``lora_proj``, one rope over whole heads and the
  caller's ``attend(q, k, v) -> (attn, cache)``, which is the ONLY code that
  knows a per-head K/V cache layout or picks an attention kernel, and lives
  in the module that owns that cache: ``models.llama.self_attend`` (no
  cache), ``models.generate.cache_attend`` (row-major rows), ``serve.engine.
  grid_attend`` (one row a slot into the stacked head-major grid),
  ``serve.spec_engine.window_attend`` (a window a slot into one layer of
  it). ``models.mla`` has the other: latent attention, whose query has a
  rotated and an unrotated part and whose cache row is one compressed
  vector and one shared rope key a token.
- ``ffn(h, lw) -> (out, aux)``: ``dense_ffn`` here, ``models.moe.moe_ffn``
  with its mesh axes bound, or ``models.generate.ffn_block`` with the
  caller's routing masks bound. A tensor-parallel FFN reduces its own output.

The block never asks which caller it serves; a new layer kind is a new
``mix``, ``attend`` or ``ffn`` beside the cache it needs, not a sixth copy of
this file.
"""

from __future__ import annotations

from functools import partial
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


def layer_stacks(cfg, params: Dict[str, Any]):
    """[(scanned leaves, banks, index of the run's first layer, its length)]
    for each homogeneous run of layers, in order. A stack is one ``lax.scan``
    over each run: one compiled body a layer kind. One run,
    ``params["layers"]`` with no banks, unless the config's family lays its
    layers out otherwise and says how (``cfg.layer_stacks(params)``, e.g.
    ``models.mla.MlaMoeConfig``: leading dense layers, and expert ``banks``
    that are NOT scanned but handed on whole: :func:`with_banks`)."""
    own = getattr(cfg, "layer_stacks", None)
    if own is not None:
        return own(params)
    stack = params["layers"]
    return [(stack, None, 0, jax.tree_util.tree_leaves(stack)[0].shape[0])]


def with_banks(ffn: Callable, banks, index) -> Callable:
    """``ffn`` with a run's whole ``banks`` and the layer's index in the run
    bound to it as ``banks=(banks, index)``; ``ffn`` itself where the run
    has none."""
    return ffn if banks is None else partial(ffn, banks=(banks, index))


def qkv_attend(cfg, freqs: jax.Array, attend: Callable) -> Callable:
    """The block's mixing operation for per-head q, k, v: the three
    projections (through ``lora_proj``), rope over whole heads, then the
    caller's ``attend(q, k, v) -> (attn, cache)``. Head counts come from the
    projections' own widths: inside ``shard_map`` the leaves are the LOCAL
    column shards (this device's ``n_heads/tp`` query and ``n_kv_heads/tp``
    kv heads; GQA grouping survives as long as tp | n_kv_heads)."""
    hd = cfg.head_dim

    def mix(h, lw, lora):
        b, t, _ = h.shape
        with jax.named_scope("kt.qkv_rope"):
            q = lora_proj(h, lw["wq"], lora, "wq").reshape(b, t, -1, hd)
            k = lora_proj(h, lw["wk"], lora, "wk").reshape(b, t, -1, hd)
            v = lora_proj(h, lw["wv"], lora, "wv").reshape(b, t, -1, hd)
            q, k = apply_rope(q, freqs), apply_rope(k, freqs)
        attn, cache = attend(q, k, v)
        return attn.reshape(b, t, -1), cache

    return mix


def decoder_block(cfg, x: jax.Array, lw: Dict[str, Any], mix: Callable,
                  ffn: Callable, *, lora=None,
                  reduce: Optional[Callable] = None):
    """One decoder layer over x (B, T, D) → (x', cache, aux), ``cache`` and
    ``aux`` being whatever ``mix`` and ``ffn`` return beside their output.

    ``lw`` is this layer's weights; int8 leaves (``models.quant``) are
    dequantized here, inside the caller's scan body, so only the current
    layer materializes in the compute dtype, and packed-int4 leaves go
    through ``wdot``. ``reduce`` is the sum over the tensor axis of the
    row-sharded ``wo`` product, inside ``shard_map``. ``lora``: None, or
    (adapters_by_target, scale) with this layer's factors
    (``models.lora.lora_proj``), applied to wq/wk/wv/wo.

    The named scopes are metadata on the ops, for a device trace to group
    time by; ``kt.cache_update`` and ``kt.attention`` sit inside ``attend``.
    """
    lw = dequant_layer(lw, cfg.dtype)
    with jax.named_scope("kt.qkv_rope"):
        h = rmsnorm(x, lw["attn_norm"], cfg.norm_eps)
    attn, cache = mix(h, lw, lora)
    with jax.named_scope("kt.out_proj"):
        out = lora_proj(attn, lw["wo"], lora, "wo")
        x = x + (reduce(out) if reduce else out)
    with jax.named_scope("kt.ffn"):
        out, aux = ffn(rmsnorm(x, lw["ffn_norm"], cfg.norm_eps), lw)
        return x + out, cache, aux

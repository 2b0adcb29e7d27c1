"""The engine's second cache kind: latent (MLA) rows.

A token's cache entry in a latent-attention layer (``models.mla``) is one
row ``[c ; k_pe]`` of ``kv_lora_rank + qk_rope_head_dim`` values with no head
axis (576 for Kimi-VL-A3B, where per-head K and V would be 16 × 320). The
grid keeps the engine's head-major axes with ONE head,
``(L, SLOTS, 1, S_max, C)``: rows of all layers in one stacked array, carried
and donated through ``_decode_block``'s scans, written in place a row a slot
(``engine._write_rows``), spliced from a prefill's rows
(``engine._splice_slot``) and constrained (``engine._constrain_cache``) by
the code that serves the K/V grid. A layer's values are the first
``kv_lora_rank`` columns of the same rows, so there is no second leaf.

Decode attends in the absorbed form (``models.mla.absorbed_attention``) over
the rows up to each slot's frontier; a prompt's prefill attends over the
expanded heads (``models.mla.expanded_mix``) and hands back its rows.

Imported only by an engine whose config says ``cache_kind == "latent"``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models.mla import absorbed_attention, expanded_mix, mla_project

# part of ``aot_cache.AOTKey``, as ``engine.GRID_LAYOUT`` is for K/V grids
GRID_LAYOUT = "latent:layer,slot,one,row,latent_dim"


class LatentCache(NamedTuple):
    """Latent rows of every layer. The slot grid (L, SLOTS, 1, S_max, C), or
    a prompt's rows row-major (L, B, T, 1, C) as a prefill returns them
    (``engine._splice_slot`` is the one crossing, as for K/V)."""
    c: jax.Array


def init_grid(cfg, slots: int, max_len: int) -> LatentCache:
    """Zeroed slot grid (L, SLOTS, 1, S_max, C)."""
    return LatentCache(c=jnp.zeros(
        (cfg.n_layers, slots, 1, max_len, cfg.latent_dim), cfg.dtype))


def init_rows(cfg, batch: int, t: int) -> LatentCache:
    """A prompt's rows before the prefill has made them, row-major
    (L, B, T, 1, C): what the layer scan takes and gives back a layer at a
    time."""
    return LatentCache(c=jnp.zeros(
        (cfg.n_layers, batch, t, 1, cfg.latent_dim), cfg.dtype))


def rows_mix(cfg, layer_rows, q_pos, freqs_full, **_):
    """The block's mixing operation for a from-zero prefill: attention over
    the prompt's own tokens, the layer's rows handed back in place of the
    zeros that came in. ``_``: what only a K/V prefill chooses (the flash
    kernel)."""
    del layer_rows
    mix = expanded_mix(cfg, freqs_full[q_pos])

    def rows_of(h, lw, lora):
        attn, rows = mix(h, lw, lora)
        return attn, (rows,)

    return rows_of


def grid_mix(cfg, cache: LatentCache, layer, pos, freqs):
    """The block's mixing operation over the slot grid: one new token a slot
    against layer ``layer`` of the stacked rows, written and read where they
    lie. pos (B,): each slot's position (also its row); freqs (B, 1, Hr/2)."""
    from .engine import _write_rows

    def mix(h, lw, lora):
        q_nope, q_pe, row = mla_project(cfg, h, lw, freqs)
        with jax.named_scope("kt.cache_update"):
            grid = _write_rows(cache.c, layer, pos, row)     # row (B, 1, C)
        rows = lax.dynamic_index_in_dim(grid, layer, 0, keepdims=False)[:, 0]
        attn = absorbed_attention(cfg, q_nope[:, 0], q_pe[:, 0],
                                  lw["wkv_b"], rows, pos)
        return attn[:, None].astype(h.dtype), LatentCache(grid)

    return mix

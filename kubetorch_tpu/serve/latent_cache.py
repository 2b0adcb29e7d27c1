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
``kv_lora_rank`` columns of the same rows, so values need no leaf of
their own. A config with a sparse-attention indexer (``cfg.indexed``, GLM-5)
does get a second leaf, of another width: the indexer's key of every token
(``IndexedLatentCache``), which goes wherever the rows go.

Decode attends in the absorbed form (``models.mla.absorbed_attention``) over
the rows up to each slot's frontier, or with an indexer over the rows it
selects among them, gathered; a prompt's prefill attends over the expanded
heads (``models.mla.expanded_mix``) and hands back its leaves.

Imported only by an engine whose config says ``cache_kind == "latent"``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models.mla import (absorbed_attention, expanded_mix, index_project,
                          index_scores, mla_project, select_rows)

# part of ``aot_cache.AOTKey``, as ``engine.GRID_LAYOUT`` is for K/V grids
GRID_LAYOUT = "latent:layer,slot,one,row,latent_dim"


class LatentCache(NamedTuple):
    """Latent rows of every layer. The slot grid (L, SLOTS, 1, S_max, C), or
    a prompt's rows row-major (L, B, T, 1, C) as a prefill returns them
    (``engine._splice_slot`` is the one crossing, as for K/V)."""
    c: jax.Array


class IndexedLatentCache(NamedTuple):
    """The cache of a config with a sparse-attention indexer
    (``cfg.indexed``): the latent rows and, in a leaf of its own with the
    same axes, the indexer's key of every token, (L, SLOTS, 1, S_max, Di).
    A decode step's scoring pass reads every key up to the frontier and none
    of the rows' 576 columns, which only the selected rows' gather reads."""
    c: jax.Array
    ki: jax.Array


def grid_layout(cfg) -> str:
    return GRID_LAYOUT + (";index:layer,slot,one,row,index_head_dim"
                          if cfg.indexed else "")


def _leaves(cfg, lead: tuple, tail: tuple):
    """Zeroed leaves (*lead, *tail, width) of the config's cache kind."""
    c = jnp.zeros((*lead, *tail, cfg.latent_dim), cfg.dtype)
    if not cfg.indexed:
        return LatentCache(c)
    return IndexedLatentCache(c, jnp.zeros(
        (*lead, *tail, cfg.index_head_dim), cfg.dtype))


def init_grid(cfg, slots: int, max_len: int):
    """Zeroed slot grid (L, SLOTS, 1, S_max, C) (and the keys')."""
    return _leaves(cfg, (cfg.n_layers, slots), (1, max_len))


def init_rows(cfg, batch: int, t: int):
    """A prompt's rows before the prefill has made them, row-major
    (L, B, T, 1, C): what the layer scan takes and gives back a layer at a
    time."""
    return _leaves(cfg, (cfg.n_layers, batch), (t, 1))


def rows_mix(cfg, layer_rows, q_pos, freqs_full, **_):
    """The block's mixing operation for a from-zero prefill: attention over
    the prompt's own tokens, the layer's leaves handed back in place of the
    zeros that came in. ``_``: what only a K/V prefill chooses (the flash
    kernel)."""
    del layer_rows
    return expanded_mix(cfg, freqs_full[q_pos])


def grid_mix(cfg, cache, layer, pos, freqs, live=None):
    """The block's mixing operation over the slot grid: one new token a slot
    against layer ``layer`` of the stacked rows, written and read where they
    lie. pos (B,): each slot's position (also its row); freqs (B, 1, Hr/2).

    With an indexer the new token's key is written beside its row, every
    reserved key of the slot is scored (those past ``pos`` masked: the read
    does not stop at the frontier), the ``index_topk`` best rows are
    gathered straight from the stacked grid and attention runs over the
    gathered rows alone. The mix then returns ``(cache, counts)``, counts
    (2,) int32: the rows scored (up to the frontier) and selected, summed
    over the slots that ``live`` (B,) marks (all, without it)."""
    from .engine import _write_rows

    def mix(h, lw, lora):
        q_nope, q_pe, row, cq = mla_project(cfg, h, lw, freqs)
        if cfg.indexed:
            q_idx, k_idx, w_idx = index_project(cfg, h, cq, lw, freqs)
        with jax.named_scope("kt.cache_update"):
            grid = _write_rows(cache.c, layer, pos, row)     # row (B, 1, C)
            if cfg.indexed:
                keys = _write_rows(cache.ki, layer, pos, k_idx)
        if not cfg.indexed:
            rows = lax.dynamic_index_in_dim(grid, layer, 0,
                                            keepdims=False)[:, 0]
            attn = absorbed_attention(cfg, q_nope[:, 0], q_pe[:, 0],
                                      lw["wkv_b"], rows, pos)
            return attn[:, None].astype(h.dtype), LatentCache(grid)
        scores = index_scores(q_idx, w_idx, lax.dynamic_index_in_dim(
            keys, layer, 0, keepdims=False)[:, 0])[:, 0]       # (B, S_max)
        chosen, ok = select_rows(cfg, scores, pos)
        with jax.named_scope("kt.dsa.gather"):
            rows = grid[layer, jnp.arange(pos.shape[0])[:, None], 0, chosen]
        attn = absorbed_attention(cfg, q_nope[:, 0], q_pe[:, 0],
                                  lw["wkv_b"], rows, pos, mask=ok)
        counted = 1 if live is None else (live != 0).astype(jnp.int32)
        counts = jnp.stack([jnp.sum((pos + 1) * counted),
                            jnp.sum(jnp.sum(ok, axis=1, dtype=jnp.int32)
                                    * counted)])
        return attn[:, None].astype(h.dtype), (
            IndexedLatentCache(grid, keys), counts)

    return mix

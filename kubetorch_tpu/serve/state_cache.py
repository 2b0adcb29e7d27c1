"""The engine's cache kind for hybrid decoders: recurrent state beside a K/V
grid (``models.hybrid``).

A hybrid model's attention layers keep rows, a token at a time, as every
other family does; its Mamba-2 layers keep what has no rows at all: a slot's
convolution window (its last ``mamba_d_conv - 1`` inputs) and its state
(``mamba_n_heads`` × ``mamba_d_head`` × ``mamba_d_state``, float32), the
same size however long the conversation. One manager holds both kinds
(:class:`HybridCache`): four leaves, carried and donated through
``_decode_block``'s scans as the K/V grid is, the attention layers' rows
written in place a row a slot (``engine._write_rows``) and a Mamba-2 layer's
state updated in place at its index, a prompt's rows and state spliced into
a slot in one call (``engine._splice_slot``), which replaces the slot's state
WHOLE: nothing of its previous occupant is left in it.

A layer's index in a leaf is counted among its own kind's layers
(``HybridConfig.layer_stacks``); which mix a layer takes is read from its
weights (``models.hybrid.is_mamba``).

Imported only by an engine whose config says ``cache_kind == "hybrid"``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models.block import qkv_attend
from ..models.generate import KVCache
from ..models.hybrid import (SSM_DTYPE, attention_rows_mix, is_mamba,
                             mamba_rows_mix, mamba_step, ssm_update)
from ..ops.ssm_state import ssm_state_step, ssm_state_step_auto

# part of ``aot_cache.AOTKey``, as ``engine.GRID_LAYOUT`` is for K/V grids
GRID_LAYOUT = ("hybrid:kv:attention_layer,slot,kv_head,row,head_dim;"
               "conv:mamba_layer,slot,tap,channel;"
               "ssm:mamba_layer,slot,head,head_dim,state")


class HybridCache(NamedTuple):
    """K and V of the attention layers and the Mamba-2 layers' state. The
    slot grid: k, v (L_attn, SLOTS, NKV, S_max, Hd), conv (L_mamba, SLOTS,
    d_conv - 1, conv_dim), ssm (L_mamba, SLOTS, H, P, N). A prompt's as a
    prefill returns it: k, v row-major (L_attn, B, T, NKV, Hd), conv and ssm
    with B for SLOTS."""
    k: jax.Array
    v: jax.Array
    conv: jax.Array
    ssm: jax.Array
    # the leaves a slot holds whole, with no rows: a prompt's replace the
    # slot's, where a prompt's rows are written over the slot's first rows
    state_leaves = ("conv", "ssm")


def _leaves(cfg, lead: int, rows: tuple):
    ssm = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state)
    return HybridCache(
        k=jnp.zeros((cfg.n_attention, lead, *rows), cfg.dtype),
        v=jnp.zeros((cfg.n_attention, lead, *rows), cfg.dtype),
        conv=jnp.zeros((cfg.n_mamba, lead, cfg.mamba_d_conv - 1,
                        cfg.conv_dim), cfg.dtype),
        ssm=jnp.zeros((cfg.n_mamba, lead, *ssm), SSM_DTYPE))


def init_grid(cfg, slots: int, max_len: int) -> HybridCache:
    """The zeroed slot grid, K/V head-major as the decode kernel reads it."""
    return _leaves(cfg, slots, (cfg.n_kv_heads, max_len, cfg.head_dim))


def init_rows(cfg, batch: int, t: int) -> HybridCache:
    """A prompt's rows and state before the prefill has made them."""
    return _leaves(cfg, batch, (t, cfg.n_kv_heads, cfg.head_dim))


def run_rows(rows: HybridCache, stack, start: int, n: int) -> tuple:
    """What one run of like layers scans over: its kind's two leaves, its
    ``n`` layers from ``start`` (counted among that kind's layers)."""
    pair = (rows.conv, rows.ssm) if is_mamba(stack) else (rows.k, rows.v)
    return tuple(r[start:start + n] for r in pair)


def join_rows(cfg, done: list):
    """The runs' outputs, in order, as one prompt's ``HybridCache``: what
    ``engine._splice_slot`` takes (and no second leaf beside it)."""
    parts = {"mamba": [], "attention": []}
    for (kind, _, _), out in zip(cfg.runs, done):
        parts[kind].append(out)

    def cat(kind, i):
        return jnp.concatenate([p[i] for p in parts[kind]], axis=0)

    return HybridCache(cat("attention", 0), cat("attention", 1),
                       cat("mamba", 0), cat("mamba", 1)), None


def rows_mix(cfg, layer_rows, q_pos, freqs_full, true_len=None,
             flash_prefill=False, **_):
    """The block's mixing operation for a from-zero prefill: a Mamba-2
    layer's chunked scan stopped at ``true_len`` (its final window and state
    come back in place of the zeros that came in), or an attention layer's
    causal self-attention writing the layer's rows. ``freqs_full``: no
    position embedding reads it."""
    del freqs_full

    def mix(h, lw, lora):
        if is_mamba(lw):
            return mamba_rows_mix(cfg, true_len)(h, lw, lora)
        return attention_rows_mix(cfg, *layer_rows, q_pos,
                                  flash_prefill)(h, lw, lora)

    return mix


def grid_mix(cfg, cache: HybridCache, layer, pos, freqs=None, live=None):
    """The block's mixing operation over the slot grid, one token a slot:
    a Mamba-2 layer shifts each slot's window and advances its state at
    ``[layer]`` of the two state leaves, in place (the state through
    ``kt_ssm_state_step`` on the TPU backend, which reads it once for the
    update and ``y``: ``ops.ssm_state``); an attention layer attends over
    layer ``layer`` of the K/V grid (``engine.grid_attend``). ``freqs`` and
    ``live``: nothing here reads them."""
    from .engine import grid_attend
    del freqs, live

    def state_update(decay, dtx, b, c):
        """(y, the whole ssm leaf with layer ``layer`` advanced)."""
        if ssm_state_step_auto(*cache.ssm.shape[2:]):
            return ssm_state_step(cache.ssm, layer, decay, dtx, b, c)
        ssm = lax.dynamic_index_in_dim(cache.ssm, layer, 0, keepdims=False)
        y, ssm = ssm_update(ssm, decay, dtx, b, c)
        return y, lax.dynamic_update_index_in_dim(cache.ssm, ssm, layer, 0)

    def mix(h, lw, lora):
        if not is_mamba(lw):
            attn, kv = qkv_attend(cfg, None, grid_attend(
                cfg, KVCache(cache.k, cache.v), layer, pos))(h, lw, lora)
            return attn, cache._replace(k=kv.k, v=kv.v)
        conv = lax.dynamic_index_in_dim(cache.conv, layer, 0, keepdims=False)
        y, conv, ssm = mamba_step(cfg, h[:, 0], lw, conv,
                                  state_update=state_update)
        with jax.named_scope("kt.cache_update"):
            cache_out = cache._replace(
                conv=lax.dynamic_update_index_in_dim(cache.conv, conv, layer,
                                                     0),
                ssm=ssm)
        return y[:, None], cache_out

    return mix

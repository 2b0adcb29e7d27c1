"""The engine's cache kind for decoders of window and full attention layers
(``models.afmoe``): a full layer's K/V rows for every position beside a
window layer's for its last W positions only.

A full layer keeps what every K/V grid keeps, a row a position,
``(L_full, SLOTS, NKV, S_max, Hd)``. A window layer never looks further
back than ``sliding_window`` = W positions, so a slot keeps W rows of it, a
**ring** ``(L_win, SLOTS, NKV, R, Hd)`` with R = min(W, S_max): position p
lives in row ``p mod R``. A decode step writes its token's row there and
attends over the ring's first ``min(pos + 1, R)`` rows, which hold the
band's keys exactly (keys are stored rotated, so their order in the ring
does not matter to the softmax): the grid's attention (``engine.
grid_attend``, the decode kernel on the TPU) with ``min(pos, R - 1)`` as the
frontier. A prompt's window layers hand back the ring its last W positions
make, row ``p mod W`` holding position p (:func:`ring_rows`), which the
engine's one splice writes over the slot's first rows; a ring row past a
short prompt's end is written by a decode step before any step reads it.

One manager holds both (:class:`WindowCache`), four leaves carried and
donated through ``_decode_block`` as the K/V grid is. A layer's index is
counted among its own kind's layers (``AfmoeConfig.layer_stacks``); which
kind a layer is, is read from its weights (``models.afmoe.is_window``).

Imported only by an engine whose config says ``cache_kind == "window"``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..models import afmoe as A
from ..models.generate import KVCache

# part of ``aot_cache.AOTKey``, as ``engine.GRID_LAYOUT`` is for K/V grids
GRID_LAYOUT = ("window:k,v:full_layer,slot,kv_head,row,head_dim;"
               "wk,wv:window_layer,slot,kv_head,row mod ring,head_dim")


class WindowCache(NamedTuple):
    """K and V of the full layers, then of the window layers' rings. The
    slot grid: k, v (L_full, SLOTS, NKV, S_max, Hd), wk, wv (L_win, SLOTS,
    NKV, R, Hd). A prompt's as a prefill returns it, row-major: k, v
    (L_full, B, T, NKV, Hd), wk, wv (L_win, B, min(W, T), NKV, Hd)."""
    k: jax.Array
    v: jax.Array
    wk: jax.Array
    wv: jax.Array


def _leaves(cfg, lead: int, full: tuple, ring: tuple) -> WindowCache:
    def z(n, rows):
        return jnp.zeros((n, lead, *rows), cfg.dtype)
    return WindowCache(z(cfg.n_full, full), z(cfg.n_full, full),
                       z(cfg.n_window, ring), z(cfg.n_window, ring))


def init_grid(cfg, slots: int, max_len: int) -> WindowCache:
    """The zeroed slot grid, head-major as the decode kernel reads it."""
    nkv, hd = cfg.n_kv_heads, cfg.head_dim
    return _leaves(cfg, slots, (nkv, max_len, hd),
                   (nkv, min(cfg.sliding_window, max_len), hd))


def init_rows(cfg, batch: int, t: int) -> WindowCache:
    """A prompt's rows before the prefill has made them (the mix makes them
    whole, and reads none of these)."""
    rows = (t, cfg.n_kv_heads, cfg.head_dim)
    return _leaves(cfg, batch, rows, rows)


def run_rows(rows: WindowCache, stack, start: int, n: int) -> tuple:
    """What one run of like layers scans over: its kind's two leaves, its
    ``n`` layers from ``start`` (counted among that kind's layers)."""
    pair = (rows.wk, rows.wv) if A.is_window(stack) else (rows.k, rows.v)
    return tuple(r[start:start + n] for r in pair)


def join_rows(cfg, done: list):
    """The runs' outputs, in order, as one prompt's ``WindowCache``: what
    ``engine._splice_slot`` takes (and no second leaf beside it)."""
    parts = {A.WINDOW: [], A.FULL: []}
    for (kind, _, _, _), out in zip(cfg.runs, done):
        parts[kind].append(out)

    def cat(kind, i):
        return jnp.concatenate([p[i] for p in parts[kind]], axis=0)

    return WindowCache(cat(A.FULL, 0), cat(A.FULL, 1), cat(A.WINDOW, 0),
                       cat(A.WINDOW, 1)), None


def ring_rows(rows: jax.Array, true_len, window: int) -> jax.Array:
    """A prompt's rows (B, T, NKV, Hd) as the ring its window layer keeps:
    (B, min(W, T), NKV, Hd), row r holding the last position p < true_len
    with p = r (mod W). A row with no such position (a prompt shorter than
    the ring) holds position 0's; decode writes it before it reads it."""
    t = rows.shape[1]
    r = jnp.arange(min(window, t))
    p = true_len - 1 - jnp.mod(true_len - 1 - r, window)
    return jnp.take(rows, jnp.maximum(p, 0), axis=1)


def rows_mix(cfg, layer_rows, q_pos, freqs_full, true_len=None,
             flash_prefill=False, **_):
    """The block's mixing operation for a from-zero prefill: the prompt's
    attention over itself (banded on a window layer; the flash kernel with
    the window as its operand where ``flash_prefill``). A full layer hands
    back its rows, a window layer the ring of its last W (:func:`ring_rows`,
    at the prompt's true length). ``layer_rows``: nothing here reads
    them."""
    del layer_rows
    t = q_pos.shape[0]
    true_len = t if true_len is None else true_len

    def attend(q, k, v, window):
        attn = A.self_attention(cfg, q, k, v, window, flash_prefill)
        if not window:
            return attn, (k, v)
        with jax.named_scope("kt.cache_update"):
            return attn, tuple(ring_rows(r, true_len, cfg.sliding_window)
                               for r in (k, v))

    return A.afmoe_mix(cfg, freqs_full[q_pos], attend)


def grid_mix(cfg, cache: WindowCache, layer, pos, freqs=None, live=None):
    """The block's mixing operation over the slot grid, one token a slot: a
    full layer attends over layer ``layer`` of the full grid, writing row
    ``pos``; a window layer over its ring, writing row ``pos mod R`` and
    reading rows up to ``min(pos, R - 1)`` (``engine.grid_attend``: the
    decode kernel on the TPU). ``live``: nothing here reads it."""
    from .engine import grid_attend
    del live

    def attend(q, k, v, window):
        if not window:
            attn, kv = grid_attend(cfg, KVCache(cache.k, cache.v), layer,
                                   pos)(q, k, v)
            return attn, cache._replace(k=kv.k, v=kv.v)
        ring = cache.wk.shape[3]
        attn, kv = grid_attend(cfg, KVCache(cache.wk, cache.wv), layer,
                               jnp.mod(pos, ring),
                               frontier=jnp.minimum(pos, ring - 1))(q, k, v)
        return attn, cache._replace(wk=kv.k, wv=kv.v)

    return A.afmoe_mix(cfg, freqs, attend)

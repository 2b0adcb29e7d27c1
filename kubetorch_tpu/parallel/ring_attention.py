"""Ring attention: context-parallel causal attention over the ICI torus.

The sequence axis is sharded over the ``context`` mesh axis. Each device holds
a local q/k/v chunk; K/V chunks rotate around the ring via
``jax.lax.ppermute`` (XLA lowers this to nearest-neighbor ICI transfers that
overlap with the chunk attention compute), and each device merges incoming
chunks into its local output with the online-softmax recurrence — attention
over the full sequence without any device ever holding more than 1/C of it.

The reference has no long-context support at all (SURVEY §5.7: no ring/
Ulysses/context-parallel code in its tree) — sequence scaling was delegated
to user frameworks. Here it is a mesh axis: ``.distribute("jax",
mesh={"context": C})``.

Two entry points:
- :func:`ring_attention` — the per-shard function, for use inside an existing
  ``shard_map`` (axis_name must be bound).
- :func:`ring_attention_sharded` — GSPMD-compatible wrapper: takes globally
  sharded arrays, applies ``shard_map`` over the context axis internally, so
  model code under plain ``jit`` can call it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _chunk_attention(q, k, v, scale, q_offset, kv_offset, causal):
    """fp32 blockwise attention of a local q chunk vs one roving kv chunk.

    Returns (m, l, unnormalized_acc) for online-softmax merging.
    q: (B, Sq, N, Hd); k, v: (B, Sk, NKV, Hd); offsets are global positions.
    """
    b, sq, nh, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    qg = q.reshape(b, sq, nkv, group, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    if causal:
        rows = q_offset + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = kv_offset + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((rows >= cols)[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                    # (b,k,g,s,1)
    # guard fully-masked rows (future-only chunks): exp(NEG_INF - NEG_INF)=1
    # would pollute l; clamp m so p underflows to 0 instead.
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m_safe)
    p = jnp.where(s <= NEG_INF, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)                    # (b,k,g,s,1)
    acc = jnp.einsum("bkgst,btkh->bkgsh", p.astype(v.dtype), v).astype(jnp.float32)
    return m_safe, l, acc


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "context", causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Per-shard ring attention. Shapes are LOCAL: (B, S/C, N, Hd).

    Must run inside ``shard_map`` (or pmap) with ``axis_name`` bound.
    """
    b, sq, nh, hd = q.shape
    nkv = k.shape[2]
    group = nh // nkv
    if scale is None:
        scale = hd ** -0.5

    ring = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    q_offset = my * sq

    # perm: device d sends its current kv chunk to d+1 (ring shift).
    perm = [(i, (i + 1) % ring) for i in range(ring)]

    m0 = jnp.full((b, nkv, group, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nkv, group, sq, 1), jnp.float32)
    acc0 = jnp.zeros((b, nkv, group, sq, hd), jnp.float32)

    def body(carry, step):
        m, l, acc, k_cur, v_cur = carry
        src = (my - step) % ring                 # origin device of k_cur
        kv_offset = src * k_cur.shape[1]
        m_c, l_c, acc_c = _chunk_attention(q, k_cur, v_cur, scale, q_offset,
                                           kv_offset, causal)
        m_new = jnp.maximum(m, m_c)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_c - m_new)
        l_new = l * alpha + l_c * beta
        acc_new = acc * alpha + acc_c * beta
        # rotate kv for the next step (skipped result on the last step is
        # harmless: scan's carry is simply unused afterwards)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    (m, l, acc, _, _), _ = lax.scan(body, (m0, l0, acc0, k, v),
                                    jnp.arange(ring))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).astype(q.dtype)              # (b, nkv, group, sq, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, nh, hd)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, mesh, *,
                           causal: bool = True, scale: Optional[float] = None,
                           batch_axes=("dcn", "data", "fsdp"),
                           context_axis: str = "context",
                           head_axis: str = "tensor") -> jax.Array:
    """GSPMD wrapper: q/k/v are (B, S, N, Hd) jit-level arrays sharded
    batch×context×heads; runs the ring per context-shard via shard_map."""
    from jax.sharding import PartitionSpec as P

    from .mesh import live_axes, normalize_batch_axes
    live = live_axes(mesh)
    ba = normalize_batch_axes(live, batch_axes)
    ha = head_axis if head_axis in live else None
    spec = P(ba, context_axis if context_axis in live else None, ha, None)

    if context_axis not in live:
        # no context sharding: plain attention on each device's rows/heads
        return _local_attention(q, k, v, mesh, causal, scale)

    fn = functools.partial(ring_attention, axis_name=context_axis,
                           causal=causal, scale=scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _local_attention(q, k, v, mesh, causal: bool, scale: Optional[float]):
    """Full-sequence attention when nothing shards the sequence: the flash
    kernel on TPU for shapes it takes (decided from the shape, beforehand),
    the XLA reference otherwise. Shared with the ulysses wrapper."""
    from ..ops.attention import flash_auto
    if flash_auto(q.shape[1], q.shape[2], k.shape[2]):
        from .kernel_shard import flash_attention_sharded
        return flash_attention_sharded(q, k, v, mesh, causal=causal,
                                       scale=scale)
    from ..models.llama import _xla_attention
    return _xla_attention(q, k, v, scale or q.shape[-1] ** -0.5,
                          causal=causal)


# ---------------------------------------------------------------------------
# context-parallel DECODE: one new token per slot against a cache whose
# sequence axis is sharded over the context mesh axis (long-context serving)
# ---------------------------------------------------------------------------


def sp_decode_attention(q, ck, cv, pos, *, axis_name: str,
                        scale: Optional[float] = None) -> jax.Array:
    """Per-shard body: decode attention over THIS shard's cache rows, then
    one online-softmax combine across the context axis — the full-sequence
    result without any device ever holding more than 1/C of the cache (and
    without the all-gather GSPMD would insert around a dense einsum).

    q (B, NH, Hd) replicated over ``axis_name``; ck/cv (B, NKV, S_local,
    Hd) this shard's rows of one layer of the head-major grid; pos (B,)
    GLOBAL frontier per slot. Rounding
    matches the engine's einsum reference (probs cast to the cache dtype
    before the PV dot); the split softmax itself combines in fp32."""
    b, nh, hd = q.shape
    nkv, s_local = ck.shape[1], ck.shape[2]
    group = nh // nkv
    if scale is None:
        scale = hd ** -0.5
    offset = lax.axis_index(axis_name) * s_local
    qg = q.reshape(b, nkv, group, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, ck).astype(jnp.float32) * scale
    cols = offset + jnp.arange(s_local)
    mask = cols[None, :] <= pos[:, None]                     # (B, S_local)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e29)
    p = jnp.exp(s - m)
    p = jnp.where(s <= NEG_INF, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)                   # (b,k,g,1)
    acc = jnp.einsum("bkgs,bksh->bkgh", p.astype(cv.dtype),
                     cv).astype(jnp.float32)
    m_g = lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)                                  # (b,k,g,1)
    l_g = lax.psum(l * corr, axis_name)
    acc_g = lax.psum(acc * corr, axis_name)
    out = acc_g / jnp.where(l_g == 0.0, 1.0, l_g)
    return out.reshape(b, nh, hd).astype(q.dtype)


def sp_decode_attention_quant(q, kq, ks, vq, vs, pos, *, axis_name: str,
                              scale: Optional[float] = None) -> jax.Array:
    """Per-shard body over an int8 cache shard (``serve.kv_quant``): the
    same split-softmax combine as :func:`sp_decode_attention` with the row
    scales folded in (logits columns ·ks, probs ·vs; all fp32) — so the
    int8 KV cache and context sharding COMPOSE: 1/(2C) of the fp cache
    bytes per chip."""
    b, nh, hd = q.shape
    nkv, s_local = kq.shape[1], kq.shape[2]
    group = nh // nkv
    if scale is None:
        scale = hd ** -0.5
    offset = lax.axis_index(axis_name) * s_local
    qg = q.reshape(b, nkv, group, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bksh->bkgs", qg,
                   kq.astype(jnp.float32)) * scale
    s = s * ks[:, :, None, :]                                # (B,NKV,1,S)
    cols = offset + jnp.arange(s_local)
    mask = cols[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e29)
    p = jnp.exp(s - m)
    p = jnp.where(s <= NEG_INF, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p * vs[:, :, None, :]
    acc = jnp.einsum("bkgs,bksh->bkgh", p, vq.astype(jnp.float32))
    m_g = lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = lax.psum(l * corr, axis_name)
    acc_g = lax.psum(acc * corr, axis_name)
    out = acc_g / jnp.where(l_g == 0.0, 1.0, l_g)
    return out.reshape(b, nh, hd).astype(q.dtype)


def _sp_decode_specs(mesh, batch_axes, context_axis, head_axis):
    """(q_spec, kv_spec, scale_spec, pos_spec) for the decode shard_maps —
    one builder so the fp and quant wrappers can't drift."""
    from jax.sharding import PartitionSpec as P

    from .mesh import live_axes, normalize_batch_axes
    live = live_axes(mesh)
    if context_axis not in live:
        raise ValueError("sp decode requires a live "
                         f"{context_axis!r} mesh axis (callers gate on it "
                         "via sp_decode_supported)")
    ba = normalize_batch_axes(live, batch_axes)
    ha = head_axis if head_axis in live else None
    return (P(ba, ha, None), P(ba, ha, context_axis, None),
            P(ba, ha, context_axis), P(ba))


def sp_decode_supported(mesh, b: int, s: int, nkv: int, nh: int, *,
                        batch_axes=("dcn", "data", "fsdp"),
                        context_axis: str = "context",
                        head_axis: str = "tensor") -> bool:
    """Can the sp decode path partition these shapes evenly? shard_map has
    no GSPMD-style padding: every named dim must divide by its axis. When
    this says no, callers fall back to the dense path and let GSPMD handle
    layout (correct, just without the memory split)."""
    import math

    from .mesh import live_axes
    live = live_axes(mesh)
    if live.get(context_axis, 1) <= 1:
        return False
    if s % live[context_axis]:
        return False
    bprod = math.prod(live.get(a, 1) for a in batch_axes)
    if b % bprod:
        return False
    hsz = live.get(head_axis, 1)
    return nkv % hsz == 0 and nh % hsz == 0


def sp_decode_attention_sharded(q, ck, cv, pos, mesh, *,
                                scale: Optional[float] = None,
                                batch_axes=("dcn", "data", "fsdp"),
                                context_axis: str = "context",
                                head_axis: str = "tensor") -> jax.Array:
    """GSPMD wrapper for the engine's decode step: one layer of the grid
    (B, NKV, S, Hd) sharded batch×heads×context, q (B, NH, Hd)
    batch×heads, pos (B,) batch. shard_map pins those layouts, so jit
    KEEPS the cache context-sharded across steps instead of gathering it.
    Callers gate on :func:`sp_decode_supported`."""
    q_spec, kv_spec, _, pos_spec = _sp_decode_specs(
        mesh, batch_axes, context_axis, head_axis)
    fn = functools.partial(sp_decode_attention, axis_name=context_axis,
                           scale=scale)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, pos_spec),
                         out_specs=q_spec, check_vma=False)(q, ck, cv, pos)


def sp_decode_attention_quant_sharded(q, kq, ks, vq, vs, pos, mesh, *,
                                      scale: Optional[float] = None,
                                      batch_axes=("dcn", "data", "fsdp"),
                                      context_axis: str = "context",
                                      head_axis: str = "tensor") -> jax.Array:
    """int8-cache variant of :func:`sp_decode_attention_sharded`: values
    int8 (B, NKV, S, Hd) + per-row scales (B, NKV, S), both sharded over
    batch×heads×context."""
    q_spec, kv_spec, sc_spec, pos_spec = _sp_decode_specs(
        mesh, batch_axes, context_axis, head_axis)
    fn = functools.partial(sp_decode_attention_quant,
                           axis_name=context_axis, scale=scale)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(q_spec, kv_spec, sc_spec, kv_spec, sc_spec, pos_spec),
        out_specs=q_spec, check_vma=False)(q, kq, ks, vq, vs, pos)

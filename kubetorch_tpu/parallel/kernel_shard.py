"""The Pallas attention kernels under a mesh.

A ``pallas_call`` is a custom call, and GSPMD cannot partition one: on the
chip jax refuses a Mosaic kernel left bare inside a sharded jit ("cannot be
automatically partitioned"). Attention is independent per batch row and per
kv-head group, so each wrapper here runs the kernel through ``shard_map`` over
the batch axes and the ``tensor`` (head) axis — every device attends its own
rows and heads, and no operand moves. Off-mesh (``mesh`` is None or has no live axis) the kernel is
called directly, as it is inside another ``shard_map``, so call sites pass
``current_mesh()`` and nothing else.

A dim an axis does not divide is left unsharded on that axis (``shard_map``
does not pad): a batch-1 prefill replicates over the batch axes, and heads
shard only when ``tensor`` divides both head counts — contiguous head blocks
keep q-head i ↔ kv-head i // group aligned per shard.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from ..ops import attention, decode_attention as decode
from .mesh import AXIS_TENSOR, fit_batch_axes, live_axes


def _axes(mesh, batch: int, n_heads: int, n_kv_heads: int):
    """(batch entry, head entry) of the PartitionSpecs; None off-mesh and
    inside another ``shard_map`` (a pipeline stage body), where the shapes
    are already the local ones."""
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    live = live_axes(mesh)
    ba = fit_batch_axes(live, batch)
    t = live.get(AXIS_TENSOR, 1)
    ha = AXIS_TENSOR if t > 1 and n_heads % t == 0 and n_kv_heads % t == 0 \
        else None
    return None if ba is None and ha is None else (ba, ha)


def flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, mesh,
                            *, causal: bool = True,
                            scale: Optional[float] = None) -> jax.Array:
    """:func:`~..ops.attention.flash_attention` on (B, S, N, Hd) arrays
    sharded batch × heads."""
    fn = functools.partial(attention.flash_attention, causal=causal,
                           scale=scale)
    axes = _axes(mesh, q.shape[0], q.shape[2], k.shape[2])
    if axes is None:
        return fn(q, k, v)
    spec = P(axes[0], None, axes[1], None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def decode_attention_sharded(q, gk, gv, pos, layer, mesh, *,
                             scale: Optional[float] = None) -> jax.Array:
    """:func:`~..ops.decode_attention.decode_attention` with q (B, NH, Hd),
    the stacked grids (L, B, NKV, S, Hd) and pos (B,) sharded slots ×
    heads — the layout ``serve.engine._cache_shardings`` keeps the grid
    in; ``layer`` (scalar) is replicated."""
    fn = functools.partial(decode.decode_attention, scale=scale)
    axes = _axes(mesh, q.shape[0], q.shape[1], gk.shape[2])
    if axes is None:
        return fn(q, gk, gv, pos, layer)
    ba, ha = axes
    q_spec, kv_spec = P(ba, ha, None), P(None, ba, ha, None, None)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, P(ba), P()),
                         out_specs=q_spec,
                         check_vma=False)(q, gk, gv, pos, layer)


def decode_attention_quant_sharded(q, kq, ks, vq, vs, pos, layer, mesh, *,
                                   scale: Optional[float] = None) -> jax.Array:
    """int8-grid variant: values (L, B, NKV, S, Hd) int8 and per-row scales
    (L, B, NKV, S), both sharded slots × heads."""
    fn = functools.partial(decode.decode_attention_quant, scale=scale)
    axes = _axes(mesh, q.shape[0], q.shape[1], kq.shape[2])
    if axes is None:
        return fn(q, kq, ks, vq, vs, pos, layer)
    ba, ha = axes
    q_spec, kv_spec, sc_spec = (P(ba, ha, None), P(None, ba, ha, None, None),
                                P(None, ba, ha, None))
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(q_spec, kv_spec, sc_spec, kv_spec, sc_spec, P(ba), P()),
        out_specs=q_spec, check_vma=False)(q, kq, ks, vq, vs, pos, layer)

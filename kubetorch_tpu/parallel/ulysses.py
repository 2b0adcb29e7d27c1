"""Ulysses-style sequence parallelism: all-to-all head scatter.

The second context-parallel strategy (SURVEY §5.7) besides ring attention:
instead of rotating K/V chunks around a ring, one ``all_to_all`` re-shards
the activations from sequence-sharded to **head-sharded**, every device runs
full-sequence attention for its head subset, and a second ``all_to_all``
restores sequence sharding. Two collectives per attention — better than the
ring when heads ≥ devices and sequence chunks are small enough that ring
latency dominates; worse at very long sequences (full-S attention memory per
device). Selectable per-config: ``attn_impl="ulysses"``.

Shapes inside shard_map over axis C (= ulysses degree, mesh axis "context"):
  local q: (B, S/C, N, Hd) ── all_to_all ──> (B, S, N/C, Hd)
  full-seq attention on N/C heads (flash kernel when on TPU)
  out: (B, S, N/C, Hd) ── all_to_all ──> (B, S/C, N, Hd)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _heads_to_seq(x: jax.Array, axis: str) -> jax.Array:
    """(B, S, N/C, Hd) → (B, S/C, N, Hd)."""
    return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)


def _seq_to_heads(x: jax.Array, axis: str) -> jax.Array:
    """(B, S/C, N, Hd) → (B, S, N/C, Hd)."""
    return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "context", causal: bool = True,
                      scale: Optional[float] = None) -> jax.Array:
    """Per-shard Ulysses attention. Local shapes: (B, S/C, N, Hd); requires
    C | N and C | NKV. Must run inside shard_map with ``axis_name`` bound."""
    n, nkv = q.shape[2], k.shape[2]
    c = lax.axis_size(axis_name)
    if n % c or nkv % c:
        raise ValueError(
            f"ulysses degree {c} must divide n_heads={n} and n_kv_heads={nkv}")

    qh = _seq_to_heads(q, axis_name)      # (B, S, N/C, Hd)
    kh = _seq_to_heads(k, axis_name)
    vh = _seq_to_heads(v, axis_name)

    # already inside shard_map (mesh=None): the local heads' full sequence
    from .ring_attention import _local_attention
    out = _local_attention(qh, kh, vh, None, causal, scale)

    return _heads_to_seq(out, axis_name)  # (B, S/C, N, Hd)


def ulysses_attention_sharded(q, k, v, mesh, *, causal: bool = True,
                              scale: Optional[float] = None,
                              batch_axes=("dcn", "data", "fsdp"),
                              context_axis: str = "context",
                              head_axis: str = "tensor"):
    """GSPMD wrapper mirroring ``ring_attention_sharded``: q/k/v are global
    (B, S, N, Hd) arrays sequence-sharded over the context axis; head
    sharding over the tensor axis is preserved (no silent all-gather)."""
    from jax.sharding import PartitionSpec as P

    from .mesh import live_axes
    live = live_axes(mesh)
    if context_axis not in live:
        # no context sharding: same choice as the ring wrapper
        from .ring_attention import _local_attention
        return _local_attention(q, k, v, mesh, causal, scale)
    from .mesh import normalize_batch_axes
    ba = normalize_batch_axes(live, batch_axes)
    # preserve head sharding over tensor only when the ulysses degree still
    # divides the LOCAL head counts; otherwise replicate heads (the pre-TP
    # behavior) instead of crashing GQA configs
    c = live[context_axis]
    t = live.get(head_axis, 1)
    ha = head_axis if (head_axis in live and
                       (q.shape[2] // t) % c == 0 and
                       (k.shape[2] // t) % c == 0 and
                       q.shape[2] % t == 0 and k.shape[2] % t == 0) else None
    spec = P(ba, context_axis, ha, None)

    fn = functools.partial(ulysses_attention, axis_name=context_axis,
                           causal=causal, scale=scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

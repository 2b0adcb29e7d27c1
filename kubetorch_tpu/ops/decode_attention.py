"""Flash-decode: fused single-token attention over a slot-grid KV cache.

The engine's decode step attends one new token per slot against that slot's
whole cache. The XLA einsum path materializes a (B, NKV, G, S) logits
tensor per step and reads the full (B, S, NKV, Hd) cache even past each
slot's frontier; at serving lengths the logits tile plus the masked tail
are wasted HBM round-trips on the latency-critical op. This kernel streams
K/V tiles through VMEM with an online softmax (the FlashAttention recipe
with a query block of GQA group rows) and — the decode-specific part —
**skips every tile beyond the slot's position outright**: ``pos`` rides in
as a prefetched scalar and the K/V BlockSpec index maps clamp to the last
in-range tile (Pallas elides the DMA when the block index repeats), so a
slot 300 tokens into a 4096-row cache streams one tile of 512 rows, not
eight ([pos // block_k] + 1 of them); ``pl.when`` skips the matching
compute.

**A grid step is one tile of rows of one slot, for every KV head the
device holds.** The grid is ``(slots × head groups, S // block_k)`` and
the K/V block ``(1, 1, heads, block_k, Hd)``: at 8 KV heads of 128 and
2,048 rows that is 16 × 4 = 64 steps a call, each moving 2 MiB; one head
per step would be 512 steps of 256 KiB, and a grid step costs ~0.3 us
whether or not it streams a tile, which most of a part-filled pool's do
not. ``heads`` is the largest divisor of the local KV heads whose K and V
tiles, double-buffered, fit ``KV_VMEM_BUDGET`` (``decode_plan``): all of
them at serving shapes, so "head groups" is 1; under a mesh the kernel
sees the device's own heads (``parallel/kernel_shard.py``) and takes
those. A past-frontier step still costs a grid step, and a slot's first
tile is fetched only one step ahead, so a part-filled pool runs at ~40%
of the HBM rate and a full one at ~85% (``PERF.md`` §5).

The kernel reads the engine's STACKED grid where it lies: K/V operands
are the whole ``(L, B, NKV, S, Hd)`` grids (head-major, so the last two
axes are (row, dim) and a ``BlockSpec`` can address one layer's tile), and
the layer index rides in as a second prefetched scalar that the index maps
put on the leading axis. No layer is sliced out and nothing is transposed
on the way in.

Two cache dtypes share ONE kernel body (``_make_decode_kernel``):

- full-precision rows — probs round through the cache dtype before the PV
  dot, matching the einsum reference bitwise;
- int8 rows + per-row fp32 scales ``(L, B, NKV, S)`` (``serve.kv_quant``)
  — the scales fold into the math (logits columns ·ks, probs ·vs; all
  fp32), so the HBM stream is int8 tiles plus one (1, block_k) scale row
  per tile and no fp rows ever materialize.

Layout mirrors ``ops.attention``: a (heads, G, Hd) query block per grid
step, fp32 accumulators in VMEM scratch (one row of m, l and acc per
head and query row), the innermost grid axis sequential over K tiles.
Inside a step both dots are batched over the heads; each head's online
softmax runs over the same tiles in the same order as with one head per
step, so at the same ``block_k`` its result is the same.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

NEG_INF = -1e30

# query rows per block = GQA group size padded up to the fp32 sublane tile
_MIN_ROWS = 8

# VMEM a grid step's K and V tiles may take, double-buffered (2 operands ×
# 2 buffers): 8 heads × 512 rows × 128 bf16 values fill it exactly
KV_VMEM_BUDGET = 4 * 2 ** 20


def _make_decode_kernel(quant: bool, *, scale: float, block_k: int,
                        head_groups: int):
    """One online-softmax body for both cache layouts. ``quant`` is a
    trace-time switch: it only changes which refs exist and where the
    row scales fold in — the frontier skip, init/finalize, and softmax
    scaffolding are shared so they can never drift apart. A grid step
    holds the tiles of several KV heads (the refs' leading block axis);
    the two dots are batched over them, and each head's softmax is the
    one-head kernel's. Grid axis 0 is slot × ``head_groups`` + group."""

    def kernel(pos_ref, layer_ref, q_ref, *refs):
        del layer_ref                     # read by the index maps only
        if quant:
            k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
        else:
            k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        kj = pl.program_id(1)
        nk = pl.num_programs(1)

        @pl.when(kj == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        pos_b = pos_ref[pl.program_id(0) // head_groups]
        start = kj * block_k

        # the whole tile is past this slot's frontier ⇒ nothing to read
        @pl.when(start <= pos_b)
        def _compute():
            q = q_ref[0].astype(jnp.float32)          # (H, Gp, Hd)
            k = k_ref[0, 0].astype(jnp.float32)       # (H, BK, Hd)
            s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if quant:
                s = s * ks_ref[0, 0]                  # (H, 1, BK) logit columns
            cols = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, q.shape[1], block_k), 2)
            s = jnp.where(cols <= pos_b, s, NEG_INF)

            m_prev = m_ref[:]                         # (H, Gp, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                # vs folds into the probs; int8 V dequantizes to fp32 —
                # the whole PV dot runs fp32 (the quant einsum reference)
                pv_lhs = p * vs_ref[0, 0]
                v = v_ref[0, 0].astype(jnp.float32)
            else:
                # p rounds through the cache dtype before the PV dot
                # (fp32 acc) — same rounding as the einsum reference and
                # the flash fwd kernel
                v = v_ref[0, 0]
                pv_lhs = p.astype(v.dtype)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                pv_lhs, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            m_ref[:] = m_new

        @pl.when(kj == nk - 1)
        def _finalize():
            l = l_ref[:]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)

    return kernel


class DecodePlan(NamedTuple):
    """What one ``pallas_call`` of the kernel is made of, from shapes
    alone (``decode_plan``)."""
    heads: int             # KV heads whose tiles one grid step holds
    block_k: int           # cache rows per tile
    grid: Tuple[int, int]  # (slots × head groups, tiles per cache)
    tile_pair_bytes: int   # one grid step's K tile + V tile (an int8 grid's
                           # scale rows, 4 bytes a row, come on top)


def decode_plan(b: int, nkv: int, s: int, hd: int, itemsize: int, *,
                block_k: int = 512) -> DecodePlan:
    """The kernel's grid and tile for ``b`` slots of ``s`` rows × ``nkv``
    (local) KV heads of ``hd`` values of ``itemsize`` bytes: what
    ``_decode_call`` itself launches. The tile is the largest power-of-two
    cut of ``block_k`` that divides ``s``; a grid step takes the largest
    divisor of ``nkv`` whose K and V tiles, double-buffered, fit
    ``KV_VMEM_BUDGET``."""
    bk = min(block_k, s)
    while s % bk:
        bk //= 2
    tile = bk * hd * itemsize
    heads = max(h for h in range(1, nkv + 1)
                if nkv % h == 0 and (h == 1 or 4 * h * tile <= KV_VMEM_BUDGET))
    return DecodePlan(heads, bk, (b * (nkv // heads), s // bk),
                      2 * heads * tile)


def _decode_call(quant: bool, q, values, scales, pos, layer, *,
                 scale: Optional[float], block_k: int,
                 interpret: Optional[bool]):
    """Shared wrapper: shape derivation, GQA padding, frontier-clamp
    BlockSpecs, scratch, and output slicing for both dtypes. ``values`` =
    (gk, gv) stacked grids (L, B, NKV, S, Hd), read in place; ``scales`` =
    (ks, vs) per-row scales (L, B, NKV, S) for the quant grid, else None;
    ``layer`` picks the grid's leading index (second prefetched scalar)."""
    b, nh, hd = q.shape
    nkv, s = values[0].shape[2], values[0].shape[3]
    assert nh % nkv == 0, f"GQA requires n_kv | n_heads, got {nkv}, {nh}"
    group = nh // nkv
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = interpret_default()

    heads, bk, grid, _ = decode_plan(b, nkv, s, hd, values[0].dtype.itemsize,
                                     block_k=block_k)
    hg = nkv // heads                 # head groups: 1 where all heads fit

    # group-major query rows, padded to the sublane tile
    gp = max(_MIN_ROWS, group)
    qg = q.reshape(b, nkv, group, hd)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    # grid axis 0 walks (slot, head group), slot-major; axis 1 the tiles.
    # The frontier skip lives in the index maps, not the kernel body:
    # Pallas elides a block DMA only when the index map returns the same
    # block as the previous step, so past-frontier steps clamp to the last
    # in-range tile (the kernel's pl.when then skips the compute too).
    # pl.when alone would save FLOPs but still stream every tile from HBM.
    def tile(i, j, pos_):
        return jnp.minimum(j, pos_[i // hg] // bk)

    val_spec = pl.BlockSpec(
        (1, 1, heads, bk, hd),
        lambda i, j, pos_, l_: (l_[0], i // hg, i % hg, tile(i, j, pos_), 0))
    scale_spec = pl.BlockSpec(
        (1, 1, heads, 1, bk),
        lambda i, j, pos_, l_: (l_[0], i // hg, i % hg, 0, tile(i, j, pos_)))
    q_spec = pl.BlockSpec((1, heads, gp, hd),
                          lambda i, j, pos_, l_: (i // hg, i % hg, 0, 0))
    inputs, in_specs = [qg], [q_spec]
    for i, val in enumerate(values):
        inputs.append(val)
        in_specs.append(val_spec)
        if quant:
            inputs.append(scales[i][:, :, :, None, :])   # (L, B, NKV, 1, S)
            in_specs.append(scale_spec)

    out = pl.pallas_call(
        _make_decode_kernel(quant, scale=scale, block_k=bk, head_groups=hg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((heads, gp, hd), jnp.float32),    # acc
                pltpu.VMEM((heads, gp, 1), jnp.float32),     # m
                pltpu.VMEM((heads, gp, 1), jnp.float32),     # l
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nkv, gp, hd), q.dtype),
        interpret=interpret,
        name="kt_decode_attention_quant" if quant else "kt_decode_attention",
    )(pos.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      *inputs)
    return out[:, :, :group].reshape(b, nh, hd)


def decode_attention(q: jax.Array, gk: jax.Array, gv: jax.Array,
                     pos: jax.Array, layer, *,
                     scale: Optional[float] = None, block_k: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """One new token per slot against its cache rows ``<= pos`` of one
    layer of the stacked grid.

    q: (B, NH, Hd); gk/gv: (L, B, NKV, S, Hd), the engine's head-major
    grid, read in place; pos: (B,) int32 — the row each slot's new token
    occupies (already written); layer: int or traced int32 scalar.
    Returns (B, NH, Hd). Bit-compatible with the masked-einsum reference
    in ``serve.engine._einsum_attention`` (asserted in
    tests/test_decode_kernel.py).

    ``block_k=512`` rows of every local KV head make a grid step (2 MiB of
    K and V at 8 heads of 128 in bf16). A larger tile streams a full cache
    in fewer steps, a smaller one over-reads fewer rows past a part-filled
    slot's frontier and overlaps more of a slot's fetch with its arithmetic:
    on a v5e at the benchmark cells' fill 256 is 7% faster for bf16 rows
    and 6% slower for int8, 128 and 1024 are slower for both (``PERF.md``
    §5), so one default serves both grids.
    """
    return _decode_call(False, q, (gk, gv), None, pos, layer, scale=scale,
                        block_k=block_k, interpret=interpret)


def decode_attention_quant(q: jax.Array, kq: jax.Array, ks: jax.Array,
                           vq: jax.Array, vs: jax.Array, pos: jax.Array,
                           layer, *, scale: Optional[float] = None,
                           block_k: int = 512,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Flash-decode over an int8 grid (``serve.kv_quant``): same frontier
    tile-skipping as :func:`decode_attention`, HALF the HBM stream.

    q: (B, NH, Hd); kq/vq: (L, B, NKV, S, Hd) int8; ks/vs: (L, B, NKV, S)
    fp32 per-row scales; pos: (B,); layer as in :func:`decode_attention`.
    Bit-compatible with the fp32 fold-in einsum reference
    (``serve.engine._einsum_attention``), asserted in tests/test_kv_quant.py."""
    return _decode_call(True, q, (kq, vq), (ks, vs), pos, layer, scale=scale,
                        block_k=block_k, interpret=interpret)

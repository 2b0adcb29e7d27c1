"""Grouped experts: one call streams only the banks that got
a token, from the whole stacked banks where they lie. Written for a decode
step's few rows; a short prompt's rows take it too (every bank is hit there,
and alone on a v5e it still reads 15-24% under the einsum form at 128-512
rows).

A decode step of a fine-grained expert layer (``models.mla``: 64 experts of
1,408, top-6) has 16 rows, and at 16 rows about a fifth of a layer's experts
get no token at all (1 − (1 − K/E)^m of them are hit: 55 / 79 / 96% at
8 / 16 / 32 rows). The einsum form multiplies every row by every bank with
a gate of zero where the row did not choose it, so it streams all of them;
XLA's grouped matmul (``jax.lax.ragged_dot``) is twice off the stream at
these sizes. This kernel computes

    out = Σ_e gate[:, e] · (silu(x·Wg_e) ⊙ (x·Wu_e)) · Wd_e

over the experts ``e`` with ``sizes[e] > 0`` only, in the house style of
``ops.decode_attention``:

- **Operands in place.** ``w_gate``, ``w_up`` ``(L, E, D, F)`` and ``w_down``
  ``(L, E, F, D)`` are a run's whole stacks; the layer index and the work
  list ride in as prefetched scalars and the ``BlockSpec`` index maps put
  them on the two leading axes. No bank is sliced, copied or transposed on
  the way in.
- **Work list.** The ids of the hit experts first (a stable sort of
  ``sizes == 0``), their count beside them, the tail padded with the last
  hit id (``work_list``). Grid step ``i < n_hit`` streams expert ``ids[i]``;
  a later step names the block of the step before it, so Pallas elides its
  DMA, and ``pl.when`` skips its body. No hit at all gives zeros.
- **A grid step is one expert, whole** where its three matrices fit the
  VMEM double-buffered (17.3 MB at 2048 × 1408 in bfloat16, ≥ 21 us of
  stream, beside which the ~0.3 us a grid step costs vanishes; 1408 =
  11 × 128 has no useful lane-aligned divisor; double-buffered 34.6 MB, so
  the call raises ``vmem_limit_bytes``: ``vmem_bytes``; a v5e has 128 MiB).
  A wider expert (6144 × 2048: 75.5 MB) goes ``f_tile`` of its F columns a
  step, the widest lane-aligned divisor that fits (1,024 there): SwiGLU is
  a sum over F, so a step adds its columns' part of the down product, and
  an expert is ``F / f_tile`` steps in a row.
- Every row goes through every hit expert, its gate (the routing weight,
  zero where the row did not choose the expert) applied to the expert's
  output in float32 before the sum, which is kept in a float32 VMEM scratch
  over the sequential grid axis and written once.

Precision is the einsum form's or better: operands in their own dtype,
float32 accumulation in each dot, the activation rounded to the operands'
dtype before the down product.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

# rows are padded up to the bfloat16 sublane tile
_ROW_TILE = 16

# what the call may ask of a core's VMEM (a v5e has 128 MiB), and what it
# needs beside the double-buffered banks: rows, gates, the accumulator, the
# products' float32 intermediates and Mosaic's own scratch
VMEM_MAX = 112 * 2 ** 20
VMEM_BESIDE_BANKS = 16 * 2 ** 20


def vmem_bytes(d: int, f: int, itemsize: int) -> int:
    """VMEM one call needs: an expert's three matrices, double-buffered,
    and ``VMEM_BESIDE_BANKS``."""
    return 2 * 3 * d * f * itemsize + VMEM_BESIDE_BANKS


def f_tile(d: int, f: int, itemsize: int) -> int:
    """The F columns of an expert that one grid step streams: all of them
    where the three matrices double-buffered fit the VMEM, else the widest
    lane-aligned divisor of ``f`` whose blocks do; 0 where the kernel cannot
    tile experts of ``d`` × ``f`` at all."""
    if d % 128 or f % 128:
        return 0
    return next((f // n for n in range(1, f // 128 + 1)
                 if f % (128 * n) == 0
                 and vmem_bytes(d, f // n, itemsize) <= VMEM_MAX), 0)


def moe_experts_supported(d: int, f: int, itemsize: int) -> bool:
    """Whether the kernel tiles on the chip for experts of ``d`` × ``f``:
    both lane-aligned, and some tile of an expert fits the VMEM."""
    return f_tile(d, f, itemsize) > 0


def moe_experts_auto(d: int, f: int, itemsize: int) -> bool:
    """The choice ``models.mla`` makes (as ``ops.attention.flash_auto``): the
    kernel on the TPU backend for experts it tiles, the einsum form
    otherwise. Interpreted, a call is 64 grid steps of Python."""
    return (jax.default_backend() == "tpu"
            and moe_experts_supported(d, f, itemsize))


def work_list(sizes: jax.Array):
    """(ids (E,), n_hit (1,)) int32 from ``sizes`` (E,), the pairs an expert
    got: the experts with any first, in their order, then the last of them
    again for every step that streams nothing."""
    e = sizes.shape[0]
    order = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)
    n_hit = jnp.sum(sizes > 0, dtype=jnp.int32)
    ids = jnp.where(jnp.arange(e) < n_hit, order,
                    order[jnp.maximum(n_hit - 1, 0)])
    return ids, n_hit.reshape(1)


def bank_block(i, layer_ref, ids_ref, n_ref, chunks: int = 1,
               down: bool = False):
    """Index map of the three banks: grid step ``i`` names expert
    ``ids[i]`` of layer ``layer``, whole; or, an expert being ``chunks``
    steps, its F-tile ``i % chunks`` (on the last axis of gate and up, on
    the row axis of ``down``). A step past the list names the last step's
    block again."""
    if chunks == 1:
        return layer_ref[0], ids_ref[i], 0, 0     # the tail repeats its last
    tile = jnp.where(i < n_ref[0] * chunks, i % chunks, chunks - 1)
    tail = (tile, 0) if down else (0, tile)
    return layer_ref[0], ids_ref[i // chunks], *tail


def _kernel(chunks, layer_ref, ids_ref, n_ref, x_ref, g_ref, wg_ref, wu_ref,
            wd_ref, o_ref, acc_ref):
    del layer_ref                         # read by the index maps only
    i = pl.program_id(0)
    expert = i if chunks == 1 else i // chunks

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < (n_ref[0] if chunks == 1 else n_ref[0] * chunks))
    def _expert():
        x = x_ref[:]                                            # (M, D)
        gate = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(x.dtype)          # (M, F)
        y = jnp.dot(act, wd_ref[0, 0], preferred_element_type=jnp.float32)
        acc_ref[:] += g_ref[ids_ref[expert]] * y                # (M, 1)·(M, D)

    @pl.when(i == pl.num_programs(0) - 1)
    def _write():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def moe_experts(x: jax.Array, gates: jax.Array, w_gate: jax.Array,
                w_up: jax.Array, w_down: jax.Array, layer, sizes: jax.Array,
                *, interpret: Optional[bool] = None) -> jax.Array:
    """x (M, D) rows; gates (M, E) float32, a row's routing weight for an
    expert and zero where it did not choose it; w_gate / w_up (L, E, D, F)
    and w_down (L, E, F, D), read in place; layer: int or traced int32
    scalar; sizes (E,): the pairs an expert got (only ``sizes > 0`` is
    read). Returns (M, D) in x's dtype."""
    m, d = x.shape
    n_experts, f = w_gate.shape[1], w_gate.shape[3]
    if interpret is None:
        interpret = interpret_default()
    mp = -(-m // _ROW_TILE) * _ROW_TILE
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
        gates = jnp.pad(gates, ((0, mp - m), (0, 0)))
    ids, n_hit = work_list(sizes)
    ft = f_tile(d, f, w_gate.dtype.itemsize) or f
    chunks = f // ft

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    block = partial(bank_block, chunks=chunks)
    out = pl.pallas_call(
        partial(_kernel, chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_experts * chunks,),
            in_specs=[whole((mp, d)), whole((n_experts, mp, 1)),
                      pl.BlockSpec((1, 1, d, ft), block),
                      pl.BlockSpec((1, 1, d, ft), block),
                      pl.BlockSpec((1, 1, ft, d), partial(block, down=True))],
            out_specs=whole((mp, d)),
            scratch_shapes=[pltpu.VMEM((mp, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(d, ft, w_gate.dtype.itemsize)),
        interpret=interpret,
        name="kt_moe_experts",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, n_hit, x,
      gates.astype(jnp.float32).T[:, :, None], w_gate, w_up, w_down)
    return out[:m]

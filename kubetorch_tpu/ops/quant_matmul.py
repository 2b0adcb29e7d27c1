"""Fused int4-dequant matmul: the HBM stream is the PACKED nibbles.

XLA cannot fuse the int4 unpack (shift / sign-extend / concat) into a
dot's operand pipeline the way it fuses the int8 ``convert``: the
unpacked full-precision weight materializes in HBM every step
(``scripts/tpu_int4_probe.py`` times it). This kernel does the unpack in VMEM:
each grid step DMAs one packed tile — half of int8's bytes — shifts the
two nibble planes out on the VPU, and issues one MXU dot per plane
against the matching halves of ``x`` (the half-split pack format of
``models.quant._quantize_leaf_int4``: byte row r = weight rows r and
r + K/2). Group scales (one per ``block_k`` rows) multiply the partial
product, so the accumulation is exact over groups.

Decode is weight-bound at batch≈slots, so this is the difference between
int4-as-capacity (fits, but slower than int8) and int4-as-throughput
(half int8's weight stream).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default


def _kernel(xlo_ref, xhi_ref, p_ref, slo_ref, shi_ref, o_ref):
    kj = pl.program_id(1)
    p = p_ref[:].astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)      # sign-extend nibble
    hi = jnp.right_shift(jnp.left_shift(p, 24), 28)
    part = jnp.dot(xlo_ref[:], lo.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * slo_ref[:]
    part = part + jnp.dot(xhi_ref[:], hi.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32) * shi_ref[:]

    @pl.when(kj == 0)
    def _init():
        o_ref[:] = part

    @pl.when(kj > 0)
    def _acc():
        o_ref[:] += part


@functools.partial(jax.jit, static_argnames=("block_j", "interpret"))
def _q4_matmul(x, packed, scale, block_j: int, interpret: bool):
    b, din = x.shape
    half, dout = packed.shape
    groups = scale.shape[0]
    block_k = half // (groups // 2)      # = the quantization group size
    kt = half // block_k
    xlo, xhi = x[:, : din // 2], x[:, din // 2:]
    # one scale row per K tile: Mosaic takes a (1, block_j) block only when
    # the 1 is the array's whole second-to-last dim, so each half rides as
    # (groups/2, 1, N) with the group dim squeezed out of the block
    slo = scale[: groups // 2, None, :]
    shi = scale[groups // 2:, None, :]
    grid = (dout // block_j, kt)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_k), lambda j, k: (0, k)),        # x lo
            pl.BlockSpec((b, block_k), lambda j, k: (0, k)),        # x hi
            pl.BlockSpec((block_k, block_j), lambda j, k: (k, j)),  # packed
            pl.BlockSpec((None, 1, block_j), lambda j, k: (k, 0, j)),  # s lo
            pl.BlockSpec((None, 1, block_j), lambda j, k: (k, 0, j)),  # s hi
        ],
        out_specs=pl.BlockSpec((b, block_j), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xlo, xhi, packed, slo, shi)
    return out


def q4_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
              block_j: int = 512,
              interpret: Optional[bool] = None) -> jax.Array:
    """``x @ W`` where W is half-split nibble-packed int4.

    x (B, K) any float dtype; packed (K/2, N) int8; scale (K/g, N) f32
    with the group size g dividing K/2 evenly (the kernel's K tile IS the
    group). Returns (B, N) f32 — callers cast. Shapes that don't tile
    (g ∤ K/2, block_j ∤ N) must use the XLA fallback
    (``models.quant._dequant_int4``); ``supported`` checks."""
    if interpret is None:
        interpret = interpret_default()
    return _q4_matmul(x.astype(jnp.bfloat16), packed, scale,
                      block_j=min(block_j, packed.shape[1]),
                      interpret=bool(interpret))


def q4_supported(x_shape, packed_shape, scale_shape,
                 block_j: int = 512) -> bool:
    """Static tiling check — mirrors what the kernel assumes."""
    b, din = x_shape
    half, dout = packed_shape
    groups = scale_shape[0]
    if din != 2 * half or groups % 2 or scale_shape[1] != dout:
        return False
    if half % (groups // 2):
        return False
    block_k = half // (groups // 2)
    if block_k % 128 or dout % min(block_j, dout):
        return False
    return True

"""A Mamba-2 layer's decode step over the engine's state grid: one pass
that advances every slot's float32 state in place and reads ``y`` out of
the same tile.

The hybrid cache keeps every slot's state in one leaf, ``(L_mamba, SLOTS,
H, P, N)`` float32 (``serve.state_cache``): at Granite-4.0-H-Micro's widths
2 MiB a slot and layer, 4.8 GB at 64 slots. One token a slot applies

    S' = exp(dt·A) S + (dt·x) ⊗ B        y = S' C

per head (``models.hybrid.mamba_step``; the skip term ``D·x`` is added by
the caller). In XLA's form the update is an in-place dynamic-update-slice
fusion, and ``y`` a second fusion that reads the freshly written state
again: XLA does not fuse a reduction over the new state into a fusion
rooted in the update, so the state is read twice and written once. This
kernel reads it once:

- **In place.** The grid operand is the whole leaf; the layer rides in as a
  prefetched scalar and the ``BlockSpec`` index map puts it on the leading
  axis (as ``ops.moe_experts.bank_block`` does), and
  ``input_output_aliases`` hands the leaf back as the output, so no layer
  is sliced or copied and the other layers' blocks are never touched.
- **A grid step is one slot, every head** (2 MiB at Granite's widths).
  Each head's (P, N) tile is advanced and written to the output block;
  then the block, still in VMEM, is multiplied by C and summed over N.
  ``dt·x`` arrives (H, P), lanes along P, and is turned once a slot so
  that a head's ``dt·x`` is a column against the tile's rows. Measured
  alone on a TPU v5e (36 layers × 64 slots, one decode step): 15.18 ms,
  78% of the HBM rate and as fast as the update without ``y``; two or
  four slots a step 0.2-0.5% faster, ``y`` summed a head at a time 12%
  slower, XLA's two passes 21.68 ms.
- **Every slot is advanced**, as XLA's form does: a slot that holds no
  request is overwritten when the next one is spliced in.

Arithmetic in float32 throughout, as XLA's form: the same products and
sums, summed over N in another order (agreement to float32's rounding, not
bit for bit).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

# beside the double-buffered state blocks: dt·x, y, decay, B, C, Mosaic's own
VMEM_BESIDE_STATE = 16 * 2 ** 20


def ssm_state_supported(h: int, p: int, n: int) -> bool:
    """Whether the kernel tiles on the chip for a state of ``h`` heads of
    ``p`` × ``n``: a head's tile whole in sublanes and lanes, and its
    ``dt·x`` a whole tile to turn."""
    return h % 8 == 0 and p % 8 == 0 and n % 128 == 0


def ssm_state_step_auto(h: int, p: int, n: int) -> bool:
    """The choice ``serve.state_cache`` makes (as ``moe_experts_auto``): the
    kernel on the TPU backend for a state it tiles, XLA's form otherwise
    (the CPU tests keep their speed)."""
    return jax.default_backend() == "tpu" and ssm_state_supported(h, p, n)


def _kernel(layer_ref, s_ref, decay_ref, dtx_ref, b_ref, c_ref, y_ref,
            out_ref):
    del layer_ref                         # read by the index maps only
    dtx = dtx_ref[0].T                                      # (P, H)
    decay = decay_ref[0]                                    # (1, H)
    b = b_ref[0]                                            # (1, N)
    for h in range(s_ref.shape[2]):
        out_ref[0, 0, h] = (decay[:, h:h + 1] * s_ref[0, 0, h]
                            + dtx[:, h:h + 1] * b)          # (P, N)
    y_ref[0] = jnp.sum(out_ref[0, 0] * c_ref[0][None], axis=-1)


def ssm_state_step(ssm_grid: jax.Array, layer, decay: jax.Array,
                   dtx: jax.Array, b: jax.Array, c: jax.Array, *,
                   interpret: Optional[bool] = None):
    """ssm_grid (L, SLOTS, H, P, N) float32, the whole leaf, updated in
    place at ``layer`` (int or traced int32 scalar); decay (SLOTS, H) =
    exp(dt·A); dtx (SLOTS, H, P) = dt·x; b, c (SLOTS, N); all float32.
    Returns (y (SLOTS, H, P) float32 without the skip term, the grid)."""
    _, n_slots, h, p, n = ssm_grid.shape
    if interpret is None:
        interpret = interpret_default()
    state = pl.BlockSpec((1, 1, h, p, n),
                         lambda s, layer_ref: (layer_ref[0], s, 0, 0, 0))

    def rows(*shape):
        return pl.BlockSpec((1, *shape), lambda s, _: (s, 0, 0))

    y, ssm_grid = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_slots,),
            in_specs=[state, rows(1, h), rows(h, p), rows(1, n), rows(1, n)],
            out_specs=[rows(h, p), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_slots, h, p), jnp.float32),
                   jax.ShapeDtypeStruct(ssm_grid.shape, ssm_grid.dtype)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * h * p * n * 4 + VMEM_BESIDE_STATE),
        interpret=interpret,
        name="kt_ssm_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ssm_grid, decay[:, None],
      dtx, b[:, None], c[:, None])
    return y, ssm_grid

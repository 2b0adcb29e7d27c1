"""A prompt's latent attention over expanded heads, blockwise with an online
softmax: no score tile leaves the VMEM. Forward only; ``models.mla`` wraps it
in a ``custom_vjp`` whose backward is the XLA form's.

``models.mla.expanded_mix`` attends a prompt's T queries over its own T keys
with the heads expanded: a query is ``[q_nope (Dn) ; q_pe (Dr)]`` a head, a
key ``[k_nope (Dn) ; k_pe (Dr)]`` where ``k_pe`` is ONE rope key a token
shared by every head, and values of their own width Dv (GLM-5: 192 + 64 and
256; Kimi-VL: 128 + 64 and 128). As XLA einsums that is (heads, queries,
keys) float32 scores in HBM, written, masked, softmaxed and read back; at
8,192 rows and 64 heads the queries go 256 a tile (``mla.SCORE_TILE_BYTES``).
This kernel computes, for every head ``h`` and query ``t``,

    s[t, u] = scale · (q_nope[t, h]·k_nope[u, h] + q_pe[t, h]·k_pe[u])
    out[t, h] = Σ_u softmax_u(s[t, u] where allowed[t, u]) · v[u, h]

in the house style of ``ops.decode_attention`` / ``ops.moe_experts``:

- **The causal blocks only.** The grid's last axis walks a work list of the
  (query block, key block) pairs on or below the diagonal (``causal_pairs``,
  prefetched as two scalar arrays); the index maps read them, so a block
  wholly above the diagonal is neither fetched nor computed, and no grid
  step is spent on it. The pairs of a query block are consecutive, so its
  query tile and its output stay resident while its keys stream.
- **Two dots into one score tile.** ``k_pe`` (B, T, Dr) is an operand of
  its own, read by every head: it is not broadcast to the heads in HBM.
- **The selection as an operand.** ``allowed`` (B, T, T) int8, 1 where a
  query attends to a key (the indexer's choice, causality included), is
  read a block at a time beside the keys; without it, causality alone, by
  position, on the blocks that cross the diagonal.
- Softmax statistics and the accumulator in float32; the scores' dots take
  the operands in their own dtype with float32 accumulation, and the
  probabilities are cast to ``v``'s dtype before the product with the values
  (as the XLA form does). A query with no allowed key in a block adds
  nothing once a later block gives it one, which every query has (itself,
  or the ``index_topk`` keys it selected).

The output is written as (B, T, N·Dv), the layout the block's output
projection reads, a (query block, head) tile at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

NEG_INF = -1e30

# the query and key rows of one grid step (halved until they divide T). At
# GLM-5's widths (8,192 rows, 64 heads, the selection an operand) one call
# took 22.5 ms on a v5e at 1,024 x 1,024, 23.4 / 23.5 at 1,024 x 512 and
# 512 x 1,024, 24.5 at 512 x 512, 29.9 at 256 x 512 (PR 39): fewer grid
# steps, and each key block streamed fewer times. A step's float32 tiles
# are then ~12 MB, hence VMEM_LIMIT.
BLOCK_Q = 1024
BLOCK_K = 1024

# what a call may ask of a core's VMEM (a v5e has 128 MiB; Mosaic's default
# scoped limit is 16)
VMEM_LIMIT = 64 * 2 ** 20


def causal_pairs(t: int, block_q: int, block_k: int):
    """(query block, key block) int32 arrays of every pair with a key at or
    before one of the block's queries, in order of query block, then key."""
    pairs = [(i, j) for i in range(t // block_q)
             for j in range((i * block_q + block_q - 1) // block_k + 1)]
    return tuple(np.asarray(a, np.int32) for a in zip(*pairs))


def _kernel(qb_ref, kb_ref, qn_ref, qp_ref, kn_ref, kp_ref, v_ref, *rest,
            scale: float, block_q: int, block_k: int, selected: bool):
    a_ref, o_ref, acc_ref, m_ref, l_ref = (
        rest if selected else (None, *rest))
    p = pl.program_id(2)
    i, j = qb_ref[p], kb_ref[p]
    last = (i * block_q + block_q - 1) // block_k

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def dot_t(a, b):                                  # a · bᵀ, float32
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def update(s):
        m_prev = m_ref[:]                             # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        probs = jnp.exp(s - m_new)                    # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        v = v_ref[0, 0]
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    s = (dot_t(qn_ref[0, 0], kn_ref[0, 0])
         + dot_t(qp_ref[0, 0], kp_ref[0])) * scale    # (BQ, BK) float32
    if selected:
        update(jnp.where(a_ref[0].astype(jnp.int32) != 0, s, NEG_INF))
    else:
        crosses = j * block_k + block_k - 1 > i * block_q

        @pl.when(crosses)
        def _diagonal():
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            update(jnp.where(rows >= cols, s, NEG_INF))

        @pl.when(jnp.logical_not(crosses))
        def _below():
            update(s)

    @pl.when(j == last)
    def _write():
        o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def mla_flash_prefill(q_nope: jax.Array, q_pe: jax.Array, k_nope: jax.Array,
                      k_pe: jax.Array, v: jax.Array,
                      allowed: Optional[jax.Array] = None, *, scale: float,
                      block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                      interpret: Optional[bool] = None) -> jax.Array:
    """q_nope, k_nope (B, T, N, Dn); q_pe (B, T, N, Dr); k_pe (B, T, Dr),
    the rope key every head shares; v (B, T, N, Dv); ``allowed`` (B, T, T)
    int8, nonzero where a query attends to a key, or None for causal
    attention → (B, T, N·Dv) in v's dtype. On the chip T and Dv are
    multiples of 128 (``models.mla.flash_prefill_supported``)."""
    b, t, n, dn = q_nope.shape
    dr, dv = q_pe.shape[-1], v.shape[-1]
    if interpret is None:
        interpret = interpret_default()
    bq, bk = min(block_q, t), min(block_k, t)
    while t % bq:
        bq //= 2
    while t % bk:
        bk //= 2
    qb, kb = causal_pairs(t, bq, bk)

    def heads(x):                        # (B, T, N, D) → (B, N, T, D)
        return x.transpose(0, 2, 1, 3)

    def q_block(d):
        return pl.BlockSpec((1, 1, bq, d),
                            lambda b_, h, p, qb, kb: (b_, h, qb[p], 0))

    def k_block(d):
        return pl.BlockSpec((1, 1, bk, d),
                            lambda b_, h, p, qb, kb: (b_, h, kb[p], 0))

    in_specs = [q_block(dn), q_block(dr), k_block(dn),
                pl.BlockSpec((1, bk, dr),
                             lambda b_, h, p, qb, kb: (b_, kb[p], 0)),
                k_block(dv)]
    operands = [heads(q_nope), heads(q_pe), heads(k_nope), k_pe, heads(v)]
    if allowed is not None:
        in_specs.append(pl.BlockSpec(
            (1, bq, bk), lambda b_, h, p, qb, kb: (b_, qb[p], kb[p])))
        operands.append(allowed.astype(jnp.int8))
    return pl.pallas_call(
        partial(_kernel, scale=scale, block_q=bq, block_k=bk,
                selected=allowed is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n, len(qb)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, bq, dv), lambda b_, h, p, qb, kb: (b_, qb[p], h)),
            scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),   # acc
                            pltpu.VMEM((bq, 1), jnp.float32),    # m
                            pltpu.VMEM((bq, 1), jnp.float32)],   # l
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, n * dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="kt_mla_flash_prefill",
    )(jnp.asarray(qb), jnp.asarray(kb), *operands)

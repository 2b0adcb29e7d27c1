"""FlashAttention-2 for TPU in Pallas: blockwise causal attention with online
softmax, GQA-aware, custom VJP with a flash backward pass.

Why a kernel at all: XLA materializes the (S, S) logits tensor per head for
plain attention — at S=8k that is the HBM-bandwidth wall. The kernel streams
K/V blocks through VMEM with fp32 accumulators, never materializing logits.

Layout: heads are moved to the second dim — (B, N, S, Hd) — so each grid step
works on a (block, head_dim) tile that maps directly onto the MXU; the
(1, 1, BQ, BK) logits tile lives only in VMEM/registers. GQA is handled in
the BlockSpec index maps (q-head h reads kv-head h*NKV//N) so K/V are never
broadcast in HBM.

Causality is enforced at two levels: whole (q-block, k-block) tiles above the
diagonal are skipped via ``pl.when`` (half the FLOPs), and the diagonal tile
is masked elementwise. A ``window`` W (a sliding-window layer: query i sees
keys i − W < j ≤ i) adds the band's lower edge at both levels: key blocks
wholly below the band are skipped, and the forward's index maps hold such a
step on a block it fetches anyway, so no DMA moves it. Without a window the
kernels are what they were.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_default

NEG_INF = -1e30

# TPU memory tiles are (8, 128) for fp32: a per-row statistic like the LSE
# cannot be stored as a bare (..., S) array with (1, 1, block_q) blocks — the
# last two block dims must tile onto (8, 128). Per-row stats are therefore
# broadcast across a 128-lane trailing dim (same layout the stock XLA flash
# kernels use) and lane 0 is read back inside the kernels.
LANES = 128


def _block_live(qb, kj, block_q, block_k, causal, window):
    """Does the (query block ``qb``, key block ``kj``) tile hold any pair the
    masks leave? True where no mask says otherwise: above the diagonal
    (``causal``) or wholly below the band (``window``) it does not."""
    live = True
    if causal:
        # block above the diagonal ⇒ fully masked ⇒ skip
        live = qb * block_q + block_q - 1 >= kj * block_k
    if window is not None:
        # every key of the block is at least ``window`` behind every query
        below = kj * block_k + block_k - 1 > qb * block_q - window
        live = below if live is True else jnp.logical_and(live, below)
    return live


def _mask(s, qb, kj, block_q, block_k, causal, window):
    """The score tile with the pairs the masks drop at NEG_INF."""
    if not causal and window is None:
        return s
    rows = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if causal:
        s = jnp.where(rows >= cols, s, NEG_INF)
    if window is not None:
        s = jnp.where(cols > rows - window, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                scale: float, causal: bool, block_q: int, block_k: int,
                need_lse: bool, window: Optional[int] = None):
    lse_ref, acc_ref, m_ref, l_ref = rest if need_lse else (None, *rest)
    qi = pl.program_id(2)   # q-block index
    kj = pl.program_id(3)   # k-block index (innermost, sequential)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(qi, kj, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (BQ, Hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (BK, Hd)
        v = v_ref[0, 0].astype(jnp.float32)          # (BK, Hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask(s, qi, kj, block_q, block_k, causal, window)

        m_prev = m_ref[:]                             # (BQ, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)               # (BQ, 1)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)          # fully-masked rows → 0 out
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        if need_lse:
            lse = m_ref[:] + jnp.log(l_safe)          # (BQ, 1)
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret, need_lse=True,
         window=None):
    b, n, s, hd = q.shape
    nkv = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    grid = (b, n, s // block_q, s // block_k)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               need_lse=need_lse, window=window)

    def kv_block(i, j):
        """The key block a grid step fetches: its own, or, under a window,
        the nearest one the query block computes (a repeated index is no
        DMA), so a step the band skips moves nothing."""
        if window is None:
            return j
        lo = jnp.maximum(i * block_q - window + 1, 0) // block_k
        hi = ((i * block_q + block_q - 1) // block_k if causal
              else s // block_k - 1)
        return jnp.minimum(jnp.maximum(j, lo), hi)

    out_specs = [pl.BlockSpec((1, 1, block_q, hd), lambda b_, h, i, j: (b_, h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, n, s, hd), q.dtype)]
    if need_lse:
        # lse only exists to seed the backward pass; the no-grad path skips
        # writing it entirely (it is 128 lanes wide — see LANES)
        out_specs.append(pl.BlockSpec((1, 1, block_q, LANES),
                                      lambda b_, h, i, j: (b_, h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, n, s, LANES), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, i, j: (b_, h * nkv // n, kv_block(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, i, j: (b_, h * nkv // n, kv_block(i, j), 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),    # m
            pltpu.VMEM((block_q, 1), jnp.float32),    # l
        ],
        interpret=interpret,
        name="kt_flash_attention_fwd",
    )(q, k, v)
    return (res[0], res[1]) if need_lse else (res[0], None)


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 style, two passes)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, scale, causal, block_q, block_k,
                   window=None):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @pl.when(_block_live(qi, kj, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]                    # (BQ, 1), lane 0
        delta = delta_ref[0, 0][:, :1]                # (BQ, 1), lane 0
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask(s, qi, kj, block_q, block_k, causal, window)
        p = jnp.exp(s - lse)                           # (BQ, BK)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc_ref[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, scale, causal, block_q, block_k, nq_blocks,
                    window=None):
    kj = pl.program_id(2)
    qi = pl.program_id(3)   # innermost: folded (group-member × q-block) index
    nq = pl.num_programs(3)
    # Decode the real q-block: the folded axis runs q-blocks fastest within
    # each query head of the GQA group. Using the folded index directly for
    # causality would mis-mask every head after the first.
    qb = qi % nq_blocks

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    @pl.when(_block_live(qb, kj, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask(s, qb, kj, block_q, block_k, causal, window)
        p = jnp.exp(s - lse)                           # (BQ, BK)
        dv_acc_ref[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                  # (BQ, BK)
        dk_acc_ref[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, interpret, res, dout, window=None):
    q, k, v, out, lse = res
    b, n, s, hd = q.shape
    nkv = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)

    # delta = rowsum(dO * O) — the softmax-grad correction term. Both stats
    # are broadcast on the fly into the 128-lane layout (see LANES) here;
    # the residual itself is stored narrow.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, window=window),
        grid=(b, n, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, i, j: (b_, h * nkv // n, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, i, j: (b_, h * nkv // n, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    # dk/dv: one pass per (kv-head, k-block), iterating q blocks of every
    # query head in the group. Grid over q-heads with accumulation across the
    # group would race, so fold the group loop into the q-block axis instead:
    # treat the (group × q-blocks) product as the innermost axis.
    group = n // nkv
    nq_blocks = s // block_q

    def qhead(h, i):
        # i indexes group*nq_blocks: which q head within the group + q block
        return h * group + i // nq_blocks

    def qblock(i):
        return i % nq_blocks

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq_blocks=nq_blocks,
                          window=window),
        grid=(b, nkv, s // block_k, group * nq_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h, j, i: (b_, qhead(h, i), qblock(i), 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h, j, i: (b_, qhead(h, i), qblock(i), 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b_, h, j, i: (b_, qhead(h, i), qblock(i), 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b_, h, j, i: (b_, qhead(h, i), qblock(i), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h, j, i: (b_, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, window):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                  need_lse=False, window=window)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                    window=window)
    # keep only lane 0 as the residual — holding the full 128-lane stat from
    # forward to backward would be a 128x HBM blow-up per un-remat'd layer
    return out, (q, k, v, out, lse[..., 0])


def _flash_bwd(scale, causal, block_q, block_k, interpret, window, res, dout):
    return _bwd(scale, causal, block_q, block_k, interpret, res, dout,
                window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_supported(seq_len: int, n_heads: int, n_kv_heads: int) -> bool:
    """Static shape check — what the kernel's blocks assume: a sequence the
    128-row tile divides (the block search halves down from 512/1024 and
    must stop on a tile Mosaic accepts) and GQA groups that divide evenly.
    Callers decide kernel-or-reference from this, never from a caught
    compile error."""
    return (seq_len >= 128 and seq_len % 128 == 0
            and n_heads % n_kv_heads == 0)


def flash_auto(seq_len: int, n_heads: int, n_kv_heads: int) -> bool:
    """The ``auto`` choice every dispatcher makes: the kernel on the TPU
    backend for a shape it takes, the XLA reference otherwise."""
    return (jax.default_backend() == "tpu"
            and flash_supported(seq_len, n_heads, n_kv_heads))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Blockwise causal attention. q: (B, S, N, Hd); k, v: (B, S, NKV, Hd).
    ``window`` W: each query i sees only the keys i − W < j ≤ i (a
    sliding-window layer, W keys with itself); None: no lower bound.

    Returns (B, S, N, Hd). NKV must divide N (GQA). S must be divisible by
    the (clamped) block sizes; callers choose between this kernel and the
    XLA reference beforehand with :func:`flash_supported`. ``interpret=None``
    compiles on TPU and interprets on a CPU backend that was asked for
    (:func:`~.backend.interpret_default`).

    Default blocks: fewer grid steps and a longer K/V stream per tile
    amortize the revisit of the q tile. Shorter sequences clamp down.
    """
    b, s, n, hd = q.shape
    nkv = k.shape[2]
    assert n % nkv == 0, f"GQA requires n_kv | n_heads, got {nkv}, {n}"
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = interpret_default()

    # choose block sizes that divide S
    bq, bk = min(block_q, s), min(block_k, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2

    # head-major layout for the kernel
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, scale, causal, bq, bk, interpret, window)
    return out.transpose(0, 2, 1, 3)

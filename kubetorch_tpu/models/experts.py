"""The expert layer of fine-grained sigmoid-routed experts with no capacity:
shared by every family whose expert layers route this way (``models.mla``:
Kimi-VL-A3B and GLM-5; ``models.afmoe``: Trinity-Mini).

``sigmoid`` scores over all experts; the chosen are the top K of ``score +
router_bias`` (one group, so group-limited selection is the identity), the
weights are the UNBIASED scores of the chosen, normalised where the config
says so and scaled. No capacity, so no token is dropped and padding claims
nothing (:func:`moe_ffn_dropless`). One algorithm in two ranges of rows, by
the row count alone: up to ``DENSE_ROWS_MAX`` rows every row goes through
every expert that got a token, with a gate of zero where the row did not
choose it (on the TPU backend ONE kernel call that streams only the banks of
the experts that got a token: ``ops.moe_experts``; elsewhere plain einsums
over every bank); more rows are sorted by expert and each expert multiplies
its own run (on the TPU backend ONE kernel call over the sorted rows that
streams each hit expert's bank once: ``ops.moe_experts.moe_sorted``;
elsewhere XLA's grouped matmul, ``jax.lax.ragged_dot``). Shared experts are
one dense SwiGLU added beside them.

A layer may hold a SHARE of its experts (``held``): the router still scores
all ``n_experts``, the layer keeps banks for the held ones only and adds only
their products; a (token, choice) pair routed to an absent expert adds
nothing here.

What it reads of a config, by name: ``experts_per_token``,
``norm_topk_prob``, ``routed_scaling_factor``, ``held`` (None: all of them),
``held_first`` and ``n_held``. The routed experts' weights come as the run's
whole stacked ``banks`` with the layer's index in the run
(``block.with_banks``), and are read in place, never sliced a layer.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.moe_experts import (moe_experts, moe_experts_auto, moe_sorted,
                                moe_sorted_auto)
from .block import dense_ffn

# The routed experts are one algorithm in two ranges of rows (the tokens of
# one call), chosen from the row count alone. The readings are one layer's
# three products alone on a v5e, ms a layer, where the 64 banks of
# 2048 x 1408 stream in 1.35 (PERF.md section 5 has the tables).
#
# Up to DENSE_ROWS_MAX rows every row goes through every expert that got a
# token, gate zero where it was not chosen: the grouped kernel
# (``ops.moe_experts``) where it runs (``moe_experts_auto``: the TPU backend
# and experts it can tile), else plain einsums over every bank. The kernel
# streams only the hit banks, 1 - (1 - K/E)^m of them: 55 / 79 / 96 / 99.8%
# at 8 / 16 / 32 / 64 rows here. Einsums against the kernel at 8 /
# 16 / 32 / 64 / 128 / 256 / 512 rows: 1.50 / 0.83, 1.51 / 1.20, 1.51 /
# 1.46, 1.51 / 1.50, 1.79 / 1.51, 2.02 / 1.54, 3.62 / 2.98: the kernel is
# no slower at any, so no second threshold chooses between them.
#
# Beyond, the (row, choice) pairs sorted by expert go through the sorted
# kernel (``ops.moe_experts.moe_sorted``) where it runs (``moe_sorted_auto``:
# the TPU backend and experts it can tile), else XLA's grouped matmul. The
# whole sorted layer (sort, gather, products, back to rows) at 256 / 512 /
# 1,024 rows: the sorted kernel 1.91 / 2.10 / 2.53, the grouped matmul
# 5.80 / 6.06 / 6.77, where the grouped kernel takes 1.72 / 3.16 / (its
# rows do not fit the VMEM beside a bank). The sorted path would be the
# faster at 512 rows too; moving the threshold changes the chat cell's
# bucket-512 program, a change of its own. The cell runs both ranges: a
# decode step's 16 rows and the buckets 256 / 512, and the bucket 1,024.
DENSE_ROWS_MAX = 512

# A layer that holds a SHARE of its experts gets about held/n_experts of the
# (row, choice) pairs, and which is not known at trace time: beyond
# DENSE_ROWS_MAX rows the pairs sorted by held expert (the absent ones' last)
# go through the sorted path this many at a time, and a chunk past the
# last held pair is skipped, so time and temporaries follow the pairs that
# are there (8,192 rows x 8 choices, 16 of 256 held: 65,536 sorted pairs of
# which about 4,096 are held, one chunk).
HELD_PAIRS_CHUNK = 4096


def route(cfg, h: jax.Array, lw: Dict[str, Any]):
    """(weights (..., K) float32, chosen experts (..., K) int32). The bias
    decides WHICH experts; their weights are the unbiased scores."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @ lw["router"])
    _, idx = lax.top_k(scores + lw["router_bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


def _dense_experts(x: jax.Array, gates: jax.Array, whole, layer) -> jax.Array:
    """Every expert of the layer over every row, plain einsums that stream
    all the banks: Σ_e gates[m, e] · SwiGLU_e(x[m]). Also what the grouped
    kernel is held to, and what its backward pass differentiates."""
    one = {k: lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
           for k, v in whole.items()}
    act = (jax.nn.silu(jnp.einsum("md,edf->emf", x, one["w_gate"]))
           * jnp.einsum("md,edf->emf", x, one["w_up"]))
    ys = jnp.einsum("emf,efd->emd", act, one["w_down"])
    return jnp.einsum("me,emd->md", gates.astype(x.dtype), ys)


@jax.custom_vjp
def _grouped_experts(x, gates, whole, layer, sizes):
    """:func:`_dense_experts` over the experts with ``sizes > 0`` alone: one
    call of the kernel, which streams just their banks from the whole stacks
    (``ops.moe_experts``). Its gradient is the einsum form's."""
    return moe_experts(x, gates, whole["w_gate"], whole["w_up"],
                       whole["w_down"], layer, sizes)


def _grouped_fwd(x, gates, whole, layer, sizes):
    return _grouped_experts(x, gates, whole, layer, sizes), (
        x, gates, whole, layer)


def _grouped_bwd(saved, ct):
    x, gates, whole, layer = saved
    _, pull = jax.vjp(lambda *a: _dense_experts(*a, layer), x, gates, whole)
    return (*pull(ct), None, None)


_grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def _ragged_swiglu(xs, whole, layer, sizes):
    """Each sorted pair's SwiGLU product through XLA's grouped matmul, expert
    e's ``sizes[e]`` pairs in a run: the stacks as ``n_layers × E`` groups,
    the other layers' empty. Rows behind the last run come out zero. What
    the sorted kernel is held to, and what its backward pass
    differentiates."""
    n_layers, E = whole["w_gate"].shape[:2]
    bank = {k: v.reshape(n_layers * E, *v.shape[2:])
            for k, v in whole.items()}
    groups = lax.dynamic_update_slice(
        jnp.zeros((n_layers * E,), jnp.int32), sizes, (layer * E,))
    act = (jax.nn.silu(lax.ragged_dot(xs, bank["w_gate"], groups))
           * lax.ragged_dot(xs, bank["w_up"], groups))
    ys = lax.ragged_dot(act, bank["w_down"], groups)
    # rows behind the last run belong to no expert: whatever is there
    return jnp.where((jnp.arange(ys.shape[0]) < jnp.sum(sizes))[:, None],
                     ys, 0)


@jax.custom_vjp
def _sorted_swiglu(xs, whole, layer, sizes):
    """:func:`_ragged_swiglu` as one call of the sorted kernel, which reads
    the banks from the whole stacks (``ops.moe_experts.moe_sorted``). Its
    gradient is the grouped matmul's."""
    return moe_sorted(xs, whole["w_gate"], whole["w_up"], whole["w_down"],
                      layer, sizes)


def _sorted_swiglu_fwd(xs, whole, layer, sizes):
    return _sorted_swiglu(xs, whole, layer, sizes), (xs, whole, layer, sizes)


def _sorted_swiglu_bwd(saved, ct):
    xs, whole, layer, sizes = saved
    _, pull = jax.vjp(lambda *a: _ragged_swiglu(*a, layer, sizes), xs, whole)
    return (*pull(ct), None, None)


_sorted_swiglu.defvjp(_sorted_swiglu_fwd, _sorted_swiglu_bwd)


def _sorted_experts(cfg, x, w, idx, sizes, banks):
    """Σ_k w[m, k] · SwiGLU_{idx[m, k]}(x[m]) over the (row, choice) pairs
    sorted by expert, an expert its own run: one call of the sorted kernel
    where it runs (``moe_sorted_auto``: the TPU backend and experts it can
    tile), else XLA's grouped matmul."""
    E, K = cfg.n_held, cfg.experts_per_token
    whole, layer = banks
    m, d = x.shape
    order = jnp.argsort(idx.reshape(-1), stable=True)
    bank = whole["w_gate"]
    swiglu = (_sorted_swiglu if moe_sorted_auto(d, bank.shape[-1],
                                                bank.dtype.itemsize)
              else _ragged_swiglu)

    def run(sel, sizes):
        """A stretch ``sel`` of the sorted pairs, of which expert e has
        ``sizes[e]`` in a row from its start → each pair's product, zero
        behind the last run."""
        return swiglu(x[sel // K], whole, layer, sizes)

    if cfg.held is None:
        pairs = run(order, sizes)[jnp.argsort(order)].reshape(m, K, d)
        return jnp.einsum("mk,mkd->md", w.astype(x.dtype), pairs)
    # a share of the experts: the held pairs lead the order (an absent
    # expert's sort last), a chunk of them at a time, and a chunk past the
    # last held pair is skipped; each product goes back to its row
    chunk = math.gcd(HELD_PAIRS_CHUNK, m * K)
    ends = jnp.cumsum(sizes)
    wflat = w.reshape(-1)

    def one(out, first):
        def add(out):
            sel = lax.dynamic_slice(order, (first,), (chunk,))
            inside = jnp.clip(jnp.minimum(ends, first + chunk)
                              - jnp.maximum(ends - sizes, first), 0, chunk)
            ys = run(sel, inside) * wflat[sel][:, None].astype(x.dtype)
            return out.at[sel // K].add(ys.astype(out.dtype))
        return lax.cond(first < ends[-1], add, lambda out: out, out), None

    out, _ = lax.scan(one, jnp.zeros((m, d), jnp.float32),
                      jnp.arange(0, m * K, chunk))
    return out.astype(x.dtype)


def _routed_experts(cfg, x: jax.Array, w: jax.Array,
                    idx: jax.Array, sizes: jax.Array, banks) -> jax.Array:
    """Σ_k w[m, k] · SwiGLU_{idx[m, k]}(x[m]) for rows x (M, D) over the
    held experts; ``idx`` holds ``n_held`` for a pair that routes nowhere
    here (its ``w`` is 0); ``sizes`` (n_held,): the pairs an expert got.
    ``banks``: (the run's whole stacked banks {w_gate, w_up (L, E, D, F),
    w_down (L, E, F, D)}, this layer's index in it). One algorithm by the
    row count (and the experts' shape and the backend, for the kernel): see
    ``DENSE_ROWS_MAX``."""
    whole, layer = banks
    m, d = x.shape
    if m > DENSE_ROWS_MAX:
        return _sorted_experts(cfg, x, w, idx, sizes, banks)
    gates = jnp.einsum("mk,mke->me", w, jax.nn.one_hot(
        idx, cfg.n_held, dtype=w.dtype))
    bank = whole["w_gate"]
    if moe_experts_auto(d, bank.shape[-1], bank.dtype.itemsize):
        return _grouped_experts(x, gates, whole, layer, sizes)
    return _dense_experts(x, gates, whole, layer)


def moe_ffn_dropless(cfg, h: jax.Array, lw: Dict[str, Any],
                     token_mask: Optional[jax.Array] = None, banks=None):
    """The expert layer over h (B, T, D) → (out, tally): route, the routed
    experts' weighted outputs (:func:`_routed_experts`: static shapes, no
    capacity), and the shared SwiGLU beside them. ``token_mask`` (B, T)
    marks real tokens (live slots): the others route nowhere, claim nothing
    and count nothing. Where the layer holds a share of its experts
    (``cfg.held``) a pair routed to an absent one adds nothing and counts
    nothing either: the sum is this chip's part. ``banks``: (the run's whole
    stacked banks, this layer's index in the run) as a stack's scan hands
    them on (``block.with_banks``); without them the layer's own,
    ``lw["banks"]``. ``tally`` (2, n_held) int32: the routed pairs of real
    tokens a held expert got in this call, and whether it got any (what
    ``EngineStats`` accumulates)."""
    b, t, d = h.shape
    E = cfg.n_held
    if banks is None:
        banks = ({k: v[None] for k, v in lw["banks"].items()}, 0)
    x = h.reshape(b * t, d)
    with jax.named_scope("kt.moe.route"):
        w, idx = route(cfg, x, lw)                          # (M, K)
        here = None if token_mask is None else jnp.broadcast_to(
            token_mask.reshape(b * t)[:, None], idx.shape)
        if cfg.held is not None:
            idx = idx - cfg.held_first
            held = (idx >= 0) & (idx < E)
            here = held if here is None else here & held
        if here is not None:
            idx = jnp.where(here, idx, E)
            w = jnp.where(here, w, 0.0)
        sizes = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(E)[None, :],
                        axis=0, dtype=jnp.int32)
    with jax.named_scope("kt.moe.experts"):
        out = _routed_experts(cfg, x, w, idx, sizes, banks)
    with jax.named_scope("kt.moe.shared"):
        out = out + dense_ffn(x, lw["shared"])[0]
    return out.reshape(b, t, d), jnp.stack([sizes, (sizes > 0).astype(
        jnp.int32)])

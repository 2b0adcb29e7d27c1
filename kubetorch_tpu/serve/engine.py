"""Continuous-batching generation engine.

The scanned :func:`kubetorch_tpu.models.generate.generate` compiles one
program per (batch, prompt-length, new-token-count) and runs each batch to
completion — right for offline eval, wrong for serving, where requests
arrive whenever they like and a finished sequence must hand its chip share
to the next caller immediately.

TPU-first design — everything the chip executes has a static shape:

- **Slot grid.** The KV cache is one fixed ``(L, SLOTS, NKV, S_max, Hd)``
  buffer: head-major, the decode kernel's layout, so a decode block reads
  and writes it where it lies (never sliced, transposed or copied). A
  request occupies a slot for its lifetime; admission and retirement are
  host-side bookkeeping, never a recompile.
- **One decode step for the whole grid.** Every step decodes ALL slots in a
  single jitted call — per-slot absolute positions (a ``(SLOTS,)`` vector)
  drive RoPE and the causal mask, so slots at different depths batch into
  the same matmuls. Idle slots compute masked garbage; that cost is the
  price of never changing shape, and it is what keeps the MXU busy when
  the grid is full.
- **Bucketed prefill.** Prompts are right-padded to a small set of bucket
  lengths (one compile each) and run through the same layer math as
  ``generate``'s prefill (flash kernel on TPU when shapes allow); the
  resulting K/V rows (row-major, as ``generate`` makes them) are
  transposed and spliced into the slot with a donated
  ``dynamic_update_slice`` — no host round-trip, no cache copy.
- **Buffer donation everywhere.** The decode step and the slot-splice
  donate the cache, so HBM holds exactly one grid regardless of step rate.

Under an ambient mesh (``parallel.mesh_context.use_mesh``) the same jits
run GSPMD-partitioned: NKV shards over ``tensor``, slots over data axes —
multi-chip serving is the training sharding story, unchanged.

Reference parity note: the reference has no engine analog (it serves
user-written handlers; batching is the user's problem) — this subsystem is
a deliberate beyond-parity capability on the serving side, sized for the
RLHF rollout actors (BASELINE config 4) and autoscaled inference services.
"""

from __future__ import annotations

import itertools
import os
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry
from ..models.block import (decoder_block, layer_stacks, qkv_attend,
                            rmsnorm, with_banks)
from ..models.generate import (KVCache, _layer_step, cache_attend, ffn_block,
                               init_cache, rope_freqs)
from ..models.moe import moe_prefill_keep_capacity as _moe_keep_capacity
from ..models.quant import lm_head_dot

NEG_INF = -1e30

# Decode-attention dispatch, frozen at import like generate's flash flag
# (the gate runs at trace time inside jits whose cache key never sees env):
# "1" forces the Pallas flash-decode kernel on (interpret mode off-TPU —
# how tests cover the branch), "0" forces the masked einsum, "auto" uses
# the kernel on the TPU backend.
_DECODE_KERNEL_FLAG = os.environ.get("KT_DECODE_KERNEL", "auto")


def _decode_kernel_wanted() -> bool:
    if _DECODE_KERNEL_FLAG == "1":
        return True
    if _DECODE_KERNEL_FLAG == "0":
        return False
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# device side
# ---------------------------------------------------------------------------


def _cache_shardings(cache):
    """NamedSharding pytree for the grid cache under the ambient mesh, or
    None off-mesh: slots (axis 1) over the batch axes, heads (axis 2) over
    ``tensor``, the SEQUENCE dim (axis 3) over ``context`` (long-context
    serving: 1/C of the cache per chip). Without the explicit constraint
    GSPMD is free to replicate the scan-carried cache even though the
    attention shard_map consumes it sharded — correct, but forfeiting the
    memory split."""
    from ..parallel.mesh_context import current_mesh
    mesh = current_mesh()
    if mesh is None:
        return None
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import live_axes
    live = live_axes(mesh)
    if not live:
        return None
    from ..parallel.mesh import fit_batch_axes

    def leaf_sharding(x):
        # values (L, B, NKV, S, Hd); quant scales (L, B, NKV, S)
        ba = fit_batch_axes(live, x.shape[1])
        tp = "tensor" if ("tensor" in live
                          and x.shape[2] % live["tensor"] == 0) else None
        ctx = "context" if ("context" in live
                            and x.shape[3] % live["context"] == 0) else None
        spec = (P(None, ba, tp, ctx, None) if x.ndim == 5
                else P(None, ba, tp, ctx))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(leaf_sharding, cache)


def _constrain_cache(cache):
    """In-jit layout pin (trace-time ambient mesh, like the MoE gate)."""
    sh = _cache_shardings(cache)
    if sh is None:
        return cache
    return jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                  cache, sh)


# Axis order of the slot grid (int8 scales: the same less ``head_dim``). Part
# of ``aot_cache.AOTKey``: a persisted executable takes the grid it was
# compiled for.
GRID_LAYOUT = "layer,slot,kv_head,row,head_dim"


def init_grid_cache(cfg, slots: int, max_len: int) -> KVCache:
    """Zeroed slot grid in the decode kernel's layout, head-major
    (L, SLOTS, NKV, S_max, Hd): the last two axes are (row, dim), so the
    kernel's ``BlockSpec`` addresses a layer's tile inside the stacked grid
    and a decode block never slices, transposes or copies it."""
    shape = (cfg.n_layers, slots, cfg.n_kv_heads, max_len, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, cfg.dtype),
                   v=jnp.zeros(shape, cfg.dtype))


class _CacheOps(NamedTuple):
    """What a cache kind supplies to the engine's one prefill, one decode
    step and one cache manager: the grid, a prompt's row-major rows, and the
    block's mixing operation over each (``models.block.decoder_block``).
    Everything else — the in-place row writes, the splice, the sharding
    pin, the donated carry, the host loop — is the engine's, for every
    kind."""
    layout: str            # part of ``aot_cache.AOTKey``
    init_grid: Callable    # (cfg, slots, max_len) -> grid
    init_rows: Callable    # (cfg, batch, t) -> a prompt's rows, row-major
    rows_mix: Callable     # (cfg, layer_rows, q_pos, freqs_full, **kw)
    grid_mix: Callable     # (cfg, grid, layer, pos, freqs, live)


_KV_OPS = _CacheOps(
    GRID_LAYOUT, init_grid_cache, init_cache,
    lambda cfg, rows, q_pos, freqs_full, **kw: qkv_attend(
        cfg, freqs_full[q_pos], cache_attend(cfg, *rows, q_pos, **kw)),
    lambda cfg, grid, layer, pos, freqs, live: qkv_attend(
        cfg, freqs, grid_attend(cfg, grid, layer, pos)))


def _cache_ops(cfg) -> _CacheOps:
    """Per-head K/V rows unless the config names another cache kind
    (``models.mla.MlaMoeConfig.cache_kind``: latent rows, whose module is
    imported here and nowhere earlier)."""
    if getattr(cfg, "cache_kind", "kv") == "latent":
        from . import latent_cache as lc
        return _CacheOps(lc.grid_layout(cfg), lc.init_grid, lc.init_rows,
                         lc.rows_mix, lc.grid_mix)
    return _KV_OPS


def _write_rows(grid, layer, pos, rows):
    """Each slot's new row into the stacked grid, in place: grid
    (L, B, NKV, S, Hd) takes rows (B, NKV, Hd) at ``[layer, b, :, pos[b]]``
    (int8 scales (L, B, NKV, S) take (B, NKV)). One
    ``dynamic_update_slice`` per slot (B is static) compiles to an in-place
    chain; the gather-style ``grid.at[layer, arange(B), :, pos].set`` gets a
    row-major layout on the TPU and a relayout copy of the whole grid on
    each side of it. ``dynamic_update_slice`` clamps an out-of-range start
    (see :func:`_decode_block`)."""
    tail = (0,) * (grid.ndim - 4)
    for b in range(rows.shape[0]):
        grid = lax.dynamic_update_slice(
            grid, rows[b][None, None, :, None].astype(grid.dtype),
            (layer, b, 0, pos[b]) + tail)
    return grid


def _einsum_attention(q, leaves, pos, scale):
    """Masked-einsum attention of a window a slot over ONE layer of the grid
    (the reference math both Pallas kernels are bit-compatible with; what
    the CPU tests run, and the speculative window's attention). q
    (B, W, NH, Hd) at absolute positions ``pos`` (B, W); ``leaves``: (ck, cv)
    (B, NKV, S, Hd), or the int8 (kq, ks, vq, vs) with scales (B, NKV, S)
    folded in (logits columns ·ks, probs ·vs; all fp32). Returns
    (B, W, NH, Hd)."""
    quant = len(leaves) == 4
    ck, cv = (leaves[0], leaves[2]) if quant else leaves
    b, w, nh, hd = q.shape
    nkv, s = ck.shape[1], ck.shape[2]
    qg = q.reshape(b, w, nkv, nh // nkv, hd)
    if quant:
        qg, ck, cv = (a.astype(jnp.float32) for a in (qg, ck, cv))
    logits = jnp.einsum("bwkgh,bksh->bkgws", qg,
                        ck).astype(jnp.float32) * scale
    if quant:
        logits = logits * leaves[1][:, :, None, None, :]
    mask = jnp.arange(s)[None, None, :] <= pos[:, :, None]  # (B, W, S)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = (probs * leaves[3][:, :, None, None, :] if quant
             else probs.astype(cv.dtype))
    return jnp.einsum("bkgws,bksh->bwkgh", probs, cv).reshape(b, w, nh, hd)


def grid_attend(cfg, cache, layer, pos):
    """The block's attention operation over the slot grid: one new token a
    slot, against layer ``layer`` of the stacked grid, which is written and
    read where it lies.

    cache: the whole grid, ``KVCache`` (L, B, NKV, S, Hd) or the int8
    ``QuantKVCache`` (``kv_quant``: the new row is QUANTIZED before it is
    written and attention folds the row scales in instead of materializing
    fp rows); layer: traced int32; pos: (B,) absolute position of each
    slot's new token (also its cache row). Returns
    ``attend(q, k, v) -> (attn, cache')`` over q (B, 1, NH, Hd) and
    k, v (B, 1, NKV, Hd)."""
    from .kv_quant import QuantKVCache, grid_rows
    quant = isinstance(cache, QuantKVCache)
    scale = cfg.head_dim ** -0.5

    def attend(q, k, v):
        b, nh, nkv = q.shape[0], q.shape[2], k.shape[2]
        q1 = q[:, 0]
        with jax.named_scope("kt.cache_update"):
            grid = type(cache)(*(
                _write_rows(g, layer, pos, r)
                for g, r in zip(cache, grid_rows(cache, k[:, 0], v[:, 0]))))

        from ..parallel import kernel_shard
        from ..parallel import ring_attention as ring
        from ..parallel.mesh_context import current_mesh
        mesh = current_mesh()

        def layer_leaves():
            # a layer-sized read: the reference paths only, no cell runs them
            return tuple(lax.dynamic_index_in_dim(g, layer, 0, keepdims=False)
                         for g in grid)

        with jax.named_scope("kt.attention"):
            if mesh is not None and ring.sp_decode_supported(
                    mesh, b, grid[0].shape[3], nkv, nh):
                # long-context serving: the cache's sequence axis is sharded
                # over the context mesh axis; local attention + one
                # online-softmax combine beats the all-gather GSPMD would
                # otherwise insert (and the Pallas kernel, which needs all
                # rows on one chip). Trace-time gate like the MoE gather
                # (mesh fixed per engine — captured at construction and
                # re-installed on whichever thread traces); shapes that
                # don't divide the mesh fall back to the dense path. int8 ×
                # context sharding compose: 1/(2C) of the fp cache bytes per
                # chip.
                sp = (ring.sp_decode_attention_quant_sharded if quant
                      else ring.sp_decode_attention_sharded)
                attn = sp(q1, *layer_leaves(), pos, mesh, scale=scale)
            elif _decode_kernel_wanted():
                # fused flash-decode over the stacked grid itself: streams
                # K/V tiles, skips tiles past each slot's frontier entirely
                # (ops/decode_attention.py); under a mesh each device runs
                # it over its own slots and heads
                kernel = (kernel_shard.decode_attention_quant_sharded if quant
                          else kernel_shard.decode_attention_sharded)
                attn = kernel(q1, *grid, pos, layer, mesh, scale=scale)
            else:
                attn = _einsum_attention(q, layer_leaves(), pos[:, None],
                                         scale)
            return attn.astype(q.dtype), grid

    return attend


def _sample_slots(logits, key, temps, top_k: Optional[int], top_ps=None,
                  lp_logits=None, keys=None):
    """Per-slot sampling: temps (B,) — 0 means greedy for THAT slot;
    ``top_ps`` (B,) — nucleus mass per slot, 1.0 disables. Vectorized
    (traced arrays, not statics) so requests with different temperatures /
    top-p share one compiled step. ``top_ps=None`` (static) skips the
    full-vocab sort entirely — engines never pay for nucleus sampling
    until a request asks for it. ``keys`` (B, 2) uint32 draws each ROW
    from its own key (per-request seeded streams — decode path); ``key``
    drives the whole batch otherwise (prefill, spec drafts). Agrees with
    ``sample_logits`` slot-wise: argmax for temp 0,
    temperature/top-k/top-p categorical otherwise."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    if top_k is not None:
        kth = lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    if top_ps is not None:
        from ..models.generate import nucleus_mask
        scaled = nucleus_mask(scaled, top_ps)
    if keys is not None:
        sampled = jax.vmap(jax.random.categorical)(keys, scaled) \
            .astype(jnp.int32)
    else:
        sampled = jax.random.categorical(key, scaled,
                                         axis=-1).astype(jnp.int32)
    tok = jnp.where(temps > 0, sampled, greedy)
    # raw-model (temperature-independent) logprob of the chosen token —
    # the OpenAI ``logprobs`` number; one logsumexp against the matmuls.
    # ``lp_logits`` lets penalty-adjusted callers pass the PRE-penalty
    # logits here, keeping the score raw while the choice is steered.
    logp = jax.nn.log_softmax(logits if lp_logits is None else lp_logits,
                              axis=-1)
    lp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    return tok, lp


def _decode_step_impl(params, cache, pos, toks, rng, temps, cfg,
                      top_k: Optional[int] = None, banks=None, aidx=None,
                      lora_scale: float = 1.0, top_ps=None,
                      counts=None, fpen=None, ppen=None,
                      bias=None, bmask=None, skeys=None, live=None):
    """Single-step decode math shared by the jitted one-step
    :func:`_decode_step` and the scanned K-step :func:`_decode_block`.
    ``bias`` (SLOTS, V) + ``bmask`` (SLOTS,): per-slot OpenAI logit_bias,
    added before sampling for slots whose mask is 1 (stale rows from past
    occupants are neutralized by the mask, like the penalty multipliers).
    ``skeys`` (SLOTS, 2) uint32: per-slot sampling keys, folded with each
    slot's position — every request's sampled stream is a pure function
    of (its key, its positions), independent of neighbors, step batching,
    and the engine-wide chain (what makes per-request ``seed`` exact and
    block decode bit-equal to one-step even when sampling).
    ``live`` (SLOTS,) int32, given only where the engine keeps a routing
    tally: non-zero for the slots that hold a request (a column of the
    carry patch); the others' tokens claim no expert
    and count nothing, and the step's tallies come back stacked a layer, by
    the names of :func:`_tally_shapes`: the expert layers' routing tally
    and, where attention selects its rows, the rows scored and selected.
    Always returns the 5-tuple (cache', next_tok, logprobs, counts',
    tallied) — ``counts'`` is None when ``counts`` is, ``tallied`` when
    ``live`` is."""
    s_max = cache[0].shape[3]
    ops = _cache_ops(cfg)
    x = params["embed"][toks[:, None]].astype(cfg.dtype)   # (B, 1, D)
    freqs = rope_freqs(cfg, s_max)[pos][:, None]            # (B, 1, Hd/2)

    from ..models.lora import gather_slot_adapters

    # the grid rides in the CARRY, the layer index beside the weights:
    # scanned as ``xs``/``ys``, XLA builds a second grid every step and
    # slices every layer out of one and writes it back into the other
    selects = "dsa" in _tally_shapes(cfg)

    def body(whole, start, carry, layer):
        h, grid = carry
        lw, l, bank_l = layer
        # per-slot adapters gathered to (B, D, R)/(B, R, O) per target
        # (multi-LoRA serving — see ``GenerationEngine`` docs)
        lora = gather_slot_adapters(bank_l, aidx, lora_scale, banks)
        h, grid, aux = decoder_block(
            cfg, h, lw, ops.grid_mix(cfg, grid, l, pos, freqs, live),
            with_banks(ffn, whole, l - start), lora=lora)
        # a mix that selects its rows hands its counts out beside the grid
        grid, seen = grid if selects else (grid, None)
        return (h, grid), (None if live is None else (aux, seen))

    ffn = (partial(ffn_block, cfg) if live is None
           else partial(ffn_block, cfg, token_mask=(live != 0)[:, None]))
    carry, routed, seen = (x, cache), None, []
    for stack, whole, start, n in layer_stacks(cfg, params):
        # adapter banks are stacked over one run of layers
        carry, out = lax.scan(
            partial(body, whole, start), carry,
            (stack, jnp.arange(start, start + n), banks or {}))
        if out is not None:
            routed = routed if out[0] is None else out[0]   # the one run
            seen.append(out[1])                             # every run
    tallied = None
    if live is not None:
        tallied = {} if routed is None else {"moe": routed}
        if selects:
            tallied["dsa"] = jnp.concatenate(seen, axis=0)
    x, new_cache = carry
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head_dot(x[:, 0], params, cfg.dtype)
    raw_logits = logits
    if counts is not None:
        # OpenAI-style repetition control: subtract per-token penalties
        # derived from each slot's seen-token counts (prompt + generated)
        # BEFORE sampling — greedy slots with zero penalties see logits
        # unchanged, so isolation holds bit-exactly. Reported logprobs
        # stay RAW-model (penalties steer the choice, not the score).
        logits = logits - (fpen[:, None] * counts.astype(jnp.float32)
                           + ppen[:, None] * (counts > 0))
    if bias is not None:
        logits = logits + bias * bmask[:, None]
    step_keys = (jax.vmap(jax.random.fold_in)(skeys, pos)
                 if skeys is not None else None)
    nxt, lps = _sample_slots(logits, rng, temps, top_k, top_ps,
                             lp_logits=raw_logits, keys=step_keys)
    if counts is not None:
        counts = counts.at[jnp.arange(counts.shape[0]), nxt].add(1)
    return _constrain_cache(new_cache), nxt, lps, counts, tallied


def _tally_shapes(cfg) -> Dict[str, tuple]:
    """What a decode step tallies on the device for this config, by name:
    ``moe``, the expert layers' routing tally (L_moe, 2, E) — the routed
    (token, choice) pairs of the live slots an expert got, and the steps in
    which it got any — and ``dsa``, (L, 2): the rows a sparse-attention
    layer scored and selected for the live slots. A family says which it
    keeps (``routed_tally_shape`` / ``dsa_tally_shape``)."""
    shapes = {"moe": getattr(cfg, "routed_tally_shape", None),
              "dsa": getattr(cfg, "dsa_tally_shape", None)}
    return {k: v for k, v in shapes.items() if v is not None}


# ``dsa`` counts rows, 100,000 a step and layer at 16 slots of 8k context:
# an int32 would wrap within minutes, so the running sum is kept as two
# int32 words, [..., 0] counting 2**_DSA_WORD of [..., 1]'s units (a step
# adds at most SLOTS x S_max to the low word before it carries)
_DSA_WORD = 24


def _init_tally(cfg) -> Optional[Dict[str, Any]]:
    shapes = _tally_shapes(cfg)
    if "dsa" in shapes:
        shapes["dsa"] += (2,)
    return {k: jnp.zeros(v, jnp.int32) for k, v in shapes.items()} or None


def _tally_add(tally, step):
    """The running tally plus one step's."""
    out = dict(tally)
    if "moe" in tally:
        out["moe"] = tally["moe"] + step["moe"]
    if "dsa" in tally:
        low = tally["dsa"][..., 1] + step["dsa"]
        out["dsa"] = jnp.stack([tally["dsa"][..., 0] + (low >> _DSA_WORD),
                                low & ((1 << _DSA_WORD) - 1)], axis=-1)
    return out


@partial(jax.jit, static_argnames=("cfg", "top_k", "lora_scale"),
         donate_argnums=(1,), donate_argnames=("counts",))
def _decode_step(params, cache, pos, toks, rng, temps, cfg,
                 top_k: Optional[int] = None, banks=None, aidx=None,
                 lora_scale: float = 1.0, top_ps=None,
                 counts=None, fpen=None, ppen=None,
                 bias=None, bmask=None, skeys=None, tally=None, live=None):
    """Advance EVERY slot one token. toks (B,) is each slot's current input
    token; pos (B,) its absolute position; temps (B,) its sampling
    temperature. ``banks`` (target → (A (L,N,D,R), B (L,N,R,O))) + ``aidx``
    (B,) select each slot's LoRA adapter (index 0 = the zero adapter =
    base model). ``cache`` is the head-major grid, a ``KVCache`` or an int8
    ``QuantKVCache`` (``kv_quant``) — the pytree structure keys the jit, so
    each engine compiles exactly one of the bodies. Returns
    (cache', next_tok, logprobs), then ``counts'`` and ``tally'`` (the
    running tally of :func:`_tally_shapes`; ``live`` comes with it) where
    they were given."""
    cache, nxt, lps, counts, tallied = _decode_step_impl(
        params, cache, pos, toks, rng, temps, cfg, top_k=top_k, banks=banks,
        aidx=aidx, lora_scale=lora_scale, top_ps=top_ps, counts=counts,
        fpen=fpen, ppen=ppen, bias=bias, bmask=bmask, skeys=skeys, live=live)
    out = (cache, nxt, lps)
    if counts is not None:
        out += (counts,)
    if tally is not None:
        out += (_tally_add(tally, tallied),)
    return out


@partial(jax.jit, static_argnames=("cfg", "top_k", "lora_scale", "n_steps"),
         donate_argnums=(1,), donate_argnames=("counts",))
def _decode_block(params, cache, pos, toks, rng, temps, cfg, n_steps: int,
                  top_k: Optional[int] = None, banks=None, aidx=None,
                  lora_scale: float = 1.0, top_ps=None,
                  counts=None, fpen=None, ppen=None,
                  bias=None, bmask=None, skeys=None, tally=None, live=None):
    """Advance every slot ``n_steps`` tokens in ONE dispatch: a ``lax.scan``
    over :func:`_decode_step_impl`, so the host pays the dispatch/sync
    overhead once per block instead of once per token — the difference
    between ~dispatch-bound and ~HBM-bound serving decode.

    The grid (L, SLOTS, NKV, S_max, Hd) rides in the carry of this scan and
    of the layer scan inside it: rows are written in place and the kernel
    reads the stacked grid directly, so no step slices, transposes or
    copies it (tests/test_decode_block_compiles.py holds the compiled
    program to that).

    The engine may queue this block's successor, from the final ``pos`` /
    ``tok`` returned here, before it has fetched this block's tokens
    (``GenerationEngine._may_run_ahead``); "between batches" is then the
    point where every dispatched block has been fetched, not every gap
    between two blocks on the device.

    A slot that retires mid-block (eos/stop/budget) keeps computing garbage
    for the rest of the block, and for the whole of a successor already in
    flight; the host discards those tokens at emit time.
    Its overshoot cache writes at positions ≥ S_max are CLAMPED
    (``dynamic_update_slice`` clips an out-of-range start): they rewrite
    the slot's own row S_max−1, after the slot's last kept token was
    computed (a request's budget ends at or before that row). Rows past a
    retired frontier are never attended before being rewritten, and the
    next occupant writes every row before it attends it — so the garbage
    is unobservable. ``tally``, where the engine keeps one
    (:func:`_tally_shapes`): what the ``live`` slots' tokens did, accumulated
    in this scan's carry and read back only when someone reads ``stats()``.
    Returns
    (cache', final_pos, final_tok, toks (K, B), logprobs (K, B), counts'),
    and ``tally'`` behind them where one was given."""

    def step_fn(carry, k):
        cache, pos, toks, counts, tally = carry
        key = jax.random.fold_in(rng, k)
        cache, nxt, lps, counts, tallied = _decode_step_impl(
            params, cache, pos, toks, key, temps, cfg, top_k=top_k,
            banks=banks, aidx=aidx, lora_scale=lora_scale, top_ps=top_ps,
            counts=counts, fpen=fpen, ppen=ppen, bias=bias, bmask=bmask,
            skeys=skeys, live=live)
        if tally is not None:
            tally = _tally_add(tally, tallied)
        return (cache, pos + 1, nxt, counts, tally), (nxt, lps)

    (cache, pos, toks, counts, tally), (toks_k, lps_k) = lax.scan(
        step_fn, (cache, pos, toks, counts, tally), jnp.arange(n_steps))
    out = (cache, pos, toks, toks_k, lps_k, counts)
    return out if tally is None else out + (tally,)


@partial(jax.jit, static_argnames=("cfg", "top_k", "lora_scale"))
def _prefill(params, tokens, true_len, rng, temps, cfg,
             top_k: Optional[int] = None, adapter=None,
             lora_scale: float = 1.0, top_ps=None, pen_row=None):
    """Prompt pass at one bucket length. tokens (1, T_bucket) right-padded;
    logits are taken at the REAL last position ``true_len - 1`` (padding
    rows only pollute their own cache rows, which decode overwrites before
    ever attending to them). Returns (first_token (1,), k, v, logprobs)
    with k/v the prompt's rows, row-major (L, 1, T_bucket, NKV, Hd); a
    latent cache's rows come as ``k`` (L, 1, T_bucket, 1, C) and ``v`` is
    None."""
    b, t = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    freqs_full = rope_freqs(cfg, t)
    q_pos = jnp.arange(t)
    from ..models.generate import _flash_prefill_wanted
    flash = _flash_prefill_wanted(cfg, t)
    ops = _cache_ops(cfg)
    rows = tuple(ops.init_rows(cfg, b, t))
    # Padding must not perturb MoE routing: masked tokens never claim a
    # capacity slot, and the overflow-drop threshold is the REAL length's
    # capacity (the static buffer stays bucket-sized) — so a bucketed
    # prompt routes bit-identically to its unpadded solo run.
    token_mask = (q_pos < true_len)[None, :]
    keep_capacity = _moe_keep_capacity(cfg, true_len)

    ffn = partial(ffn_block, cfg, token_mask=token_mask,
                  keep_capacity=keep_capacity)

    def body(whole, carry, layer):
        lw, l, rows_l, ad_l = layer
        lora = (ad_l, lora_scale) if adapter else None
        h, rows_l, _ = decoder_block(
            cfg, carry, lw,
            ops.rows_mix(cfg, rows_l, q_pos, freqs_full, flash_prefill=flash,
                         causal_prefill=True),
            with_banks(ffn, whole, l), lora=lora)
        return h, tuple(rows_l)

    # a run of layers a scan (adapters are stacked over one run only)
    done = []
    for stack, whole, start, n in layer_stacks(cfg, params):
        x, out = lax.scan(partial(body, whole), x, (
            stack, jnp.arange(n), tuple(r[start:start + n] for r in rows),
            adapter or {}))
        done.append(out)
    rows = (done[0] if len(done) == 1
            else tuple(jnp.concatenate(r, axis=0) for r in zip(*done)))
    nk, nv = (*rows, None)[:2]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    h_last = x[jnp.arange(b), true_len - 1]                 # (1, D)
    logits = lm_head_dot(h_last, params, cfg.dtype)
    raw_logits = logits
    if pen_row is not None:
        logits = logits - pen_row[None, :]
    first, lps = _sample_slots(logits, rng, temps, top_k, top_ps,
                               lp_logits=raw_logits)
    return first, nk, nv, lps




@partial(jax.jit, static_argnames=("cfg", "top_k", "lora_scale"))
def _prefill_suffix(params, tokens, true_len, prefix_k, prefix_v, prefix_len,
                    rng, temps, cfg, top_k: Optional[int] = None,
                    adapter=None, lora_scale: float = 1.0, top_ps=None,
                    pen_row=None):
    """Suffix prompt pass behind a cached prefix: tokens (1, T_bucket)
    right-padded run at absolute positions ``prefix_len + i`` attending the
    prefix's REAL K/V rows plus themselves. The prefix stays padded to its
    BUCKET (``prefix_k``: (L, 1, P_bucket, NKV, Hd); ``prefix_len`` is the
    traced true length), so compiles are bounded by bucket pairs, never by
    distinct prefix lengths. Suffix rows are written starting at
    ``prefix_len`` — over the prefix's padding garbage — and the causal
    mask (kv_pos <= q_pos) never admits an unwritten row. Returns
    (first_token, k, v) with k/v covering rows [0, P_bucket + T_bucket),
    ready to splice into a slot.

    Exact for dense models (same math as a from-zero prefill of
    prefix+suffix). For MoE, expert capacity is per SEGMENT (the prefix
    routed at registration, the suffix here), so overflow-drop pressure can
    differ from a solo full-prompt run — the standard prefix-cache trade;
    identical whenever no expert overflows."""
    b, t = tokens.shape
    p_bucket = prefix_k.shape[2]
    x = params["embed"][tokens].astype(cfg.dtype)
    freqs_full = rope_freqs(cfg, p_bucket + t)
    q_pos = prefix_len + jnp.arange(t)
    token_mask = (jnp.arange(t) < true_len)[None, :]
    keep_capacity = _moe_keep_capacity(cfg, true_len)
    pad = jnp.zeros((prefix_k.shape[0], b, t) + prefix_k.shape[3:],
                    prefix_k.dtype)
    ck0 = jnp.concatenate([prefix_k, pad], axis=2)
    cv0 = jnp.concatenate([prefix_v, pad], axis=2)

    def body(carry, layer):
        lw, ck, cv, ad_l = layer
        lora = (ad_l, lora_scale) if adapter else None
        h, ck, cv = _layer_step(cfg, carry, lw, ck, cv, q_pos, freqs_full,
                                flash_prefill=False, token_mask=token_mask,
                                keep_capacity=keep_capacity, lora=lora)
        return h, (ck, cv)

    x, (nk, nv) = lax.scan(body, x, (params["layers"], ck0, cv0,
                                     adapter or {}))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    h_last = x[jnp.arange(b), true_len - 1]
    logits = lm_head_dot(h_last, params, cfg.dtype)
    raw_logits = logits
    if pen_row is not None:
        logits = logits - pen_row[None, :]
    first, lps = _sample_slots(logits, rng, temps, top_k, top_ps,
                               lp_logits=raw_logits)
    return first, nk, nv, lps


@partial(jax.jit, donate_argnums=(0,))
def _set_counts_row(counts, slot, row, first=None):
    """Seed one slot's seen-token counts at admission (prompt + prefix +
    first sampled token); stale rows from prior occupants never matter —
    zero-penalty slots multiply them by 0. ``first`` (1,) is the prefill's
    first token, counted here on the device: the host has not seen it yet
    when the row is set."""
    if first is not None:
        row = row.at[first[0]].add(1)
    return counts.at[slot].set(row)


# The decode carry: ``pos`` and ``tok``, a block's own outputs and its
# successor's inputs, stay on the device, and so do what a prefill leaves
# there (its first token, the slot's sampling key). The host sends ONE packed
# patch a block (uint32 bit patterns, a row a slot): seated or retired since
# the last block?, ``tok`` from the prefill's first token?, the host's ``pos``
# and ``tok`` for such a slot, and the per-slot vectors that only the host
# ever changes, a column each in this order (``live``: does the slot hold a
# request; read by the expert layers' routing tally alone).
_PATCH_VECTORS = (("temps", jnp.float32), ("aidx", jnp.int32),
                  ("top_ps", jnp.float32), ("fpen", jnp.float32),
                  ("ppen", jnp.float32), ("bmask", jnp.float32),
                  ("live", jnp.int32))
_PATCH_WIDTH = 4 + len(_PATCH_VECTORS)


@jax.jit
def _seat_first(firsts, skeys, slot, first, key):
    """What an admission leaves on the device for the block that follows it:
    the prefill's first token, (1,), into the (SLOTS,) vector the next
    carry patch reads, and the slot's sampling key into (SLOTS, 2). The
    host has seen neither when that block is dispatched, and never reads
    the key."""
    return firsts.at[slot].set(first[0]), skeys.at[slot].set(key)


@jax.jit
def _patch_carry(pos, tok, firsts, skeys, patch):
    """A block's inputs from its predecessor's final ``pos`` / ``tok``:
    kept where the slot decodes on, replaced by the host's values where it
    was seated or retired since (a newly seated slot's ``tok`` taken from
    ``firsts``), beside the per-slot vectors unpacked from ``patch``
    (SLOTS, _PATCH_WIDTH) and ``skeys`` as it came. EVERY block's inputs
    come out of this function, an empty patch included, so
    ``_decode_block`` sees one input signature whether or not a slot
    changed."""
    def column(col, dtype):
        return lax.bitcast_convert_type(patch[:, col], dtype)

    changed, from_first = patch[:, 0] != 0, patch[:, 1] != 0
    out = {"pos": jnp.where(changed, column(2, jnp.int32), pos),
           "tok": jnp.where(changed, jnp.where(from_first, firsts,
                                               column(3, jnp.int32)), tok),
           "skeys": skeys}
    for col, (name, dtype) in enumerate(_PATCH_VECTORS, start=4):
        out[name] = column(col, dtype)
    return out


@partial(jax.jit, donate_argnums=(0,))
def _splice_slot(cache, slot, k_new, v_new):
    """Write a prefill's K/V rows into one slot of the grid cache, donated
    (no second grid-sized buffer ever exists). The one crossing between the
    two layouts: k/v_new arrive row-major (L, 1, T_b, NKV, Hd) in the model
    dtype, as ``_prefill`` / the prefix store hold them, and are transposed
    to the grid's head-major (L, 1, NKV, T_b, Hd) as they are written (the
    ≤ bucket-width new rows, not the grid). For an int8 ``QuantKVCache``
    grid the rows quantize HERE — prefill itself always runs full-precision
    math."""
    from .kv_quant import grid_rows
    return _constrain_cache(type(cache)(*(
        lax.dynamic_update_slice(
            g, jnp.swapaxes(r, 2, 3).astype(g.dtype),
            (0, slot) + (0,) * (g.ndim - 2))
        for g, r in zip(cache, grid_rows(cache, k_new, v_new)))))


# ---------------------------------------------------------------------------
# host side
# ---------------------------------------------------------------------------


def _normalize_stop(stop) -> tuple:
    """One token-id sequence or a list of them → tuple of non-empty int
    tuples. An int-leading sequence is ONE stop sequence, not a list."""
    if stop is None or len(stop) == 0:
        return ()
    # scalar-leading (python or numpy int) → ONE sequence; else a list of
    # sequences (tokenizer pipelines hand numpy ids, not python ints)
    seqs = [stop] if not hasattr(stop[0], "__len__") else list(stop)
    if any(len(q) == 0 for q in seqs):
        raise ValueError("empty stop sequence")
    return tuple(tuple(int(t) for t in q) for q in seqs)


@dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    temperature: Optional[float] = None      # None → engine default
    top_p: Optional[float] = None            # None → engine default
    frequency_penalty: float = 0.0           # OpenAI-style repetition ctl
    presence_penalty: float = 0.0
    logit_bias: Optional[Dict[int, float]] = None  # token id → additive bias
    seed: Optional[int] = None               # reproducible sampling stream
    stop: tuple = ()                         # stop token-id sequences
    prefix_id: Optional[int] = None          # cached shared-prefix K/V
    full_prompt: Optional[List[int]] = None  # pre-strip prompt (auto match)
    adapter_id: Optional[int] = None         # registered LoRA adapter
    cancelled: bool = False                  # reaped at the next step
    error: Optional[BaseException] = None    # admission failure, surfaced
    out: "queue.Queue[Optional[int]]" = field(default_factory=queue.Queue)
    tail: list = field(default_factory=list)  # last max(len(stop)) tokens
    logprobs: list = field(default_factory=list)  # raw-model lp per token
    generated: int = 0
    submitted_at: float = field(default_factory=time.monotonic)
    admitted_at: Optional[float] = None      # popped from the queue
    first_token_at: Optional[float] = None
    # the engine's phase clock when the request was seated, and, once it has
    # retired or been cancelled, its life (GenerationEngine._close_life)
    seated: Optional[Dict[str, float]] = None
    life: Optional[Dict[str, Any]] = None
    # decode blocks it emitted from, and how many of them were dispatched
    # one block ahead (before their predecessor had been fetched)
    blocks: int = 0
    blocks_ahead: int = 0


@dataclass
class _Flight:
    """One dispatched decode block whose tokens the host has not fetched:
    the request each slot held at dispatch (tokens are emitted only where
    the slot still holds it), the block's (K, SLOTS) tokens and
    log-probabilities on the device, and whether it was dispatched ahead."""
    reqs: List[Optional[_Request]]
    toks: Any
    lps: Any
    ahead: bool


class RequestHandle:
    """Streaming view of one request: iterate tokens as they decode, or
    block for the full completion. Tokens drained from the queue are kept on
    the handle, so a ``result()`` that times out loses nothing — a retry
    (or a later iteration) sees the full stream from the start. Single
    consumer: share the handle's results, not the handle, across threads."""

    def __init__(self, req: _Request, engine: "GenerationEngine" = None):
        self._req = req
        self._engine = engine
        self._collected: List[int] = []
        self._done = False
        self._reported = False

    @property
    def request_id(self) -> int:
        return self._req.rid

    @property
    def logprobs(self):
        """Raw-model (temperature-independent) logprob per DRAINED token,
        aligned with the tokens this handle has yielded so far (the full
        completion after ``result()``). Entries are None on paths that
        don't compute them (speculative verify)."""
        return list(self._req.logprobs[:len(self._collected)])

    def cancel(self) -> bool:
        """Abandon this request (``GenerationEngine.cancel``): the stream
        ends cleanly with whatever tokens already decoded."""
        return (self._engine.cancel(self._req.rid)
                if self._engine is not None else False)

    def _pull(self, timeout: Optional[float]) -> bool:
        """Move one queue item into ``_collected``; False once finished.
        ``timeout=0`` means the item must already be queued."""
        if self._done:
            return False
        try:
            tok = (self._req.out.get_nowait() if timeout is not None
                   and timeout <= 0 else self._req.out.get(timeout=timeout))
        except queue.Empty:
            raise TimeoutError(
                f"request {self._req.rid} still decoding") from None
        if tok is None:
            self._done = True
            if self._req.error is not None:
                raise self._req.error
            return False
        self._collected.append(tok)
        return True

    def __iter__(self):
        i = 0
        while True:
            while i < len(self._collected):
                yield self._collected[i]
                i += 1
            if not self._pull(None):
                return

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """All generated tokens (prompt excluded), blocking to completion.
        ``timeout=0`` requires the request to already be complete."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._done:
            left = (None if deadline is None
                    else deadline - time.monotonic())
            self._pull(left)
        if self._req.error is not None:
            raise self._req.error
        if not self._reported and self._req.life is not None:
            # once, on the caller's current span (the rank's worker.execute
            # when the engine serves as a kt.cls): the pod hands it back to
            # the client in X-KT-Timing. No span current, or KT_TRACE=0:
            # nothing happens.
            self._reported = True
            if telemetry.current_span() is not None:
                telemetry.add_event("engine.request", **self._req.life)
        return list(self._collected)

    def time_to_first_token(self) -> Optional[float]:
        if self._req.first_token_at is None:
            return None
        return self._req.first_token_at - self._req.submitted_at

    def timeline(self) -> Optional[Dict[str, Any]]:
        """The request's life as the engine saw it, once it has finished or
        been cancelled (None before): ``queue_s`` (submitted to popped from
        the queue), ``prefill_s`` (popped to first token), ``decode_s``
        (first token to retirement), ``blocks`` (decode blocks it sat in),
        and the stepping thread's phase seconds while it was seated:
        ``host_s`` / ``wait_s`` and one ``host.<phase>_s`` /
        ``wait.<phase>_s`` each (``telemetry.engine_metrics``). All on the
        monotonic clock."""
        life = self._req.life
        return None if life is None else dict(life)


@dataclass
class EngineStats:
    slots: int
    active: int
    queued: int
    admitted_total: int
    finished_total: int
    tokens_generated: int
    decode_steps: int
    tokens_per_sec: float
    # rolling mean time-to-first-token over the last admissions (secs);
    # 0.0 until anything has admitted
    ttft_avg: float = 0.0
    # decode blocks dispatched before their predecessor's tokens had been
    # fetched (of decode_steps / decode_block blocks in all)
    blocks_run_ahead: int = 0
    # expert layers only (None elsewhere), (L_moe, E) int64 each: the routed
    # (token, choice) pairs of live slots an expert got in decode steps, and
    # the decode steps in which it got any. Accumulated on the device;
    # fetched when this is read at a batch boundary (``at_batch_boundary``),
    # else the last reading
    moe_routed_pairs: Any = None
    moe_expert_hits: Any = None
    # layers whose attention selects its rows (a sparse-attention indexer;
    # None elsewhere), (L,) int64 each: the cached rows the live slots'
    # decode steps scored (every row up to the frontier) and the rows they
    # then attended to. Accumulated and fetched like the routing tally
    dsa_rows_scored: Any = None
    dsa_rows_selected: Any = None


class GenerationEngine:
    """Continuous-batching decode over a fixed slot grid (module docstring
    has the design). Drive it manually with :meth:`step` (deterministic —
    how the tests use it) or start the background loop with :meth:`start`.

    ``params``/``cfg`` are any decoder family ``models.generate`` handles:
    Llama-dense or MoE (a ``router`` leaf switches the FFN). ``eos_id``
    retires a slot early; ``max_len`` caps prompt+completion per request.
    """

    def __init__(self, params: Dict[str, Any], cfg, *, slots: int = 8,
                 max_len: int = 1024, eos_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 prefill_buckets: Sequence[int] = (128, 256, 512, 1024),
                 quantize_kv: bool = False, seed: int = 0,
                 decode_block: int = 1, auto_prefix: bool = False,
                 prefill_chunk: Optional[int] = None, aot_cache=None):
        self.params = params
        self.cfg = cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = top_k
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        self.top_p = None if top_p is None else float(top_p)
        self.quantize_kv = bool(quantize_kv)
        if decode_block < 1:
            raise ValueError(f"decode_block must be >= 1, got {decode_block}")
        # K decode steps per dispatch (_decode_block): amortizes the
        # per-dispatch host overhead across K tokens. Admission,
        # retirement, and cancellation stay host-side, honored at block
        # boundaries — worst-case K-1 garbage steps per retiring slot and
        # up to one block of extra latency on cancel and admission. Every
        # dispatch runs the full K (one compiled variant, honored exactly
        # as configured). 1 = the historical one-token step() (what the
        # deterministic tests drive).
        self.decode_block = int(decode_block)
        # chunked prefill: a prompt longer than this admits over multiple
        # engine steps — one fixed-size chunk of prefill between decode
        # blocks — so a long admission never stalls the active streams for
        # more than one chunk. Chunk i extends the accumulated K/V through
        # the prefix-suffix math (exact for dense models; MoE expert
        # capacity becomes per-CHUNK, the standard chunked-prefill trade).
        # None = one-shot admission (the historical behavior).
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        # (req, slot, k_acc, v_acc, consumed, frontier, adapter_kw, aidx,
        #  prefix_tokens)
        self._chunking: Optional[tuple] = None
        # constant key for non-sampling (intermediate) prefill chunks
        self._dummy_key = jax.random.PRNGKey(0)
        # per-slot sampling keys: each slot's stream is a pure function of
        # (its key, its positions) — a request with seed=S decodes the
        # same tokens whatever slot it lands in, whoever its neighbors
        # are, and whatever decode_block is; unseeded requests draw their
        # key from the engine chain at admission. On the device only
        # (_seat_first): reading a key back would wait for the prefill.
        self._skeys = jnp.zeros((self.slots, 2), jnp.uint32)
        # the ambient mesh is THREAD-LOCAL trace state: capture it at
        # construction and re-install it around every trace site, or an
        # engine driven by its background loop thread (start()/generate(),
        # the kt.cls deployment mode) would silently lose the mesh-aware
        # dispatch (context-sharded decode, MoE gather gating)
        from ..parallel.mesh_context import current_mesh
        self._mesh = current_mesh()
        self._buckets = sorted({min(b, self.max_len)
                                for b in prefill_buckets} | {self.max_len})
        # the cache kind's operations; a kind other than per-head K/V rows
        # refuses, here and by name, the mechanisms it does not carry yet
        self._ops = _cache_ops(cfg)
        self._refuse(quantize_kv and "int8 KV rows (quantize_kv)",
                     prefill_chunk is not None
                     and "chunked prefill (prefill_chunk)",
                     auto_prefix and "the prefix store (auto_prefix)",
                     aot_cache is not None
                     and "the AOT executable cache (aot_cache)",
                     self._mesh is not None and "a sharded mesh")
        # what the decode steps tally on the device (_tally_shapes: the
        # expert layers' routing, a sparse attention's rows), for families
        # that keep any; the host's copy is refreshed when stats() is read
        # at a batch boundary
        self._tally = _init_tally(cfg)
        self._tally_host = self._tally and {
            k: np.zeros(v.shape, np.int64) for k, v in self._tally.items()}
        if self.quantize_kv:
            # int8 grid (kv_quant): halves the decode HBM stream + cache
            # footprint; prefill/prefix math stays full-precision, rows
            # quantize at the splice
            from .kv_quant import init_quant_cache
            self._cache = init_quant_cache(cfg, self.slots, self.max_len)
        else:
            self._cache = self._ops.init_grid(cfg, self.slots, self.max_len)
        shardings = _cache_shardings(self._cache)
        if shardings is not None:
            # grid lives sharded from step 0 (slots over data axes, the
            # sequence dim over context, heads over tensor)
            self._cache = jax.device_put(self._cache, shardings)
        # the per-slot vectors below are the HOST's mirrors (written at
        # emit, seat and retire; SpeculativeEngine decodes from them). The
        # decode loop's own copy is the device carry, further down.
        self._pos = np.zeros(self.slots, np.int32)     # next write position
        self._tok = np.zeros(self.slots, np.int32)     # next decode input
        self._slot_req: List[Optional[_Request]] = [None] * self.slots
        self._pending: "deque[_Request]" = deque()
        self._temps = np.zeros(self.slots, np.float32)
        self._top_ps = np.ones(self.slots, np.float32)
        self._fpen = np.zeros(self.slots, np.float32)
        self._ppen = np.zeros(self.slots, np.float32)
        # (SLOTS, V) seen-token counts, allocated on the first penalized
        # request (sticky, like _nucleus): V-sized buffers and the per-step
        # scatter only exist once someone pays for them
        self._counts = None
        # (SLOTS, V) logit_bias rows + per-slot mask, allocated on the
        # first biased request (same sticky pattern); the mask neutralizes
        # stale rows, so retirement never needs a device write
        self._bias = None
        self._bmask = np.zeros(self.slots, np.float32)
        # sticky: flips on the first nucleus request so the common
        # no-top-p engine never compiles (or pays for) the vocab sort;
        # afterwards both step variants stay in the jit cache
        self._nucleus = self.top_p is not None and self.top_p < 1.0
        # id → (k_bucketed, v_bucketed, true_len, tokens, adapter_id)
        self._prefixes: Dict[int, tuple] = {}
        self._prefix_ids = itertools.count()
        # auto_prefix: submit() reuses the LONGEST registered prefix the
        # prompt starts with (same adapter), no prefix_id needed — register
        # the system prompts / few-shot headers once, every matching
        # request skips recomputing them
        self.auto_prefix = bool(auto_prefix)
        self._prefix_hits = 0
        # multi-LoRA: stacked adapter banks, target → (A (L,N,D,R),
        # B (L,N,R,O)); bank index 0 is the all-zero adapter (= base model),
        # which idle and base-traffic slots point at
        self._lora_cfg = None
        self._banks: Optional[Dict[str, tuple]] = None
        self._adapter_slots: Dict[int, int] = {}   # public id → bank index
        self._free_bank: List[int] = []
        self._adapter_ids = itertools.count(1)
        self._aidx = np.zeros(self.slots, np.int32)
        self._admitting: Optional[_Request] = None   # cancel() window
        # the decode carry on the device: the newest block's final (pos,
        # tok) and the prefills' first tokens beside them, and the slots
        # seated or retired since the last dispatch, which the next patch
        # (_patch_carry) overwrites. Stepping thread only.
        self._carry = (jnp.zeros(self.slots, jnp.int32),
                       jnp.zeros(self.slots, jnp.int32))
        self._firsts = jnp.zeros(self.slots, jnp.int32)
        self._dirty: set = set()
        # admissions of this boundary whose first token is still on the
        # device only: (req, slot, first, logprob), in admission order
        self._seating: List[tuple] = []
        # dispatched blocks not yet fetched, oldest first: at most the
        # running one and one queued behind it
        self._inflight: "deque[_Flight]" = deque()
        self._blocks_ahead = 0
        self._rng = jax.random.PRNGKey(seed)
        self._rid = itertools.count()
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # start()/stop() are reached concurrently when the engine serves as
        # a kt.cls (the pod runs sync methods on an executor): exactly one
        # loop thread may ever exist — two would interleave _decode_step on
        # the same donated cache
        self._lifecycle = threading.Lock()
        # callables queued for the next batch boundary (weight hot swap —
        # serve/rollout.py is the only assigner of self.params after
        # construction; see at_batch_boundary)
        self._boundary_hooks: "deque[tuple]" = deque()
        # continuous-learning tap (flywheel/ledger.py): when set — e.g. to
        # flywheel.ledger.engine_feedback_hook(ledger) — every retired
        # request's summary passes through it once, on the step thread.
        # The sink owns sampling and MUST swallow its own errors; the
        # retire path still guards, because a raised sink would wedge the
        # decode loop for every live slot, not just the sampled one.
        self.feedback_sink = None
        # stats
        self._admitted = self._finished = 0
        self._tokens = self._steps = 0
        self._ttfts = deque(maxlen=256)   # rolling TTFT window
        self._t0 = time.monotonic()
        # step anatomy: where the stepping thread's time goes, phase by
        # phase (cumulative seconds here, kt_engine_phase_seconds per
        # block, kt.engine.<phase> on a profiler's host timeline)
        self._phases = telemetry.PhaseClock(
            telemetry.engine_metrics()["phase_seconds"],
            annotate=jax.profiler.TraceAnnotation, prefix="kt.engine.",
            wait=telemetry.ENGINE_WAIT_PHASES)
        # persistent AOT compile cache (ISSUE 16): pre-load the
        # common-signature executables (prefill per bucket + the decode
        # step) so a warm replica skips tracing entirely. Mesh-sharded
        # engines keep the traced-jit path: serialized executables bake
        # device assignments, which don't survive a different pod's mesh.
        self._aot_cache = aot_cache
        self._aot_exec: Dict[tuple, Any] = {}
        if aot_cache is not None and self._mesh is None:
            from .aot_cache import warm_engine
            self._aot_exec = warm_engine(self, aot_cache)

    def _refuse(self, *mechanisms) -> None:
        """Raise the typed error for the first named mechanism asked of a
        cache kind that does not carry it (each argument: the mechanism's
        name if it was asked for, else falsy). Per-head K/V rows carry
        all of them."""
        if self._ops is _KV_OPS:
            return
        for m in mechanisms:
            if m:
                from ..exceptions import UnsupportedMechanismError
                raise UnsupportedMechanismError(
                    m, getattr(self.cfg, "cache_kind", "?"))

    # -- adapters -----------------------------------------------------------

    def register_adapter(self, adapters: Dict[str, Any], lora_cfg) -> int:
        """Install a LoRA adapter (``models.lora.lora_init`` layout:
        ``layers`` dict of per-target stacked ``{t}__a`` (L, D, R) /
        ``{t}__b`` (L, R, O) factors) for UNMERGED activation-path serving:
        requests submitted with the returned id run ``x·W + s·(x·A)·B``
        through one compiled step shared with every other adapter and the
        base model — different slots, different adapters, no weight swap.

        All adapters on one engine must share the first registration's
        rank, targets, and scale (they stack into one bank per target).
        Growing the bank (a registration with no free slot) changes the
        decode step's shapes — one recompile; prefer registering the fleet
        up front. Freed slots (:meth:`unregister_adapter`) are reused
        without recompiling."""
        self._refuse("LoRA adapters on the attention projections "
                     "(register_adapter)")
        layers = adapters.get("layers", adapters)
        served = {"wq", "wk", "wv", "wo"}
        extra = set(lora_cfg.targets) - served
        if extra:
            # training (lora_loss/merge_lora) adapts ANY layer leaf, but the
            # serving path applies lora_proj only at the attention
            # projections — banking other targets would silently drop them
            raise ValueError(
                f"activation-path serving supports targets {sorted(served)}; "
                f"got {sorted(extra)} — serve those via merge_lora instead")
        pairs = {}
        for t in lora_cfg.targets:
            try:
                pairs[t] = (jnp.asarray(layers[f"{t}__a"]),
                            jnp.asarray(layers[f"{t}__b"]))
            except KeyError:
                raise KeyError(
                    f"adapter missing factors for target {t!r} "
                    f"(have {sorted(layers)})") from None
        with self._lock:
            # config check under the lock: two racing first registrations
            # must not both pass the None check and stack mismatched
            # factors (the loser would serve with the winner's scale)
            if self._lora_cfg is not None and (
                    lora_cfg.rank != self._lora_cfg.rank
                    or tuple(lora_cfg.targets) != tuple(self._lora_cfg.targets)
                    or lora_cfg.scale != self._lora_cfg.scale):
                raise ValueError(
                    f"adapter config {lora_cfg} does not match the engine's "
                    f"existing bank config {self._lora_cfg} (one bank per "
                    "engine: rank/targets/scale must agree)")
            self._lora_cfg = self._lora_cfg or lora_cfg
            if self._banks is None:
                self._banks = {
                    t: (jnp.stack([jnp.zeros_like(a), a], axis=1),
                        jnp.stack([jnp.zeros_like(b), b], axis=1))
                    for t, (a, b) in pairs.items()}
                idx = 1
            elif self._free_bank:
                idx = self._free_bank.pop()
                self._banks = {
                    t: (A.at[:, idx].set(pairs[t][0]),
                        B.at[:, idx].set(pairs[t][1]))
                    for t, (A, B) in self._banks.items()}
            else:
                idx = next(iter(self._banks.values()))[0].shape[1]
                self._banks = {
                    t: (jnp.concatenate([A, pairs[t][0][:, None]], axis=1),
                        jnp.concatenate([B, pairs[t][1][:, None]], axis=1))
                    for t, (A, B) in self._banks.items()}
            aid = next(self._adapter_ids)
            self._adapter_slots[aid] = idx
        return aid

    def unregister_adapter(self, adapter_id: int) -> bool:
        """Free an adapter's bank slot (reused by the next registration —
        no recompile). The slot's factors are zeroed and any request still
        DECODING on it is repointed at bank index 0, so it falls back to
        the base model mid-stream — never onto whatever tenant reuses the
        slot next. Queued requests against the id fail at admission through
        their handle."""
        with self._lock:
            idx = self._adapter_slots.pop(adapter_id, None)
            if idx is None:
                return False
            self._banks = {t: (A.at[:, idx].set(0.0), B.at[:, idx].set(0.0))
                           for t, (A, B) in self._banks.items()}
            self._aidx[self._aidx == idx] = 0
            self._free_bank.append(idx)
        return True

    def _resolve_adapter(self, adapter_id: Optional[int]):
        """(per-layer-stacked adapter dict for prefill, bank index) — under
        the lock so a concurrent unregister can't hand back a half-freed
        slot."""
        if adapter_id is None:
            return None, 0
        with self._lock:
            if adapter_id not in self._adapter_slots:
                raise KeyError(f"unknown adapter_id {adapter_id}")
            idx = self._adapter_slots[adapter_id]
            banks = self._banks
        return {t: (A[:, idx], B[:, idx])
                for t, (A, B) in banks.items()}, idx

    # -- submission ---------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 64,
               temperature: Optional[float] = None,
               prefix_id: Optional[int] = None,
               adapter_id: Optional[int] = None,
               top_p: Optional[float] = None,
               frequency_penalty: float = 0.0,
               presence_penalty: float = 0.0,
               stop: Optional[Sequence] = None,
               logit_bias: Optional[Dict[int, float]] = None,
               seed: Optional[int] = None) -> RequestHandle:
        """Queue one request. ``temperature`` overrides the engine default
        for THIS request only (0 = greedy) — per-slot temperatures share the
        same compiled step. ``prefix_id`` (from :meth:`register_prefix`)
        reuses a cached shared prefix's K/V: only the suffix is prefilled,
        and generation continues as if prefix+prompt had been submitted.
        ``adapter_id`` (from :meth:`register_adapter`) runs THIS request
        through its LoRA adapter — prefill and every decode step — while
        neighboring slots run theirs (or the base model). ``top_p``
        overrides the engine default for THIS request (nucleus sampling;
        applies only when its temperature is > 0 — greedy slots ignore
        it). ``stop`` is one token-id sequence or a list of them: the
        request retires as soon as its generated tokens end with any stop
        sequence (the matching tokens ARE emitted, mirroring eos_id).

        With ``auto_prefix=True`` (engine ctor) and no explicit
        ``prefix_id``, the longest registered prefix the prompt starts
        with (same adapter) is reused automatically — pass the FULL
        prompt; the engine strips the cached part itself."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the prefill "
                             "always samples the first token)")
        full_prompt = None
        if prefix_id is None and self.auto_prefix:
            prefix_id, stripped = self._match_prefix(prompt, adapter_id,
                                                     int(max_new_tokens))
            if prefix_id is not None:
                full_prompt, prompt = prompt, stripped
        prefix_bucket = 0
        if prefix_id is not None:
            # fetch ONCE: a concurrent unregister between an existence
            # check and a later read must not blow up mid-validation
            pref = self._prefixes.get(prefix_id)
            if pref is None:
                if full_prompt is not None:
                    # the engine matched this prefix itself (auto_prefix)
                    # and lost the race with an eviction — the caller never
                    # asked for it, so serve the full prompt instead
                    prompt, full_prompt, prefix_id = full_prompt, None, None
                else:
                    raise KeyError(f"unknown prefix_id {prefix_id}")
            else:
                # validate against the BUCKETED length: the spliced rows
                # span the bucket, so that is what must fit under max_len
                prefix_bucket = pref[0].shape[2]
        if prefix_bucket + len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prefix bucket ({prefix_bucket}) + prompt ({len(prompt)}) "
                f"+ max_new_tokens ({max_new_tokens}) exceeds the engine's "
                f"max_len ({self.max_len})")
        if adapter_id is not None and adapter_id not in self._adapter_slots:
            raise KeyError(f"unknown adapter_id {adapter_id}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if logit_bias:
            import math
            logit_bias = {int(t): float(b) for t, b in logit_bias.items()}
            bad = [t for t in logit_bias
                   if not 0 <= t < self.cfg.vocab_size]
            if bad:
                raise ValueError(f"logit_bias token ids out of vocab "
                                 f"range [0, {self.cfg.vocab_size}): {bad}")
            nonfin = [t for t, b in logit_bias.items()
                      if not math.isfinite(b)]
            if nonfin:
                # a single NaN/inf bias poisons the whole logits row
                raise ValueError(
                    f"logit_bias values must be finite; got "
                    f"{ {t: logit_bias[t] for t in nonfin} }")
        req = _Request(next(self._rid), prompt, int(max_new_tokens),
                       temperature=temperature, prefix_id=prefix_id,
                       adapter_id=adapter_id, top_p=top_p,
                       frequency_penalty=float(frequency_penalty),
                       presence_penalty=float(presence_penalty),
                       stop=_normalize_stop(stop), full_prompt=full_prompt,
                       logit_bias=logit_bias or None,
                       seed=None if seed is None else int(seed))
        with self._lock:
            self._pending.append(req)
        self._work.set()
        return RequestHandle(req, engine=self)

    def register_prefix(self, tokens: Sequence[int],
                        adapter_id: Optional[int] = None) -> int:
        """Prefill a shared prefix (system prompt, few-shot header) ONCE and
        cache its K/V; subsequent :meth:`submit` calls with the returned id
        skip recomputing it. Exact for dense models; for MoE, expert
        capacity is per segment (see ``_prefill_suffix``). ``adapter_id``
        computes the prefix K/V through that adapter — pair it with
        requests running the SAME adapter, or the cached rows won't match
        what a solo run would have produced."""
        self._refuse("the prefix store (register_prefix)")
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("empty prefix")
        if len(tokens) >= self.max_len:
            raise ValueError(f"prefix ({len(tokens)}) must leave room under "
                             f"max_len ({self.max_len})")
        with self._mesh_scope():
            return self._register_prefix(tokens, adapter_id)

    def _register_prefix(self, tokens, adapter_id) -> int:
        t = len(tokens)
        adapter, _ = self._resolve_adapter(adapter_id)
        lkw = ({"adapter": adapter, "lora_scale": self._lora_cfg.scale}
               if adapter is not None else {})
        bucket = next(b for b in self._buckets if b >= t)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :t] = tokens
        _, k_new, v_new, _lp = _prefill(
            self.params, jnp.asarray(padded), jnp.int32(t), self._next_key(),
            jnp.zeros((1,), jnp.float32), self.cfg, top_k=self.top_k, **lkw)
        # Keep BUCKETED K/V: _prefill_suffix takes the true length as a
        # traced scalar, so one compile covers every prefix sharing the
        # bucket (padding rows are overwritten by the suffix / masked).
        # The STORAGE bucket must leave room for at least a 1-token suffix
        # + 1 generated token under max_len — when the run bucket doesn't
        # (e.g. the smallest bucket is most of max_len), trim to the exact
        # length instead (a compile per distinct prefix length only in
        # that degenerate config).
        store = next((b for b in self._buckets
                      if b >= t and b + 2 <= self.max_len), t)
        if store != bucket:
            k_new = k_new[:, :, :store]
            v_new = v_new[:, :, :store]
        pid = next(self._prefix_ids)
        self._prefixes[pid] = (k_new, v_new, t, tuple(tokens), adapter_id)
        return pid

    def _match_prefix(self, prompt: List[int], adapter_id: Optional[int],
                      max_new_tokens: int):
        """Longest registered prefix this prompt starts with (auto_prefix):
        returns (prefix_id, suffix) or (None, prompt). Candidates must have
        been computed through the SAME adapter (a prefix cached through
        adapter A holds A's K/V — serving it to base traffic would splice
        the wrong activations), leave a non-empty suffix, and fit the
        bucket + suffix + budget under max_len."""
        with self._lock:
            items = list(self._prefixes.items())
        best = None
        for pid, (pk, _v, _t, toks, pad) in items:
            n = len(toks)
            if (pad == adapter_id and n < len(prompt)
                    and (best is None or n > best[1])
                    and pk.shape[2] + (len(prompt) - n)
                    + max_new_tokens <= self.max_len
                    and list(toks) == prompt[:n]):
                best = (pid, n)
        if best is None:
            return None, prompt
        return best[0], prompt[best[1]:]

    def unregister_prefix(self, prefix_id: int) -> bool:
        """Free a cached prefix's K/V buffers. The caller owns prefix
        lifetime — the engine never evicts on its own, and each live prefix
        pins ~2·L·P·NKV·Hd device bytes. Requests already queued against
        the id fail with a KeyError surfaced through their handle."""
        return self._prefixes.pop(prefix_id, None) is not None

    def cancel(self, request_id: int) -> bool:
        """Abandon a request: a queued one never admits, an ACTIVE one
        frees its slot at the next step boundary (the in-flight decode
        step finishes — shapes are static, there is nothing to interrupt
        mid-jit). A request caught MID-ADMISSION (popped from the queue,
        prefill in flight) is flagged and reaped right after its
        admission completes. The handle's stream ends cleanly with
        whatever tokens already decoded. False if the id is unknown,
        already finished, or already cancelled — the second of two racing
        cancels always reads False, whatever state the request is in."""
        with self._lock:
            for i, req in enumerate(self._pending):
                if req.rid == request_id:
                    del self._pending[i]
                    self._close_life(req)
                    req.out.put(None)
                    return True
        # active slots are only mutated on the step path; flag the request
        # and let the next step boundary retire it
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.rid == request_id:
                if req.cancelled:
                    return False
                req.cancelled = True
                self._work.set()
                return True
        # the admission window: _admit popped it, _admit_one's prefill is
        # running — without this check a disconnect during a seconds-long
        # first compile would be silently lost and the request would decode
        # its full budget anyway
        adm = self._admitting
        if adm is not None and adm.rid == request_id and not adm.cancelled:
            adm.cancelled = True
            self._work.set()
            return True
        # mid-chunked-admission: the next _chunk_step abandons it
        ck = self._chunking
        if (ck is not None and ck[0].rid == request_id
                and not ck[0].cancelled):
            ck[0].cancelled = True
            self._work.set()
            return True
        return False

    def _retire_slot(self, slot: int) -> None:
        """THE slot-retirement path (natural finish, eos, cancel): end the
        handle's stream, free the grid slot, clear every ledger — one
        definition so a new piece of per-slot state can't be cleared on
        one path and leak on another. Step-thread only."""
        req = self._slot_req[slot]
        if req is None:
            return
        if self.feedback_sink is not None:
            # snapshot BEFORE state clears: after this method the slot's
            # ledgers are gone and the request object is unreachable
            try:
                self.feedback_sink({
                    "request_id": req.rid,
                    "prompt": list(req.full_prompt or req.prompt),
                    "generated": int(req.generated),
                    "cancelled": bool(req.cancelled),
                    "ttft_s": (req.first_token_at - req.submitted_at
                               if req.first_token_at is not None else None),
                    "latency_s": time.monotonic() - req.submitted_at,
                })
            except Exception:  # noqa: BLE001 — never wedge the step thread
                pass
        self._close_life(req)
        req.out.put(None)
        self._slot_req[slot] = None
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._temps[slot] = 0.0
        self._top_ps[slot] = 1.0
        self._bmask[slot] = 0.0
        self._fpen[slot] = 0.0
        self._ppen[slot] = 0.0
        self._aidx[slot] = 0
        self._dirty.add(slot)
        self._finished += 1
        self._free_slot_ledgers(slot)

    def _close_life(self, req: _Request) -> None:
        """The request's life, written once when it retires or is cancelled
        (before its stream ends, so a consumer that has seen the end can
        read it): ``RequestHandle.timeline`` documents the fields. The
        phase seconds are the stepping thread's between the request's
        seating and now; one cancelled while still queued has only
        ``queue_s``."""
        now = time.monotonic()
        life: Dict[str, Any] = {
            "queue_s": (req.admitted_at or now) - req.submitted_at,
            "tokens": int(req.generated), "cancelled": bool(req.cancelled)}
        if req.admitted_at is not None and req.first_token_at is not None:
            life["prefill_s"] = req.first_token_at - req.admitted_at
            life["decode_s"] = now - req.first_token_at
        if req.seated is not None:
            life["blocks"] = req.blocks
            life["blocks_ahead"] = req.blocks_ahead
            life.update(self._phases.since(req.seated))
        req.life = life

    def _reap_cancelled(self) -> None:
        """Step-boundary retirement for cancelled active slots (the only
        thread that mutates slot state is the stepping thread)."""
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.cancelled:
                self._retire_slot(slot)

    def _free_slot_ledgers(self, slot: int) -> None:
        """Subclass hook: extra per-slot state to clear on retirement."""

    # -- batch-boundary scheduling ------------------------------------------

    def at_batch_boundary(self, fn, timeout: Optional[float] = None):
        """Run ``fn()`` between decode batches, on the stepping thread.

        THE safe point for anything that mutates engine-wide device state
        — above all the live weight hot swap (``serve/rollout.py``, the
        only sanctioned ``engine.params`` writer after construction): no
        decode dispatch is in flight when the hook runs, so donated
        buffers can be freed and replaced without racing a jit. "Between
        batches" means that every block dispatched so far has been fetched
        and emitted, also one that was dispatched ahead: a queued hook
        stops the run-ahead (:meth:`_may_run_ahead`), the loop fetches and
        emits what is in flight (two blocks at most) and then runs the
        hook, so the counters it reads count exactly the tokens made so
        far and the next token comes from what it left behind. Blocks the
        CALLER until the hook has run (the decode loop itself never
        blocks on anything but the device); with no loop thread running,
        runs inline under the engine's mesh scope, after fetching and
        emitting whatever ``step()`` left in flight — the caller is the
        de-facto stepping thread. Exceptions propagate to the caller,
        never into the decode loop. Returns ``fn()``'s result."""
        with self._lifecycle:
            thread = self._thread
        running = thread is not None and thread.is_alive()
        if not running or threading.current_thread() is thread:
            with self._mesh_scope():
                if not running:
                    self._drain()
                return fn()
        box: Dict[str, Any] = {"done": threading.Event()}
        self._boundary_hooks.append((fn, box))
        self._work.set()
        if not box["done"].wait(timeout):
            raise TimeoutError(
                "engine did not reach a batch boundary in time")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def _run_boundary_hooks(self) -> None:
        """Drain queued boundary hooks (stepping thread, between batches)."""
        while self._boundary_hooks:
            fn, box = self._boundary_hooks.popleft()
            try:
                box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 — hand to the waiter
                box["error"] = e
            finally:
                box["done"].set()

    # -- engine loop --------------------------------------------------------

    def _mesh_scope(self):
        """use_mesh(self._mesh) on the CURRENT thread (no-op off-mesh)."""
        import contextlib
        if self._mesh is None:
            return contextlib.nullcontext()
        from ..parallel.mesh_context import use_mesh
        return use_mesh(self._mesh)

    def _next_key(self) -> jax.Array:
        # under _lock: register_prefix runs on caller threads while the
        # loop thread decodes — an unsynchronized split can hand two
        # consumers the same key (correlated samples)
        with self._lock:
            self._rng, sub = jax.random.split(self._rng)
        return sub

    def _free_slots(self) -> List[int]:
        busy = self._chunking[1] if self._chunking is not None else None
        return [i for i, r in enumerate(self._slot_req)
                if r is None and i != busy]

    def _admit(self) -> None:
        if self._chunking is not None:
            # one chunk of the in-progress long admission per engine step
            # (decode blocks run in between — that's the point)
            self._chunk_step()
        free = self._free_slots()
        while free:
            with self._lock:
                if not self._pending:
                    return
                req = self._pending.popleft()
            if req.admitted_at is None:   # a requeued long prompt keeps it
                req.admitted_at = time.monotonic()
            slot = free.pop(0)
            if (self.prefill_chunk is not None and self._chunking is not None
                    and len(req.prompt) > self.prefill_chunk):
                # a second long prompt while the chunker is busy: requeue
                # and stop admitting this step (FIFO preserved) rather
                # than falling back to a one-shot prefill at the max_len
                # bucket — a giant compile + the exact stall chunking
                # exists to avoid. The chunker frees within a few steps.
                with self._lock:
                    self._pending.appendleft(req)
                return
            if (self.prefill_chunk is not None and self._chunking is None
                    and len(req.prompt) > self.prefill_chunk):
                # long prompt with the chunker free: reserve the slot and
                # prefill one chunk per step (a long prompt arriving while
                # the chunker is BUSY requeued above and waits for it).
                # _admitting makes the request cancellable during the
                # first chunk's (possibly compile-long) prefill; once
                # _chunking is set, cancel() finds it there instead.
                self._admitting = req
                try:
                    with self._phases.phase("admit.setup"):
                        self._start_chunking(req, slot)
                except Exception as e:   # noqa: BLE001
                    req.error = e
                    req.out.put(None)
                    free.insert(0, slot)
                finally:
                    self._admitting = None
                continue
            # visible to cancel() during the (possibly seconds-long)
            # prefill below; the flag it may set is honored by the reap at
            # the next step boundary once the slot is assigned
            self._admitting = req
            try:
                self._admit_one(req, slot)
            except Exception as e:   # noqa: BLE001 — per-request failure
                # (unregistered prefix, bad state) fails THAT request via
                # its handle; the loop thread must survive
                req.error = e
                req.out.put(None)
                free.insert(0, slot)
            finally:
                self._admitting = None

    # -- chunked prefill ----------------------------------------------------

    def _start_chunking(self, req: _Request, slot: int) -> None:
        """First chunk of a long admission: seed the FIXED-capacity
        accumulator (max_len rows — one compiled chunk-step shape for the
        engine's lifetime, and the final splice is exactly cache-width)
        from the request's cached prefix when it has one, else from a
        plain prefill of the first chunk. Costs one extra slot's worth of
        K/V while a chunked admission is in flight."""
        pref = self._resolve_prefix(req)
        adapter, aidx = self._resolve_adapter(req.adapter_id)
        lkw = ({"adapter": adapter, "lora_scale": self._lora_cfg.scale}
               if adapter is not None else {})
        c = self.prefill_chunk
        if req.prefix_id is not None:
            # the registered prefix IS the seed; chunks run behind it
            rows_k, rows_v, p_real = pref[0], pref[1], pref[2]
            self._prefix_hits += 1
            consumed, frontier = 0, int(p_real)
        else:
            toks = req.prompt[:c]                  # len(prompt) > c
            padded = np.zeros((1, c), np.int32)
            padded[0, :] = toks
            # greedy dummy key: intermediate chunks never sample, and
            # drawing real keys here would shift the engine's key stream
            # vs one-shot admission (breaking sampled-mode equivalence)
            _f, rows_k, rows_v, _lp = _prefill(
                self.params, jnp.asarray(padded), jnp.int32(c),
                self._dummy_key, jnp.zeros((1,), jnp.float32), self.cfg,
                top_k=self.top_k, **lkw)
            consumed = frontier = c
        pad_w = self.max_len - rows_k.shape[2]
        widen = [(0, 0)] * rows_k.ndim
        widen[2] = (0, pad_w)
        k_acc = jnp.pad(rows_k, widen)
        v_acc = jnp.pad(rows_v, widen)
        self._chunking = (req, slot, k_acc, v_acc, consumed, frontier,
                          lkw, aidx, pref[3] if pref is not None else None)

    def _chunk_step(self) -> None:
        """Advance the in-progress chunked admission by one chunk; the
        LAST chunk samples the first token and seats the request. The
        accumulator stays max_len-wide: ``_prefill_suffix`` returns
        max_len + C rows (scattered at absolute positions < max_len), and
        the trailing pad is sliced back off."""
        (req, slot, k_acc, v_acc, consumed, frontier,
         lkw, aidx, pref_toks) = self._chunking
        if req.cancelled:
            self._chunking = None
            req.out.put(None)
            return
        phase = self._phases.phase
        c = self.prefill_chunk
        rest = len(req.prompt) - consumed
        take = min(c, rest)
        toks = req.prompt[consumed:consumed + take]
        padded = np.zeros((1, c), np.int32)
        padded[0, :take] = toks
        last = take == rest
        try:
            if not last:
                # an intermediate chunk is dispatched and not waited for
                with phase("admit.setup"):
                    _f, k_acc, v_acc, _lp = _prefill_suffix(
                        self.params, jnp.asarray(padded), jnp.int32(take),
                        k_acc, v_acc, jnp.int32(frontier), self._dummy_key,
                        jnp.zeros((1,), jnp.float32), self.cfg,
                        top_k=self.top_k, **lkw)
                    self._chunking = (req, slot, k_acc[:, :, :self.max_len],
                                      v_acc[:, :, :self.max_len],
                                      consumed + take, frontier + take,
                                      lkw, aidx, pref_toks)
                return
            with phase("admit.setup"):
                temp, temps, tp, pkw, row, bias_vec = self._sampling_setup(
                    req, pref_toks)
                key, skey = self._request_keys(req, frontier + take)
            with phase("admit.prefill"):
                first, k_new, v_new, flp = _prefill_suffix(
                    self.params, jnp.asarray(padded), jnp.int32(take),
                    k_acc, v_acc, jnp.int32(frontier), key,
                    temps, self.cfg, top_k=self.top_k, **lkw, **pkw)
            self._chunking = None
            self._finish_admission(req, slot, first, flp,
                                   k_new[:, :, :self.max_len],
                                   v_new[:, :, :self.max_len],
                                   frontier + take, temp, tp, row, aidx,
                                   skey, bias_vec=bias_vec)
        except Exception as e:   # noqa: BLE001 — fail THIS request only
            self._chunking = None
            req.error = e
            req.out.put(None)

    def _resolve_prefix(self, req: _Request):
        """Fetch the request's prefix tuple ONCE (every later use reads
        the returned local, so an unregister racing admission can't fail
        a request that passed the check here). An evicted AUTO-matched
        prefix falls back to the full prompt; an evicted explicit one is
        the caller's error."""
        pref = (self._prefixes.get(req.prefix_id)
                if req.prefix_id is not None else None)
        if req.prefix_id is not None and pref is None:
            if req.full_prompt is not None:
                req.prompt, req.full_prompt = req.full_prompt, None
                req.prefix_id = None
            else:
                raise KeyError(f"unknown prefix_id {req.prefix_id}")
        return pref

    def _sampling_setup(self, req: _Request, pref_toks):
        """Per-request sampling state for the admission prefill
        (``pref_toks``: the request's cached-prefix token tuple, or None).
        Returns (temp, temps (1,), tp, pkw jit-kwargs, row counts-seed,
        bias_vec (V,) float32 or None)."""
        temp = (self.temperature if req.temperature is None
                else float(req.temperature))
        temps = jnp.full((1,), temp, jnp.float32)
        tp = (self.top_p if req.top_p is None else float(req.top_p))
        tp = 1.0 if tp is None else tp
        if tp < 1.0:
            self._nucleus = True
        pkw = {"top_ps": jnp.full((1,), tp, jnp.float32)} \
            if self._nucleus else {}
        fp, pp = req.frequency_penalty, req.presence_penalty
        if (fp or pp) and self._counts is None:
            self._counts = jnp.zeros((self.slots, self.cfg.vocab_size),
                                     jnp.int32)
        row = None
        if fp or pp:
            # only penalized requests pay the V-sized row (zero-penalty
            # neighbors neutralize any stale row by multiplying it by 0,
            # so they need no seeding at all)
            seen = list(req.prompt)
            if pref_toks is not None:
                seen += list(pref_toks)
            row = np.zeros(self.cfg.vocab_size, np.int32)
            np.add.at(row, np.asarray(seen, np.int64), 1)
            # penalties apply to the FIRST sampled token too (the prompt
            # is "text so far" — OpenAI semantics)
            pkw["pen_row"] = jnp.asarray(
                fp * row.astype(np.float32)
                + pp * (row > 0).astype(np.float32))
        bias_vec = None
        if req.logit_bias:
            bias_vec = np.zeros(self.cfg.vocab_size, np.float32)
            for tid, b in req.logit_bias.items():
                bias_vec[tid] = b
            # pen_row is SUBTRACTED from the prefill logits, so the bias
            # folds in negated — the first sampled token is biased too
            prev = pkw.get("pen_row")
            pkw["pen_row"] = ((0.0 if prev is None else prev)
                              - jnp.asarray(bias_vec))
        return temp, temps, tp, pkw, row, bias_vec

    def _request_keys(self, req: _Request, start: int):
        """Sampling keys of an admission, both on the device: the prefill's
        (the FIRST token, placed at position ``start``) and the slot's.
        Seeded requests fold their own base key by ``start - 1`` for the
        prefill — disjoint from the decode folds at start, start+1, … —
        and draw nothing from the engine chain; unseeded ones draw both
        from it, in this order."""
        if req.seed is None:
            return self._next_key(), self._next_key()
        base = jax.random.PRNGKey(req.seed)
        return jax.random.fold_in(base, start - 1), base

    def _finish_admission(self, req: _Request, slot: int, first, flp,
                          k_new, v_new, start: int, temp: float, tp: float,
                          row, aidx: int, skey, bias_vec=None) -> None:
        """Post-prefill slot bookkeeping shared by one-shot and chunked
        admission: queue the splice of the K/V rows behind the prefill,
        seat the request, seed ledgers, re-check the adapter mapping.
        Nothing here reads the device back: the slot's key and the first
        sampled token stay there (``_seat_first``; the penalty row counts
        the token there too), the decode block is dispatched behind the
        prefill before the host waits for it (unless a slot is still free:
        :meth:`_admit_late`), and :meth:`_emit_firsts` emits it."""
        with self._phases.phase("admit.seat"):
            slot_i = jnp.int32(slot)
            self._cache = _splice_slot(self._cache, slot_i, k_new, v_new)
            self._firsts, self._skeys = _seat_first(
                self._firsts, self._skeys, slot_i, first, skey)
            # the copies to the host start when the prefill ends, not when
            # _emit_firsts asks
            first.copy_to_host_async()
            flp.copy_to_host_async()
            self._slot_req[slot] = req
            req.seated = self._phases.snapshot()
            self._pos[slot] = start
            self._temps[slot] = temp
            self._top_ps[slot] = tp
            self._fpen[slot] = req.frequency_penalty
            self._ppen[slot] = req.presence_penalty
            if row is not None:
                self._counts = _set_counts_row(
                    self._counts, slot_i, jnp.asarray(row), first)
            if bias_vec is not None:
                if self._bias is None:
                    self._bias = jnp.zeros(
                        (self.slots, self.cfg.vocab_size), jnp.float32)
                self._bias = _set_counts_row(self._bias, slot_i,
                                             jnp.asarray(bias_vec))
                self._bmask[slot] = 1.0
            with self._lock:
                # prefill ran outside the lock: if the adapter was evicted
                # in that window (and its index possibly reused by a new
                # tenant), pointing at the stale index would decode through
                # the WRONG factors — re-check the mapping and fall back to
                # base
                if (req.adapter_id is not None
                        and self._adapter_slots.get(req.adapter_id) != aidx):
                    aidx = 0
                self._aidx[slot] = aidx
            self._dirty.add(slot)
            self._admitted += 1
            self._seating.append((req, slot, first, flp))

    def _emit_firsts(self) -> None:
        """Wait for the first token of each request seated at this boundary
        and emit it, in admission order. The decode block that follows the
        prefills is already queued behind them; a request whose first token
        ends it retires here, and its slot's share of that block is dropped
        like any other garbage."""
        phase = self._phases.phase
        seating, self._seating = self._seating, []
        for req, slot, first, flp in seating:
            try:
                with phase("admit.prefill"):
                    # the wait for the prefill
                    first_tok = int(np.asarray(first)[0])
                    logprob = float(np.asarray(flp)[0])
            except Exception as e:   # noqa: BLE001 — fail THIS request only
                req.error = e
                if self._slot_req[slot] is req:
                    self._retire_slot(slot)
                continue
            with phase("admit.seat"):
                self._tok[slot] = first_tok
                self._emit(slot, first_tok, logprob)
                # TTFT sample at the only place it's defined: the first emit
                if req.first_token_at is not None:
                    self._ttfts.append(
                        req.first_token_at - req.submitted_at)

    def _admit_late(self) -> None:
        """Keep the boundary's admission open while a prefill it dispatched
        is still running and a slot is still free. A slot freed by the block
        just fetched is refilled by its caller a fabric round trip later
        (7-14 ms in the benchmark's closed loop), and ``_admit`` alone closes
        the moment it finds nothing pending: whether that caller was seated
        at once or a whole block later then hung on which of two host paths
        of about equal length was the quicker on that machine. The device is
        busy with the prefill either way, so the wait costs nothing until it
        ends; the decode block is then dispatched behind the late prefills,
        or, when nobody came, after this boundary's first tokens instead of
        behind them (the time of one dispatch, on these boundaries only).
        With no free slot, or nothing seated here, nothing could be gained
        and the order is the usual one."""
        while (self._seating and self._chunking is None
               and self._free_slots()):
            self._emit_firsts()
            with self._lock:
                if not self._pending:
                    return
            self._admit()

    def _admit_one(self, req: _Request, slot: int) -> None:
        phase = self._phases.phase
        with phase("admit.setup"):
            pref = self._resolve_prefix(req)
            t = len(req.prompt)
            temp, temps, tp, pkw, row, bias_vec = self._sampling_setup(
                req, pref[3] if pref is not None else None)
            adapter, aidx = self._resolve_adapter(req.adapter_id)
            lkw = ({"adapter": adapter, "lora_scale": self._lora_cfg.scale}
                   if adapter is not None else {})
            if req.prefix_id is not None:
                pk, pv, p_real, p_toks, _pad = pref
                p_bucket = pk.shape[2]
                bucket = next((b for b in self._buckets if b >= t
                               and p_bucket + b <= self.max_len), None)
                if bucket is None:
                    # no bucket leaves room behind the prefix: pad the
                    # suffix to exactly what fits (still one compile per
                    # distinct size, bounded by max_len)
                    bucket = self.max_len - p_bucket
                start = p_real + t
            else:
                bucket = next(b for b in self._buckets if b >= t)
                start = t
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :t] = req.prompt
            tokens, true_len = jnp.asarray(padded), jnp.int32(t)
            key, skey = self._request_keys(req, start)
        # the dispatch only: _finish_admission queues the splice behind it and
        # _emit_firsts waits for the first token, under the same phase, once
        # the decode block has been queued behind both
        with phase("admit.prefill"):
            if req.prefix_id is not None:
                first, k_new, v_new, flp = _prefill_suffix(
                    self.params, tokens, true_len, pk, pv,
                    jnp.int32(p_real), key, temps, self.cfg,
                    top_k=self.top_k, **lkw, **pkw)
                self._prefix_hits += 1
            else:
                # common signature (no adapter/nucleus/penalty kwargs): use
                # the pre-loaded AOT executable when the cache warmed one —
                # statics (cfg, top_k) are baked in, so only dynamic args
                # pass
                exe = (self._aot_exec.get(("prefill", bucket))
                       if not lkw and not pkw else None)
                if exe is not None:
                    first, k_new, v_new, flp = exe(
                        self.params, tokens, true_len, key, temps)
                else:
                    first, k_new, v_new, flp = _prefill(
                        self.params, tokens, true_len, key, temps, self.cfg,
                        top_k=self.top_k, **lkw, **pkw)
        self._finish_admission(req, slot, first, flp, k_new, v_new, start,
                               temp, tp, row, aidx, skey, bias_vec=bias_vec)

    def _emit(self, slot: int, tok: int,
              logprob: Optional[float] = None) -> None:
        req = self._slot_req[slot]
        if req is None:
            return
        if req.first_token_at is None:
            req.first_token_at = time.monotonic()
        # appended before the queue put: a consumer that has seen token i
        # can always read logprob i (None for paths that don't compute it,
        # e.g. speculative verify)
        req.logprobs.append(logprob)
        req.out.put(tok)
        req.generated += 1
        self._tokens += 1
        done = (req.generated >= req.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id))
        if req.stop and not done:
            req.tail.append(tok)
            maxlen = max(len(q) for q in req.stop)
            del req.tail[:-maxlen]
            done = any(len(q) <= len(req.tail)
                       and req.tail[len(req.tail) - len(q):] == list(q)
                       for q in req.stop)
        if done:
            self._retire_slot(slot)

    def step(self) -> int:
        """One pass of the engine loop: at most one decode block dispatched
        (``decode_block`` device steps, default 1, for every slot) and at
        most one fetched and emitted. With nothing in flight the pass is a
        batch boundary: hooks run, pending requests are admitted (and, while
        a slot is still free, whoever arrives before their prefills end:
        :meth:`_admit_late`), the block is dispatched behind the prefills
        and their first tokens emitted.
        Then the block is fetched and emitted — unless its successor may
        run ahead (:meth:`_may_run_ahead`), in which case it stays in
        flight and the NEXT pass dispatches the successor from its device
        carry before fetching it, and so on, one block ahead, until
        something could be seated at a boundary. Returns the remaining work
        — active slots plus queued requests, or the blocks still in flight
        when there is no other — so ``while eng.step(): ...`` runs the
        backlog dry and ends with nothing in flight."""
        with self._mesh_scope():
            return self._step_once()

    def _may_run_ahead(self) -> bool:
        """May the successor of the one block in flight be dispatched before
        that block is fetched? Only when nothing could be seated at the
        boundary between them and the boundary is owed to no one: every
        slot holds a live request, none is pending or mid-chunked-admission,
        no hook is queued, the engine is not stopping — and some request's
        budget outlasts the block in flight (else the successor is garbage
        for every slot, and a request arriving meanwhile would wait for
        it). All of it is state the engine sees now; anything else keeps
        the order fetch, emit, admit, dispatch."""
        ahead = self.decode_block * len(self._inflight)
        return (not self._stop.is_set() and not self._boundary_hooks
                and self._chunking is None and not self._pending
                and all(r is not None and not r.cancelled
                        for r in self._slot_req)
                and any(r.max_new_tokens - r.generated > ahead
                        for r in self._slot_req))

    def _step_once(self) -> int:
        phase = self._phases.phase
        boundary = not self._inflight
        # boundary hooks only BETWEEN decode batches: every dispatched
        # block has been fetched and emitted and nothing is queued on the
        # device, so a weight swap scheduled via at_batch_boundary never
        # overlaps a decode dispatch on the old params. A queued hook stops
        # the run-ahead, so the next pass but one at the latest is here.
        with phase("hooks"):
            if boundary:
                self._run_boundary_hooks()
            self._reap_cancelled()
        if boundary:
            self._admit()
            self._admit_late()
            if any(r is not None for r in self._slot_req):
                self._dispatch(ahead=False)
            self._emit_firsts()
            # depth 0, today's order, unless the successor may run ahead:
            # then this block stays in flight for the next pass
            if self._inflight and not self._may_run_ahead():
                self._collect()
        else:
            if self._may_run_ahead():
                self._dispatch(ahead=True)
            self._collect()
        self._phases.end_block()
        with self._lock:
            queued = len(self._pending)
        return (sum(r is not None for r in self._slot_req) + queued
                + (1 if self._chunking is not None else 0)
                or len(self._inflight))

    @property
    def _live(self) -> np.ndarray:
        """(SLOTS,) int32: 1 where the slot holds a request."""
        return np.array([r is not None for r in self._slot_req], np.int32)

    def _pack_patch(self) -> np.ndarray:
        """The host's mirrors as one carry patch (``_patch_carry``), marked
        for the slots seated or retired since the last one: one upload a
        block, whatever changed."""
        patch = np.zeros((self.slots, _PATCH_WIDTH), np.uint32)
        patch[sorted(self._dirty), 0] = 1
        patch[[slot for _req, slot, _first, _lp in self._seating], 1] = 1
        names = ("pos", "tok", *(name for name, _ in _PATCH_VECTORS))
        for col, name in enumerate(names, start=2):
            patch[:, col] = getattr(self, "_" + name).view(np.uint32)
        return patch

    def _dispatch(self, ahead: bool) -> None:
        """Queue one decode block on the device, from the newest block's
        device carry patched with the slots the host seated or retired since
        (none, when it runs ahead), and record it in flight. ``self._cache``
        and ``self._counts`` are reassigned to the block's donated outputs,
        so two blocks in flight chain them output to input."""
        phase = self._phases.phase
        self._phases.blocks += 1
        with phase("upload"):
            with self._lock:
                banks = self._banks
            carry = _patch_carry(*self._carry, self._firsts, self._skeys,
                                 self._pack_patch())
            self._dirty.clear()
            # once a bank exists every step pays the per-slot gather,
            # base traffic included (aidx 0 = the zero adapter) — the
            # price of one shared compiled step
            lkw = ({"banks": banks, "aidx": carry["aidx"],
                    "lora_scale": self._lora_cfg.scale} if banks else {})
            if self._nucleus:
                lkw["top_ps"] = carry["top_ps"]
            if self._counts is not None:
                lkw.update(counts=self._counts, fpen=carry["fpen"],
                           ppen=carry["ppen"])
            if self._bias is not None:
                lkw.update(bias=self._bias, bmask=carry["bmask"])
            lkw["skeys"] = carry["skeys"]
            if self._tally is not None:
                lkw.update(tally=self._tally, live=carry["live"])
            pos, tok, temps = carry["pos"], carry["tok"], carry["temps"]
            key = self._next_key()
        # always the FULL configured block — never a tail-sized one:
        # n_steps is a static argname, so a variable tail would compile
        # a fresh variant mid-serving (a multi-second stall for every
        # concurrent stream) to save at most K-1 ~ms-scale garbage
        # steps on the final dispatch of a draining backlog
        k = self.decode_block
        # common decode signature (lkw is exactly {skeys}: no banks,
        # nucleus, penalties, or bias): the warm AOT executable takes
        # the dispatch; sticky features fall back to the traced jits
        aot = (self._aot_exec.get(("decode", k))
               if set(lkw) == {"skeys"} else None)
        with phase("dispatch"):
            if k > 1:
                if aot is not None:
                    (self._cache, pos, tok, toks_k, lps_k, counts) = aot(
                        self.params, self._cache, pos, tok, key, temps,
                        skeys=lkw["skeys"])
                else:
                    (self._cache, pos, tok, toks_k, lps_k, counts,
                     *tally) = _decode_block(
                        self.params, self._cache, pos, tok, key, temps,
                        self.cfg, n_steps=k, top_k=self.top_k, **lkw)
                    if tally:
                        self._tally = tally[0]
                if self._counts is not None:
                    self._counts = counts
            else:
                if aot is not None:
                    out = aot(
                        self.params, self._cache, pos, tok, key, temps,
                        skeys=lkw["skeys"])
                else:
                    out = _decode_step(
                        self.params, self._cache, pos, tok, key, temps,
                        self.cfg, top_k=self.top_k, **lkw)
                if self._tally is not None:
                    *out, self._tally = out
                if self._counts is not None:
                    self._cache, tok, lps, self._counts = out
                else:
                    self._cache, tok, lps = out
                pos = pos + 1
                toks_k, lps_k = tok[None], lps[None]    # (1, B)
            # the block's own final pos / tok are its successor's inputs
            self._carry = (pos, tok)
            toks_k.copy_to_host_async()
            lps_k.copy_to_host_async()
        self._steps += k
        self._blocks_ahead += ahead
        self._inflight.append(
            _Flight(list(self._slot_req), toks_k, lps_k, ahead))

    def _collect(self) -> None:
        """Fetch the oldest block in flight and emit its tokens where the
        slot still holds the request it held at dispatch: a slot that was
        retired, cancelled or reseated since computed garbage there, as one
        that retires mid-block does (``_decode_block``). A device error
        surfacing here fails the requests seated in the block and leaves
        the loop alive."""
        phase = self._phases.phase
        flight = self._inflight.popleft()

        def live():
            return [(slot, req) for slot, req in enumerate(flight.reqs)
                    if req is not None and self._slot_req[slot] is req]

        try:
            with phase("fetch"):
                toks_k, lps_k = np.asarray(flight.toks), np.asarray(flight.lps)
        except Exception as e:   # noqa: BLE001 — per-block failure
            for slot, req in live():
                req.error = e
                self._retire_slot(slot)
            return
        with phase("emit"):
            for _slot, req in live():
                req.blocks += 1
                req.blocks_ahead += flight.ahead
            for i in range(toks_k.shape[0]):
                # a slot retired at emit i' < i skips the rest of its
                # block (garbage past the stop point). Each emitted token
                # consumed position _pos[slot]; the next feeds back one
                # position later.
                for slot, _req in live():
                    self._pos[slot] += 1
                    self._tok[slot] = int(toks_k[i, slot])
                    self._emit(slot, int(toks_k[i, slot]),
                               float(lps_k[i, slot]))

    def _drain(self) -> None:
        """Fetch and emit every block in flight (stepping thread, or the
        caller that stands in for it)."""
        while self._inflight:
            self._collect()

    def _run(self) -> None:
        while not self._stop.is_set():
            n = self.step()
            if n == 0 and not self._pending:
                self._work.clear()
                self._work.wait(timeout=0.5)
        # a stopped engine has nothing in flight: its seated requests keep
        # every token that was dispatched for them
        with self._mesh_scope():
            self._drain()

    def start(self) -> "GenerationEngine":
        with self._lifecycle:
            if self._thread is None or not self._thread.is_alive():
                self._stop.clear()
                self._thread = threading.Thread(target=self._run, daemon=True,
                                                name="kt-gen-engine")
                self._thread.start()
        return self

    def stop(self) -> None:
        with self._lifecycle:
            self._stop.set()
            self._work.set()
            thread = self._thread
        if thread is not None:
            thread.join(timeout=10)
        with self._lifecycle:
            # only forget a thread that actually exited: clearing a live
            # straggler would let the next start() run a second loop beside
            # it on the same donated cache
            if self._thread is thread and (thread is None
                                           or not thread.is_alive()):
                self._thread = None
        # hooks enqueued in the stop race would otherwise strand their
        # waiters: with the loop gone, this thread is the stepping thread
        with self._mesh_scope():
            self._run_boundary_hooks()

    # -- introspection ------------------------------------------------------

    def aot_stats(self) -> Dict[str, int]:
        """AOT compile-cache lookup counts for THIS engine's warm-up
        (``hit``/``miss``/``incompatible``/``corrupt``/``publish``…, the
        local mirror of ``kt_aot_cache_total``), plus the number of
        executables the dispatch sites can consult. Empty counts when the
        engine was built without a cache."""
        out = dict(self._aot_cache.counts) if self._aot_cache else {}
        out["executables"] = len(self._aot_exec)
        return out

    def phase_seconds(self) -> Dict[str, Any]:
        """Where the stepping thread's time has gone so far: cumulative
        ``seconds`` per phase of a step (``telemetry.engine_metrics`` names
        them; ``admit.prefill`` and ``fetch`` wait on the device, the rest
        is the host's own work) and the decode ``blocks`` dispatched."""
        return {"seconds": self._phases.snapshot(),
                "blocks": self._phases.blocks}

    def _read_tally(self) -> Dict[str, Any]:
        """The device tallies as ``EngineStats`` has them (its fields by
        name, absent where the family keeps none): the routing tally as
        pairs and hits, each (L_moe, E); the sparse attention's rows scored
        and selected, each (L,). The device's are fetched only where nothing
        is in flight (a batch boundary: ``at_batch_boundary`` runs its hook
        there, on the stepping thread or inline); any other reader — the
        metrics scrape — gets the last reading and never waits for a
        block."""
        if self._tally is None:
            return {}
        thread = self._thread
        if not self._inflight and (thread is None or not thread.is_alive()
                                   or threading.current_thread() is thread):
            self._tally_host = {k: np.asarray(v).astype(np.int64)
                                for k, v in self._tally.items()}
        out, host = {}, self._tally_host
        if "moe" in host:
            out.update(moe_routed_pairs=host["moe"][:, 0],
                       moe_expert_hits=host["moe"][:, 1])
        if "dsa" in host:
            rows = (host["dsa"][..., 0] << _DSA_WORD) + host["dsa"][..., 1]
            out.update(dsa_rows_scored=rows[:, 0], dsa_rows_selected=rows[:, 1])
        return out

    def stats(self) -> EngineStats:
        dt = max(time.monotonic() - self._t0, 1e-9)
        return EngineStats(
            slots=self.slots,
            active=sum(r is not None for r in self._slot_req),
            # a request mid-chunked-admission is neither seated nor in
            # _pending; count it as queued so load gauges never read an
            # idle engine while it prefills
            queued=len(self._pending)
            + (1 if self._chunking is not None else 0),
            admitted_total=self._admitted,
            finished_total=self._finished,
            tokens_generated=self._tokens,
            decode_steps=self._steps,
            tokens_per_sec=self._tokens / dt,
            ttft_avg=(sum(self._ttfts) / len(self._ttfts)
                      if self._ttfts else 0.0),
            blocks_run_ahead=self._blocks_ahead, **self._read_tally())

    def __kt_metrics__(self) -> Dict[str, float]:
        """Pod-scrape hook (``serving.process_worker`` — the
        ``__kt_warmup__`` sibling): a deployed engine's live gauges land
        on the pod's ``/metrics`` under ``kt_user_`` with no exporter
        code. Cheap (host counters only); runs per 3s scrape."""
        s = self.stats()
        out = {"engine_slots": float(s.slots),
               "engine_active": float(s.active),
               # the router packs against free slots: exported so `kt
               # serve status` and the bench can see per-replica headroom
               "engine_slots_free": float(s.slots - s.active),
               "engine_queued": float(s.queued),
               "engine_admitted_total": float(s.admitted_total),
               "engine_finished_total": float(s.finished_total),
               "engine_tokens_generated": float(s.tokens_generated),
               "engine_decode_steps": float(s.decode_steps),
               "engine_blocks_run_ahead_total": float(s.blocks_run_ahead),
               "engine_tokens_per_sec": float(s.tokens_per_sec),
               "engine_ttft_avg_seconds": float(s.ttft_avg),
               "engine_prefix_hits": float(self._prefix_hits)}
        if s.moe_routed_pairs is not None:
            out["engine_moe_routed_pairs_total"] = float(
                s.moe_routed_pairs.sum())
            out["engine_moe_expert_hits_total"] = float(
                s.moe_expert_hits.sum())
            for layer, row in enumerate(s.moe_routed_pairs):
                out[f"engine_moe_layer{layer}_load_max_over_mean"] = float(
                    row.max() / max(row.mean(), 1e-9))
        if s.dsa_rows_scored is not None:
            out["engine_dsa_rows_scored_total"] = float(
                s.dsa_rows_scored.sum())
            out["engine_dsa_rows_selected_total"] = float(
                s.dsa_rows_selected.sum())
        spec = getattr(self, "spec_stats", None)
        if spec is not None:
            out["engine_spec_rounds"] = float(spec.rounds)
            out["engine_spec_acceptance_rate"] = float(spec.acceptance_rate)
            # adaptive draft length (ISSUE 12): the k the EWMA controller
            # currently bets per round
            out["engine_spec_draft_len"] = float(getattr(self, "k", 0))
        return out

    # remote-service surface: a deployed engine (kt.cls) exposes a blocking
    # generate() so callers don't need the handle/iterator machinery
    def generate(self, prompt: Sequence[int], max_new_tokens: int = 64,
                 timeout: Optional[float] = 300.0, *,
                 temperature: Optional[float] = None,
                 prefix_id: Optional[int] = None,
                 adapter_id: Optional[int] = None,
                 top_p: Optional[float] = None,
                 frequency_penalty: float = 0.0,
                 presence_penalty: float = 0.0,
                 stop: Optional[Sequence] = None,
                 logit_bias: Optional[Dict[int, float]] = None,
                 seed: Optional[int] = None) -> List[int]:
        # timeout keeps its historical positional slot; the newer knobs are
        # keyword-only so generate(tokens, 64, 30.0) still means timeout=30
        self.start()
        return self.submit(prompt, max_new_tokens, temperature=temperature,
                           prefix_id=prefix_id, adapter_id=adapter_id,
                           top_p=top_p, frequency_penalty=frequency_penalty,
                           presence_penalty=presence_penalty,
                           stop=stop, logit_bias=logit_bias, seed=seed
                           ).result(timeout=timeout)

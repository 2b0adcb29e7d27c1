"""Autoregressive generation with a static KV cache.

TPU-first decode loop: everything is ``lax.scan`` over static shapes — the
cache is a fixed (L, B, S_max, NKV, Hd) buffer, positions are masked, and one
jit covers prefill + N decode steps (no per-token dispatch, no dynamic
shapes). The cache layout matches the mesh rules: NKV shards over ``tensor``,
batch over data axes, so multi-chip serving is the same NamedSharding story
as training. Works for both decoder families: a layer carrying a ``router``
leaf runs the MoE FFN (top-k dispatch per chunk of new tokens), dense
otherwise — pass the matching ``LlamaConfig`` / ``MoeConfig``.

This is what the RLHF rollout actors (BASELINE config 4) and autoscaled
inference services run.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .block import decoder_block, dense_ffn, qkv_attend, rmsnorm
from .llama import LlamaConfig, rope_freqs
from .moe import MoeConfig, moe_ffn, moe_ffn_decode

NEG_INF = -1e30


class KVCache(NamedTuple):
    """K and V of every layer, in one of two layouts. Row-major
    (L, B, S_max, NKV, Hd): ``generate``'s own cache and a prompt's rows out
    of a prefill (:func:`init_cache`, :func:`cache_attend`). Head-major
    (L, SLOTS, NKV, S_max, Hd): the serving engine's slot grid
    (``serve.engine.init_grid_cache``), which is the decode kernel's layout;
    ``serve.engine._splice_slot`` is the one crossing."""
    k: jax.Array
    v: jax.Array


def init_cache(cfg: "LlamaConfig | MoeConfig", batch: int, max_len: int,
               dtype=None) -> KVCache:
    """Zeroed row-major cache (L, B, S_max, NKV, Hd)."""
    if getattr(cfg, "cache_kind", "kv") != "kv":
        from ..exceptions import UnsupportedMechanismError
        raise UnsupportedMechanismError(
            "the scanned generate path (models.generate)", cfg.cache_kind,
            "serve it through serve.GenerationEngine")
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    dtype = dtype or cfg.dtype
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _cached_attention(q, cache_k, cache_v, q_pos, scale):
    """q: (B, T, N, Hd) at absolute positions q_pos (T,); cache: (B, S, NKV, Hd).
    Causal mask over absolute positions; unwritten cache slots masked out."""
    b, t, nh, hd = q.shape
    s, nkv = cache_k.shape[1], cache_k.shape[2]
    group = nh // nkv
    qg = q.reshape(b, t, nkv, group, hd)
    logits = jnp.einsum("btkgh,bskh->bkgts", qg, cache_k).astype(jnp.float32) * scale
    kv_pos = lax.broadcasted_iota(jnp.int32, (t, s), 1)
    mask = kv_pos <= q_pos[:, None]                     # (T, S)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, cache_v)
    return out.reshape(b, t, nh, hd)


# Read ONCE at import: the gate runs at trace time inside jitted generate(),
# and jit's cache key never sees the env var — a post-compile flip would be
# silently ignored. Import-time freezing makes the semantics honest: the flag
# is per-process (restart to change), matching how serving processes are
# configured. 1 forces the flash prefill on (interpret mode off-TPU — how
# tests cover the branch), 0 forces it off.
_FLASH_PREFILL_FLAG = os.environ.get("KT_FLASH_PREFILL", "auto")


def _flash_prefill_wanted(cfg, t: int) -> bool:
    """Route a from-zero prefill through the Pallas flash kernel?

    The cached-attention einsum materializes a (T, S_max) logits tile per
    head — the HBM wall for long prompts. A prefill starting at position 0
    attends only within its own T tokens (every cache slot beyond them is
    unwritten and masked), so it is exactly causal self-attention and the
    flash kernel applies. Gated to configs that allow the flash kernel
    (``attn_impl`` auto/flash — an explicit "xla" is a deliberate opt-out,
    e.g. an unsupported head_dim), to T a multiple of the 128-lane tile
    (serving pads prompts), and to the TPU backend.
    """
    if _FLASH_PREFILL_FLAG == "0":
        return False
    if cfg.attn_impl not in ("auto", "flash"):
        return False
    from ..ops.attention import flash_auto, flash_supported
    if _FLASH_PREFILL_FLAG == "1":
        return flash_supported(t, cfg.n_heads, cfg.n_kv_heads)
    return flash_auto(t, cfg.n_heads, cfg.n_kv_heads)


# A from-zero prefill routes through ring attention instead of one-chip
# flash when the ambient mesh has a live context axis and the prompt is
# long enough to be worth sequence-sharding — below this, chunk overheads
# beat the parallelism and short buckets stay on the single-chip kernels.
RING_PREFILL_MIN_T = 512


def _sp_prefill_impl(cfg, b: int, t: int) -> Optional[str]:
    """Which sequence-sharded strategy a long from-zero prefill should
    take: "ring"/"ulysses", or None for the single-chip kernels.
    Honors ``cfg.attn_impl`` — "ulysses" routes through its all-to-all,
    an explicit "xla"/"flash" is a deliberate single-chip choice this
    gate must not override; "auto"/"ring" pick ring (the ICI-native
    default, matching ``llama.attention``'s auto resolution)."""
    if t < RING_PREFILL_MIN_T:
        return None
    impl = {"auto": "ring", "ring": "ring",
            "ulysses": "ulysses"}.get(cfg.attn_impl)
    if impl is None:
        return None
    from ..parallel.mesh_context import current_mesh
    from ..parallel.ring_attention import sp_decode_supported
    mesh = current_mesh()
    # batch_axes=(): prefill runs B=1 — replicate over the data axes and
    # shard the SEQUENCE; the divisibility rules are shard_map's
    if (mesh is None
            or not sp_decode_supported(mesh, b, t, cfg.n_kv_heads,
                                       cfg.n_heads, batch_axes=())):
        return None
    return impl


def cache_attend(cfg, layer_cache_k, layer_cache_v, q_pos,
                 flash_prefill: bool = False, causal_prefill: bool = False):
    """The block's attention operation over a row-major layer cache
    (B, S, NKV, Hd): write the T new rows at ``q_pos[0]``, then attend.
    A from-zero prefill (``causal_prefill``) is causal self-attention over
    its own T tokens and takes the sequence-sharded or the flash kernel where
    :func:`_sp_prefill_impl` / the caller's ``flash_prefill`` say so;
    everything else attends the cache under the absolute-position mask.
    Returns ``attend(q, k, v) -> (attn, (cache_k, cache_v))``."""
    scale = cfg.head_dim ** -0.5

    def attend(q, k, v):
        b, t = q.shape[:2]
        with jax.named_scope("kt.cache_update"):
            ck = lax.dynamic_update_slice_in_dim(
                layer_cache_k, k.astype(layer_cache_k.dtype), q_pos[0],
                axis=1)
            cv = lax.dynamic_update_slice_in_dim(
                layer_cache_v, v.astype(layer_cache_v.dtype), q_pos[0],
                axis=1)

        sp_impl = _sp_prefill_impl(cfg, b, t) if causal_prefill else None
        with jax.named_scope("kt.attention"):
            if sp_impl is not None:
                # long-prompt prefill on a context mesh: sequence-sharded
                # attention — no chip holds the full (T, T) attention problem
                from ..parallel.mesh_context import current_mesh
                if sp_impl == "ulysses":
                    from ..parallel.ulysses import ulysses_attention_sharded
                    attn = ulysses_attention_sharded(
                        q, k, v, current_mesh(), causal=True, scale=scale,
                        batch_axes=())
                else:
                    from ..parallel.ring_attention import \
                        ring_attention_sharded
                    attn = ring_attention_sharded(
                        q, k, v, current_mesh(), causal=True, scale=scale,
                        batch_axes=())
            elif flash_prefill:
                from ..parallel.kernel_shard import flash_attention_sharded
                from ..parallel.mesh_context import current_mesh
                attn = flash_attention_sharded(q, k, v, current_mesh(),
                                               causal=True, scale=scale)
            else:
                attn = _cached_attention(q, ck, cv, q_pos, scale)
        return attn, (ck, cv)

    return attend


def _layer_step(cfg, x, lw, layer_cache_k, layer_cache_v, q_pos, freqs_full,
                flash_prefill: bool = False, token_mask=None,
                keep_capacity=None, lora=None, moe_no_drop: bool = False,
                causal_prefill: bool = False):
    """One transformer layer over T new tokens at absolute positions
    ``q_pos`` (T,), updating this layer's row-major cache:
    ``models.block.decoder_block`` over :func:`cache_attend` and
    :func:`ffn_block`. ``lora``: None, or (adapters_by_target, scale) with
    this LAYER's factors per target — the unmerged activation-path adapters
    multi-LoRA serving runs."""
    x, (layer_cache_k, layer_cache_v), _ = decoder_block(
        cfg, x, lw,
        qkv_attend(cfg, freqs_full[q_pos],
                   cache_attend(cfg, layer_cache_k, layer_cache_v, q_pos,
                                flash_prefill=flash_prefill,
                                causal_prefill=causal_prefill)),
        partial(ffn_block, cfg, token_mask=token_mask,
                keep_capacity=keep_capacity, moe_no_drop=moe_no_drop),
        lora=lora)
    return x, layer_cache_k, layer_cache_v


def ffn_block(cfg, h: jax.Array, lw: Dict[str, jax.Array],
              token_mask=None, keep_capacity=None,
              moe_no_drop: bool = False, banks=None):
    """The block's FFN operation for a decode/prefill layer, (out, aux) —
    dense SwiGLU, or the MoE dispatch when the layer carries a ``router``
    leaf. Shared by the scanned ``generate`` path and the continuous-batching
    engines (``serve.engine``, ``serve.spec_engine``), each binding its own
    routing masks, so their expert-routing semantics can never diverge.

    MoE choice: true decode steps (T == 1, where capacity slots can never
    overflow, so both formulations are exactly equal) gather just the K
    chosen experts' weights per token when that moves less weight traffic
    than streaming all E experts. Prefill (T > 1) always uses the
    capacity-buffer dispatch to keep its overflow-drop semantics identical
    to training. The gather is also mechanically disabled under an ambient
    mesh with a live ``expert`` axis: a data-dependent gather along the
    sharded E axis would force GSPMD to all-gather every expert's weights
    per step. Traffic headroom: the gather writes B*K expert-matrix copies
    and re-reads them in the einsum (~2x beyond the read), so it must beat
    the dispatch path's single stream of all E experts with margin — hence
    2*B*K <= E, not B*K <= E. All inputs are static at trace time ⇒ the
    choice is fixed per compile.

    ``banks``: where the stack keeps a run's expert weights out of the
    scanned leaves, (the whole of them, this layer's index in the run), bound
    by ``block.with_banks``."""
    b, t = h.shape[0], h.shape[1]
    if "router_bias" in lw:
        # sigmoid bias-corrected routing, shared experts, no capacity
        from .mla import moe_ffn_dropless
        return moe_ffn_dropless(cfg, h, lw, token_mask=token_mask,
                                banks=banks)
    if "router" in lw:
        from ..parallel.mesh import AXIS_EXPERT
        from ..parallel.mesh_context import axis_size, current_mesh

        if (t == 1 and cfg.decode_gather_ffn
                and axis_size(current_mesh(), AXIS_EXPERT) == 1
                and 2 * b * cfg.experts_per_token <= cfg.n_experts):
            return moe_ffn_decode(cfg, h, lw), None
        return moe_ffn(cfg, h, lw, token_mask=token_mask,
                       keep_capacity=keep_capacity, no_drop=moe_no_drop)
    return dense_ffn(h, lw)


def forward_with_cache(params, tokens, cache: KVCache, start_pos,
                       cfg: "LlamaConfig | MoeConfig"):
    """Run T new tokens at absolute position ``start_pos``; returns logits
    for the LAST position and the updated cache. Used for both prefill
    (T = prompt length) and decode (T = 1)."""
    b, t = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    freqs_full = rope_freqs(cfg, cache.k.shape[2])
    q_pos = start_pos + jnp.arange(t)
    # static decision: only a from-zero prefill is pure causal self-attention
    causal_prefill = isinstance(start_pos, int) and start_pos == 0
    flash_prefill = causal_prefill and _flash_prefill_wanted(cfg, t)

    def body(carry, layer_inputs):
        h = carry
        lw, ck, cv = layer_inputs
        h, ck, cv = _layer_step(cfg, h, lw, ck, cv, q_pos, freqs_full,
                                flash_prefill=flash_prefill,
                                causal_prefill=causal_prefill)
        return h, (ck, cv)

    x, (new_k, new_v) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    from .quant import lm_head_dot
    logits = lm_head_dot(x[:, -1], params, cfg.dtype)
    return logits, KVCache(k=new_k, v=new_v)


def nucleus_mask(scaled: jax.Array, top_ps: jax.Array) -> jax.Array:
    """Top-p (nucleus) logit filter over the last axis: keep the smallest
    prefix of the probability-sorted vocab whose cumulative mass reaches
    ``top_ps`` (per row; 1.0 disables). The top-1 token always survives
    (its preceding mass is 0), so greedy/degenerate rows stay samplable.
    ``scaled`` is post-temperature logits; returns filtered logits."""
    probs = jax.nn.softmax(scaled, axis=-1)
    sp, si = lax.top_k(probs, probs.shape[-1])          # descending sort
    before = jnp.cumsum(sp, axis=-1) - sp               # mass strictly above
    keep_sorted = before < top_ps[..., None]
    rows = jnp.arange(scaled.shape[0])[:, None]
    keep = jnp.zeros(scaled.shape, bool).at[rows, si].set(keep_sorted)
    return jnp.where(keep, scaled, NEG_INF)


def sample_logits(logits: jax.Array, key: jax.Array, temperature: float,
                  top_k: Optional[int],
                  top_p: Optional[float] = None) -> jax.Array:
    """Greedy (temperature 0) or temperature/top-k/top-p sampling over the
    last axis. One definition shared by the scanned ``generate`` path and
    the continuous-batching engine (``serve.engine``) so their sampling
    semantics can never diverge."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temperature
    if top_k is not None:
        kth = lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    if top_p is not None and top_p < 1.0:
        scaled = nucleus_mask(scaled, jnp.full(scaled.shape[:-1], top_p,
                                               jnp.float32))
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "temperature",
                                  "top_k", "top_p"))
def generate(params, prompt: jax.Array, cfg: "LlamaConfig | MoeConfig",
             max_new_tokens: int = 64, temperature: float = 0.0,
             top_k: Optional[int] = None,
             rng: Optional[jax.Array] = None,
             top_p: Optional[float] = None) -> jax.Array:
    """Greedy (temperature=0) or sampled generation.

    prompt: (B, T_prompt) int32 → (B, T_prompt + max_new_tokens). One compile
    per (shape, config); prefill and all decode steps inside.
    """
    b, t_prompt = prompt.shape
    max_len = t_prompt + max_new_tokens
    cache = init_cache(cfg, b, max_len)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    logits, cache = forward_with_cache(params, prompt, cache, 0, cfg)

    def sample(logits, key):
        return sample_logits(logits, key, temperature, top_k, top_p)

    def step(carry, i):
        cache, tok, key = carry
        key, sub = jax.random.split(key)
        logits, cache = forward_with_cache(
            params, tok[:, None], cache, t_prompt + i, cfg)
        nxt = sample(logits, sub)
        return (cache, nxt, key), nxt

    # never reuse a consumed key: the first sample gets its own split
    rng, first_key = jax.random.split(rng)
    first = sample(logits, first_key)
    (_, _, _), toks = lax.scan(step, (cache, first, rng),
                               jnp.arange(max_new_tokens - 1))
    out = jnp.concatenate([prompt, first[:, None],
                           toks.transpose(1, 0)], axis=1)
    return out

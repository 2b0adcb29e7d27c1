"""Latent-attention (MLA) decoders with fine-grained experts: the language
model of Kimi-VL-A3B-Instruct (https://huggingface.co/moonshotai/
Kimi-VL-A3B-Instruct/blob/main/config.json; the DeepSeek-V2/V3 layer).
Text only: the vision tower and its projector are not here.

What differs from ``models.llama`` / ``models.moe``, each handed to
``models.block.decoder_block`` as an operation and not as a copy of it:

- **Attention.** The query is ``[q_nope (Hn) ; q_pe (Hr)]`` a head, only
  ``q_pe`` rotated. Keys and values come from ONE compressed vector a token,
  ``c = rmsnorm(h·W_kva[:, :R])`` (R = ``kv_lora_rank``), and one rope key
  shared by all heads, ``k_pe = rope(h·W_kva[:, R:])``; ``[k_nope_i ; v_i] =
  c·W_kvb`` a head. What is cached a token and layer is the row
  ``[c ; k_pe]`` (R + Hr values, no head axis): ``serve.latent_cache``.
  :func:`expanded_mix` attends over the expanded heads (training, the plain
  forward, a prompt's prefill); :func:`absorbed_attention` is the same
  mathematics with ``W_kvb`` folded into the query and the output, over
  cached rows (decode): ``q'_i = q_nope_i·W_kvb[K, i]ᵀ``, scores
  ``q'_i·c + q_pe_i·k_pe``, ``o_i = (Σ p·c)·W_kvb[V, i]``.
- **The expert layer.** ``sigmoid`` scores over all experts; the chosen are
  the top K of ``score + router_bias`` (``noaux_tc``; one group, so
  group-limited selection is the identity), the weights are the UNBIASED
  scores of the chosen, normalised and scaled by ``routed_scaling_factor``.
  No capacity, so no token is dropped and padding claims nothing
  (:func:`moe_ffn_dropless`). One algorithm in two ranges of rows, by the
  row count alone. Up to ``DENSE_ROWS_MAX`` rows (a decode step's slots, a
  short prompt) every row goes through every expert that got a token, with
  a gate of zero where the row did not choose it: on the TPU backend ONE
  kernel call that walks those experts and streams only their banks
  (``ops.moe_experts``: about a fifth of a layer's experts get no token at
  16 rows), elsewhere, and for experts the kernel cannot tile, plain einsums
  over every bank (also the kernel's reference and its backward pass). More
  rows are sorted by expert and each expert multiplies its own run
  (``jax.lax.ragged_dot``, which XLA:TPU lowers to its grouped-matmul
  kernel). Shared experts are one dense SwiGLU added beside them.
- **The stack.** ``first_dense_layers`` dense layers, then expert layers:
  two stacked leaves, ``params["dense_layers"]`` and ``params["layers"]``,
  each scanned (:meth:`MlaMoeConfig.layer_stacks`, which ``models.block.
  layer_stacks`` asks); the routed experts' weights sit under
  ``params["layers"]["banks"]`` and are read in place from the whole stack,
  never sliced a layer.

RoPE pairs are (2i, 2i+1) as ``block.apply_rope`` has them; the published
code permutes the rope columns before a half-split rotation, which is a
relabelling of columns of ``wq`` / ``wkv_a``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..exceptions import UnsupportedMechanismError
from ..ops.moe_experts import moe_experts, moe_experts_auto
from .block import (apply_rope, decoder_block, dense_ffn, layer_stacks,
                    rmsnorm, with_banks)
from .llama import rope_freqs
from .quant import wdot

NEG_INF = -1e30

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
# at 8 / 16 / 32 / 64 rows here. Einsums against the kernel (PR 34) at 8 /
# 16 / 32 / 64 / 128 / 256 / 512 rows: 1.50 / 0.83, 1.51 / 1.20, 1.51 /
# 1.46, 1.51 / 1.50, 1.79 / 1.51, 2.02 / 1.54, 3.62 / 2.98: the kernel is
# no slower at any, so no second threshold chooses between them.
#
# Beyond, (row, choice) pairs sorted by expert go through XLA's grouped
# matmul. At 16 / 256 / 512 / 1,024 rows the einsums take 1.49 / 2.01 / 3.61
# / 7.35 and the grouped matmul over the whole stack 2.70 / 5.53 / 5.84 /
# 6.39 (PR 33); the kernel was not read at 1,024. The cell runs both ranges:
# a decode step's 16 rows and the buckets 256 / 512, and the bucket 1,024.
DENSE_ROWS_MAX = 512


@dataclass(frozen=True)
class MlaMoeConfig:
    """Published keys under the program's names (``config.json`` key in
    brackets where it differs)."""
    vocab_size: int = 163840
    dim: int = 2048                     # hidden_size
    n_layers: int = 27                  # num_hidden_layers
    n_heads: int = 16                   # num_attention_heads
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 11264                # intermediate_size (dense layers)
    moe_ffn_dim: int = 1408             # moe_intermediate_size
    n_experts: int = 64                 # n_routed_experts
    experts_per_token: int = 6          # num_experts_per_tok
    n_shared_experts: int = 2
    first_dense_layers: int = 1         # first_k_dense_replace
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    max_seq_len: int = 8192
    rope_theta: float = 800000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False

    # what the serving engine keys its cache on (``serve.engine._cache_ops``)
    cache_kind = "latent"
    # what ``generate._flash_prefill_wanted`` reads: the expanded heads (q/k
    # of 192, v of 128) attend through XLA einsums, not the flash kernel
    attn_impl = "xla"

    def __post_init__(self):
        for name, want in (("n_group", 1), ("topk_group", 1),
                           ("q_lora_rank", None), ("scoring_func", "sigmoid"),
                           ("topk_method", "noaux_tc")):
            if getattr(self, name) != want:
                raise UnsupportedMechanismError(
                    f"{name}={getattr(self, name)!r}", "latent",
                    f"models.mla implements {name}={want!r} only")
        if not 0 < self.first_dense_layers < self.n_layers:
            raise ValueError("first_dense_layers must leave at least one "
                             "dense and one expert layer")

    @property
    def rope_dim(self) -> int:
        """The rotated width (``llama.rope_freqs`` reads it)."""
        return self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Width of a cached row: the compressed vector and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_dense_layers

    @property
    def routed_tally_shape(self) -> tuple:
        """What ``moe_ffn_dropless`` tallies, stacked over the expert layers
        (``serve.engine.EngineStats.moe_routed_pairs`` / ``moe_expert_hits``)."""
        return (self.n_moe_layers, 2, self.n_experts)

    def layer_stacks(self, params: Dict[str, Any]):
        """This family's runs of like layers, as ``models.block.
        layer_stacks`` hands them to every stack: the leading dense layers,
        then the expert layers with their routed experts' ``banks`` kept out
        of the scanned leaves. A kernel's operand is materialised, so a
        slice a layer would be a copy of the layer's banks a layer (1.1 GB
        at 64 experts of 2048 x 1408): the banks go to the expert layer
        whole, with the layer's index (``block.with_banks``)."""
        out, start = [], 0
        for name in ("dense_layers", "layers"):
            stack = params[name]
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            scanned = {k: v for k, v in stack.items() if k != "banks"}
            out.append((scanned, stack.get("banks"), start, n))
            start += n
        return out

    @classmethod
    def tiny(cls, **kw) -> "MlaMoeConfig":
        d = dict(vocab_size=256, dim=64, n_layers=3, n_heads=4,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, ffn_dim=128, moe_ffn_dim=32, n_experts=8,
                 experts_per_token=3, n_shared_experts=1, max_seq_len=128)
        d.update(kw)
        return cls(**d)

    def param_count(self) -> int:
        d, nh = self.dim, self.n_heads
        attn = (d * nh * self.qk_head_dim + d * self.latent_dim
                + self.kv_lora_rank
                + self.kv_lora_rank * nh * (self.qk_nope_head_dim
                                            + self.v_head_dim)
                + nh * self.v_head_dim * d + 2 * d)
        fm = self.moe_ffn_dim
        moe = (3 * d * fm * (self.n_experts + self.n_shared_experts)
               + d * self.n_experts + self.n_experts)
        return (self.vocab_size * d * 2 + d
                + self.first_dense_layers * (attn + 3 * d * self.ffn_dim)
                + self.n_moe_layers * (attn + moe))


def mla_moe_init(rng: jax.Array, cfg: MlaMoeConfig) -> Dict[str, Any]:
    """The param pytree: ``dense_layers`` and ``layers`` stacked on dim 0."""
    d, nh, E = cfg.dim, cfg.n_heads, cfg.n_experts
    fm, fs = cfg.moe_ffn_dim, cfg.n_shared_experts * cfg.moe_ffn_dim
    R = cfg.kv_lora_rank
    k = iter(jax.random.split(rng, 32))

    def init(shape, fan_in, dtype=None):
        w = jax.random.normal(next(k), shape, jnp.float32) / jnp.sqrt(fan_in)
        return w.astype(dtype or cfg.dtype)

    def attn(L):
        return {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "wq": init((L, d, nh * cfg.qk_head_dim), d),
            "wkv_a": init((L, d, cfg.latent_dim), d),
            "kv_norm": jnp.ones((L, R), jnp.float32),
            "wkv_b": init((L, R, nh * (cfg.qk_nope_head_dim
                                       + cfg.v_head_dim)), R),
            "wo": init((L, nh * cfg.v_head_dim, d), nh * cfg.v_head_dim),
            "ffn_norm": jnp.ones((L, d), jnp.float32)}

    def swiglu(lead, f):
        return {"w_gate": init((*lead, d, f), d), "w_up": init((*lead, d, f), d),
                "w_down": init((*lead, f, d), f)}

    Ld, Lm = cfg.first_dense_layers, cfg.n_moe_layers
    return {
        "embed": init((cfg.vocab_size, d), d),
        "dense_layers": {**attn(Ld), **swiglu((Ld,), cfg.ffn_dim)},
        "layers": {
            **attn(Lm),
            "router": init((Lm, d, E), d, jnp.float32),
            # a zero bias would leave the correction path untested
            "router_bias": 0.01 * jax.random.normal(next(k), (Lm, E),
                                                    jnp.float32),
            "banks": swiglu((Lm, E), fm),
            "shared": swiglu((Lm,), fs)},
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": init((d, cfg.vocab_size), d),
    }


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def mla_project(cfg: MlaMoeConfig, h: jax.Array, lw: Dict[str, Any],
                freqs: jax.Array):
    """The normed input (B, T, D) → ``q_nope`` (B, T, N, Hn), ``q_pe``
    (B, T, N, Hr) rotated, and the token's cache row ``[c ; k_pe]``
    (B, T, R + Hr). ``freqs`` as ``block.apply_rope`` takes them."""
    b, t, _ = h.shape
    R = cfg.kv_lora_rank
    with jax.named_scope("kt.mla.q"):
        q = wdot(h, lw["wq"]).reshape(b, t, cfg.n_heads, cfg.qk_head_dim)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_pe = apply_rope(q[..., cfg.qk_nope_head_dim:], freqs)
    with jax.named_scope("kt.mla.kv_latent"):
        kva = wdot(h, lw["wkv_a"])
        c = rmsnorm(kva[..., :R], lw["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(kva[..., None, R:], freqs)[:, :, 0]
        row = jnp.concatenate([c, k_pe], axis=-1)
    return q_nope, q_pe, row


def _kvb_heads(cfg: MlaMoeConfig, wkv_b: jax.Array):
    """``W_kvb`` (R, N·(Hn + Hv)) as its key part (R, N, Hn) and its value
    part (R, N, Hv)."""
    w = wkv_b.reshape(cfg.kv_lora_rank, cfg.n_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def expanded_mix(cfg: MlaMoeConfig, freqs: jax.Array) -> Callable:
    """The block's mixing operation over T tokens that attend only to
    themselves, causally (training, the plain forward, a from-zero prefill):
    keys and values expanded a head, softmax in float32. Plain XLA einsums:
    the flash kernel wants one width for q, k and v, and here they are
    192 / 192 / 128. Returns ``mix(h, lw, lora) -> (attn (B, T, N·Hv),
    rows (B, T, 1, R + Hr))``, the rows as a row-major prompt cache with
    one KV head holds them."""
    scale = cfg.qk_head_dim ** -0.5

    def mix(h, lw, lora):
        b, t, _ = h.shape
        q_nope, q_pe, row = mla_project(cfg, h, lw, freqs)
        R = cfg.kv_lora_rank
        with jax.named_scope("kt.mla.kv_latent"):
            kv = wdot(row[..., :R], lw["wkv_b"]).reshape(
                b, t, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
            k_nope, v = (kv[..., :cfg.qk_nope_head_dim],
                         kv[..., cfg.qk_nope_head_dim:])
        with jax.named_scope("kt.attention"):
            logits = (jnp.einsum("btnh,bsnh->bnts", q_nope, k_nope)
                      + jnp.einsum("btnh,bsh->bnts", q_pe, row[..., R:])
                      ).astype(jnp.float32) * scale
            mask = jnp.tril(jnp.ones((t, t), bool))
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            attn = jnp.einsum("bnts,bsnh->btnh", probs, v)
        return attn.reshape(b, t, -1), row[:, :, None]

    return mix


def absorbed_attention(cfg: MlaMoeConfig, q_nope: jax.Array, q_pe: jax.Array,
                       wkv_b: jax.Array, rows: jax.Array,
                       pos: jax.Array) -> jax.Array:
    """One new token a slot against its cached rows, ``W_kvb`` absorbed:
    q_nope (B, N, Hn), q_pe (B, N, Hr), rows (B, S, R + Hr), pos (B,) the
    token's position (rows past it are masked) → (B, N·Hv). The masked-einsum
    path; the values are the rows' first R columns, taken from the small
    product and not from the rows (a slice of the rows would copy them)."""
    wk, wv = _kvb_heads(cfg, wkv_b)
    with jax.named_scope("kt.mla.absorb"):
        q_abs = jnp.einsum("bnh,rnh->bnr", q_nope, wk)
        qf = jnp.concatenate([q_abs, q_pe], axis=-1)         # (B, N, R+Hr)
    with jax.named_scope("kt.attention"):
        logits = jnp.einsum("bnc,bsc->bns", qf, rows).astype(
            jnp.float32) * cfg.qk_head_dim ** -0.5
        mask = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(rows.dtype)
        u = jnp.einsum("bns,bsc->bnc", probs, rows)[..., :cfg.kv_lora_rank]
    with jax.named_scope("kt.mla.absorb"):
        out = jnp.einsum("bnr,rnh->bnh", u, wv)
    return out.reshape(out.shape[0], -1)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def route(cfg: MlaMoeConfig, h: jax.Array, lw: Dict[str, Any]):
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


def _routed_experts(cfg: MlaMoeConfig, x: jax.Array, w: jax.Array,
                    idx: jax.Array, sizes: jax.Array, banks) -> jax.Array:
    """Σ_k w[m, k] · SwiGLU_{idx[m, k]}(x[m]) for rows x (M, D); ``idx`` may
    hold ``n_experts`` for a row that routes nowhere (its ``w`` is 0);
    ``sizes`` (E,): the pairs an expert got.
    ``banks``: (the run's whole stacked banks {w_gate, w_up (L, E, D, F),
    w_down (L, E, F, D)}, this layer's index in it). One algorithm by the
    row count (and the experts' shape and the backend, for the kernel): see
    ``DENSE_ROWS_MAX``."""
    E, K = cfg.n_experts, cfg.experts_per_token
    whole, layer = banks
    m, d = x.shape
    if m <= DENSE_ROWS_MAX:
        gates = jnp.einsum("mk,mke->me", w, jax.nn.one_hot(
            idx, E, dtype=w.dtype))
        bank = whole["w_gate"]
        if moe_experts_auto(d, bank.shape[-1], bank.dtype.itemsize):
            return _grouped_experts(x, gates, whole, layer, sizes)
        return _dense_experts(x, gates, whole, layer)
    # sorted (row, choice) pairs, an expert its own run; the other layers'
    # groups of the whole stack are empty
    order = jnp.argsort(idx.reshape(-1), stable=True)
    n_layers = whole["w_gate"].shape[0]
    groups = lax.dynamic_update_slice(
        jnp.zeros((n_layers * E,), jnp.int32), sizes, (layer * E,))
    bank = {k: v.reshape(n_layers * E, *v.shape[2:])
            for k, v in whole.items()}
    xs = x[order // K]
    act = (jax.nn.silu(lax.ragged_dot(xs, bank["w_gate"], groups))
           * lax.ragged_dot(xs, bank["w_up"], groups))
    ys = lax.ragged_dot(act, bank["w_down"], groups)
    # rows behind the last run belong to no expert: whatever is there
    ys = jnp.where((jnp.arange(ys.shape[0]) < jnp.sum(sizes))[:, None], ys, 0)
    pairs = ys[jnp.argsort(order)].reshape(m, K, d)
    return jnp.einsum("mk,mkd->md", w.astype(x.dtype), pairs)


def moe_ffn_dropless(cfg: MlaMoeConfig, h: jax.Array, lw: Dict[str, Any],
                     token_mask: Optional[jax.Array] = None, banks=None):
    """The expert layer over h (B, T, D) → (out, tally): route, the routed
    experts' weighted outputs (:func:`_routed_experts`: static shapes, no
    capacity), and the shared SwiGLU beside them. ``token_mask`` (B, T)
    marks real tokens (live slots): the others route nowhere, claim nothing
    and count nothing. ``banks``: (the run's whole stacked banks, this
    layer's index in the run) as a stack's scan hands them on
    (``block.with_banks``); without them the layer's own, ``lw["banks"]``.
    ``tally`` (2, E) int32: the routed pairs of real tokens an expert got in
    this call, and whether it got any (what ``EngineStats`` accumulates)."""
    b, t, d = h.shape
    E = cfg.n_experts
    if banks is None:
        banks = ({k: v[None] for k, v in lw["banks"].items()}, 0)
    x = h.reshape(b * t, d)
    with jax.named_scope("kt.moe.route"):
        w, idx = route(cfg, x, lw)                          # (M, K)
        if token_mask is not None:
            real = token_mask.reshape(b * t)
            idx = jnp.where(real[:, None], idx, E)
            w = jnp.where(real[:, None], w, 0.0)
        sizes = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(E)[None, :],
                        axis=0, dtype=jnp.int32)
    with jax.named_scope("kt.moe.experts"):
        out = _routed_experts(cfg, x, w, idx, sizes, banks)
    with jax.named_scope("kt.moe.shared"):
        out = out + dense_ffn(x, lw["shared"])[0]
    return out.reshape(b, t, d), jnp.stack([sizes, (sizes > 0).astype(
        jnp.int32)])


# ---------------------------------------------------------------------------
# the plain forward
# ---------------------------------------------------------------------------


def mla_moe_forward(params: Dict[str, Any], tokens: jax.Array,
                    cfg: MlaMoeConfig) -> jax.Array:
    """tokens (B, S) → logits (B, S, V) float32."""
    from .generate import ffn_block
    x = params["embed"][tokens].astype(cfg.dtype)
    mix = expanded_mix(cfg, rope_freqs(cfg, tokens.shape[1]))

    def body(whole, h, layer):
        lw, l = layer
        h, _, _ = decoder_block(cfg, h, lw, mix, with_banks(
            partial(ffn_block, cfg), whole, l))
        return h, None

    for stack, whole, _start, n in layer_stacks(cfg, params):
        run = partial(body, whole)
        x, _ = lax.scan(jax.checkpoint(run) if cfg.remat else run, x,
                        (stack, jnp.arange(n)))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)

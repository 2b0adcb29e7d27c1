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

- **What a config may add** (GLM-5, ``glm_moe_dsa``; each path is taken by
  what the config holds, none by a switch). A **query rank**
  (``q_lora_rank``): ``c_q = rmsnorm(h·W_qa)``, ``q = c_q·W_qb``. A
  **learned sparse-attention indexer** (``index_n_heads`` > 0; the
  "lightning indexer" of DeepSeek-V3.2-Exp): a small key a token,
  ``k_I = rope(layernorm(h·W_Ik))``, cached in a leaf of its own beside the
  latent rows; queries ``q_I = rope(c_q·W_Iq)`` a head and head weights
  ``w = h·W_Iw / sqrt(heads · width)``; the score of a (query, key) pair is
  ``Σ_j w_j · relu(q_I_j · k_I)`` in float32, and a query attends to the
  ``index_topk`` causal keys of largest score (all of them while there are
  no more). A prompt applies the selection as a mask, made a tile of queries
  at a time so that a score tile stays bounded, and on the TPU attends
  through ONE flash kernel call that takes the mask as an operand
  (:func:`expanded_mix`, ``ops.mla_prefill_attention``); decode scores every
  cached key, picks, gathers the chosen latent rows and attends over those
  alone (:func:`select_rows`, ``serve.latent_cache.grid_mix``). A **share of
  the experts** (``held``: first and count): the router still scores all
  ``n_experts``, the layer keeps banks for the held ones only and adds only
  their products; a (token, choice) pair routed to an absent expert adds
  nothing here (another chip adds it; nothing stands in for the exchange).

RoPE pairs are (2i, 2i+1) as ``block.apply_rope`` has them; the published
code permutes the rope columns before a half-split rotation, which is a
relabelling of columns of ``wq`` / ``wkv_a``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

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

# A layer that holds a SHARE of its experts gets about held/n_experts of the
# (row, choice) pairs, and which is not known at trace time: beyond
# DENSE_ROWS_MAX rows the pairs sorted by held expert (the absent ones' last)
# go through the grouped matmul this many at a time, and a chunk past the
# last held pair is skipped, so time and temporaries follow the pairs that
# are there (8,192 rows x 8 choices, 16 of 256 held: 65,536 sorted pairs of
# which about 4,096 are held, one chunk).
HELD_PAIRS_CHUNK = 4096

# A prompt's attention materialises float32 scores (heads, queries, keys).
# Up to this many bytes they are one array (every configuration without an
# indexer at its buckets: 16 x 1,024^2 x 4 = 64 MB); beyond, the queries go a
# tile at a time, the largest power of two of them under the bound (64 heads
# at 8,192 keys: 256 queries, 0.5 GB, where the whole is 17 GB).
SCORE_TILE_BYTES = 512 * 2 ** 20

# A tiled prompt's queries go in this many runs of tiles, each over the keys
# up to the run's last query and no further: 4 runs do 10/16 of the square's
# work where the causal triangle is 8.5/16 (more runs, more compiled bodies).
KEY_EXTENT_RUNS = 4

# eps of the LayerNorm on the indexer's key (DeepSeek-V3.2-Exp's inference
# code; the published config.json does not carry it)
INDEX_NORM_EPS = 1e-6


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
    # learned sparse attention: the indexer's heads (0: none), their width,
    # and the keys a query attends to
    index_n_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 2048
    # this chip's share of the routed experts, (first, count) of
    # ``n_experts`` (which stays the router's width); None: all of them
    held: Optional[Tuple[int, int]] = None

    # what the serving engine keys its cache on (``serve.engine._cache_ops``)
    cache_kind = "latent"
    # what ``generate._flash_prefill_wanted`` reads: the expanded heads (q/k
    # and v of their own widths) never take ``ops.attention``'s kernel; a
    # prompt too long for one score array takes ``ops.mla_prefill_attention``
    # (``expanded_mix``)
    attn_impl = "xla"

    def __post_init__(self):
        for name, want in (("n_group", 1), ("topk_group", 1),
                           ("scoring_func", "sigmoid"),
                           ("topk_method", "noaux_tc")):
            if getattr(self, name) != want:
                raise UnsupportedMechanismError(
                    f"{name}={getattr(self, name)!r}", "latent",
                    f"models.mla implements {name}={want!r} only")
        if not 0 < self.first_dense_layers < self.n_layers:
            raise ValueError("first_dense_layers must leave at least one "
                             "dense and one expert layer")
        if self.indexed and not self.q_lora_rank:
            raise ValueError("the indexer's queries come from the query's "
                             "compressed vector: index_n_heads needs "
                             "q_lora_rank")
        if self.indexed and not (self.qk_rope_head_dim <= self.index_head_dim
                                 and self.index_topk > 0):
            raise ValueError("index_head_dim holds the rotated columns, and "
                             "index_topk is at least 1")
        if self.held is not None:
            first, count = self.held
            if not (0 <= first and 0 < count
                    and first + count <= self.n_experts):
                raise ValueError(f"held={self.held!r} is not a run of the "
                                 f"{self.n_experts} routed experts")

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
    def indexed(self) -> bool:
        """Does attention go through the indexer's selection (and the cache
        keep the indexer's keys: ``serve.latent_cache``)?"""
        return self.index_n_heads > 0

    @property
    def held_first(self) -> int:
        return 0 if self.held is None else self.held[0]

    @property
    def n_held(self) -> int:
        """The routed experts whose banks this chip keeps."""
        return self.n_experts if self.held is None else self.held[1]

    @property
    def routed_tally_shape(self) -> tuple:
        """What ``moe_ffn_dropless`` tallies, stacked over the expert layers,
        over the HELD experts (``serve.engine.EngineStats.moe_routed_pairs``
        / ``moe_expert_hits``)."""
        return (self.n_moe_layers, 2, self.n_held)

    @property
    def dsa_tally_shape(self) -> Optional[tuple]:
        """What the indexer's selection tallies a decode step, a layer: the
        rows scored and the rows selected (``EngineStats.dsa_rows_scored`` /
        ``dsa_rows_selected``); None without an indexer."""
        return (self.n_layers, 2) if self.indexed else None

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
        """The parameters this chip holds (the held experts' banks only)."""
        d, nh, qr = self.dim, self.n_heads, self.q_lora_rank
        query = (d * nh * self.qk_head_dim if not qr
                 else d * qr + qr + qr * nh * self.qk_head_dim)
        attn = (query + d * self.latent_dim
                + self.kv_lora_rank
                + self.kv_lora_rank * nh * (self.qk_nope_head_dim
                                            + self.v_head_dim)
                + nh * self.v_head_dim * d + 2 * d)
        if self.indexed:
            hi, di = self.index_n_heads, self.index_head_dim
            attn += qr * hi * di + d * di + 2 * di + d * hi
        fm = self.moe_ffn_dim
        moe = (3 * d * fm * (self.n_held + self.n_shared_experts)
               + d * self.n_experts + self.n_experts)
        return (self.vocab_size * d * 2 + d
                + self.first_dense_layers * (attn + 3 * d * self.ffn_dim)
                + self.n_moe_layers * (attn + moe))


def mla_moe_init(rng: jax.Array, cfg: MlaMoeConfig) -> Dict[str, Any]:
    """The param pytree: ``dense_layers`` and ``layers`` stacked on dim 0.
    With a query rank ``wq`` is ``wq_a`` / ``q_norm`` / ``wq_b``; with an
    indexer every layer also has ``idx_wq`` / ``idx_wk`` / ``idx_k_norm`` /
    ``idx_k_bias`` / ``idx_w``; ``banks`` hold the held experts only."""
    d, nh, E = cfg.dim, cfg.n_heads, cfg.n_experts
    fm, fs = cfg.moe_ffn_dim, cfg.n_shared_experts * cfg.moe_ffn_dim
    R, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    k = iter(jax.random.split(rng, 32))

    def init(shape, fan_in, dtype=None):
        w = jax.random.normal(next(k), shape, jnp.float32) / jnp.sqrt(fan_in)
        return w.astype(dtype or cfg.dtype)

    def attn(L):
        query = ({"wq": init((L, d, nh * cfg.qk_head_dim), d)} if not qr else
                 {"wq_a": init((L, d, qr), d),
                  "q_norm": jnp.ones((L, qr), jnp.float32),
                  "wq_b": init((L, qr, nh * cfg.qk_head_dim), qr)})
        out = {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            **query,
            "wkv_a": init((L, d, cfg.latent_dim), d),
            "kv_norm": jnp.ones((L, R), jnp.float32),
            "wkv_b": init((L, R, nh * (cfg.qk_nope_head_dim
                                       + cfg.v_head_dim)), R),
            "wo": init((L, nh * cfg.v_head_dim, d), nh * cfg.v_head_dim),
            "ffn_norm": jnp.ones((L, d), jnp.float32)}
        if cfg.indexed:
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            out.update({
                "idx_wq": init((L, qr, hi * di), qr),
                "idx_wk": init((L, d, di), d),
                "idx_k_norm": jnp.ones((L, di), jnp.float32),
                "idx_k_bias": jnp.zeros((L, di), jnp.float32),
                "idx_w": init((L, d, hi), d)})
        return out

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
            "banks": swiglu((Lm, cfg.n_held), fm),
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
    (B, T, N, Hr) rotated, the token's cache row ``[c ; k_pe]``
    (B, T, R + Hr), and the query's compressed vector ``c_q`` (B, T, Qr)
    where the config has a query rank (else None; the indexer's queries come
    from it). ``freqs`` as ``block.apply_rope`` takes them."""
    b, t, _ = h.shape
    R = cfg.kv_lora_rank
    with jax.named_scope("kt.mla.q"):
        cq = None
        if cfg.q_lora_rank:
            cq = rmsnorm(wdot(h, lw["wq_a"]), lw["q_norm"], cfg.norm_eps)
            q = wdot(cq, lw["wq_b"])
        else:
            q = wdot(h, lw["wq"])
        q = q.reshape(b, t, cfg.n_heads, cfg.qk_head_dim)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_pe = apply_rope(q[..., cfg.qk_nope_head_dim:], freqs)
    with jax.named_scope("kt.mla.kv_latent"):
        kva = wdot(h, lw["wkv_a"])
        c = rmsnorm(kva[..., :R], lw["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(kva[..., None, R:], freqs)[:, :, 0]
        row = jnp.concatenate([c, k_pe], axis=-1)
    return q_nope, q_pe, row, cq


def _kvb_heads(cfg: MlaMoeConfig, wkv_b: jax.Array):
    """``W_kvb`` (R, N·(Hn + Hv)) as its key part (R, N, Hn) and its value
    part (R, N, Hv)."""
    w = wkv_b.reshape(cfg.kv_lora_rank, cfg.n_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# -- the indexer ------------------------------------------------------------


def _rope_head(x: jax.Array, freqs: jax.Array, hr: int) -> jax.Array:
    """x (B, T, N, Di): its first ``hr`` columns rotated."""
    return jnp.concatenate([apply_rope(x[..., :hr], freqs), x[..., hr:]],
                           axis=-1)


def index_project(cfg: MlaMoeConfig, h: jax.Array, cq: jax.Array,
                  lw: Dict[str, Any], freqs: jax.Array):
    """The indexer's side of a token: its key ``k_I`` (B, T, Di), what the
    cache's second leaf keeps; its queries ``q_I`` (B, T, Hi, Di); and its
    head weights (B, T, Hi) float32, the two scalings folded in."""
    b, t, _ = h.shape
    hi, di, hr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("kt.dsa.index_keys"):
        k = wdot(h, lw["idx_wk"]).astype(jnp.float32)
        mean = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean((k - mean) ** 2, axis=-1, keepdims=True)
        k = ((k - mean) * lax.rsqrt(var + INDEX_NORM_EPS) * lw["idx_k_norm"]
             + lw["idx_k_bias"]).astype(h.dtype)
        k = _rope_head(k[:, :, None], freqs, hr)[:, :, 0]
    with jax.named_scope("kt.dsa.scores"):
        q = _rope_head(wdot(cq, lw["idx_wq"]).reshape(b, t, hi, di), freqs,
                       hr)
        w = jnp.einsum("btd,dh->bth", h, lw["idx_w"],
                       preferred_element_type=jnp.float32) * (hi * di) ** -0.5
    return q, k, w


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """I(t, s) = Σ_j w[t, j] · relu(q[t, j] · keys[s]), float32: q
    (B, T, Hi, Di), w (B, T, Hi), keys (B, S, Di) → (B, T, S). Not masked."""
    with jax.named_scope("kt.dsa.scores"):
        dots = jnp.einsum("bthd,bsd->bhts", q, keys,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bhts,bth->bts", jax.nn.relu(dots), w)


def _kth_largest(x: jax.Array, k: int) -> jax.Array:
    """The k-th largest value of each row of float32 x (..., S), as
    (..., 1), exact and without a sort: the order of floats is the order of
    their bit patterns once the negatives' are flipped, and the answer's 32
    bits are found one at a time, from the top, by counting the entries at or
    above a candidate. (XLA:TPU sorts for a top-k this wide; a prompt's
    selection needs only the threshold.)"""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def step(i, best):
        cand = best | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(u >= cand, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, best)

    best = lax.fori_loop(0, 32, step, jnp.zeros((*x.shape[:-1], 1),
                                                jnp.uint32))
    bits = jnp.where(best >> 31 == 1, best & jnp.uint32(0x7fffffff), ~best)
    return lax.bitcast_convert_type(bits, jnp.float32)


def select_mask(cfg: MlaMoeConfig, scores: jax.Array,
                causal: jax.Array) -> jax.Array:
    """Which keys a query attends to, as a mask (a prompt's form): scores
    (..., S) float32, ``causal`` the keys it may see → those among them with
    one of the ``index_topk`` largest scores; all of them while they are no
    more."""
    with jax.named_scope("kt.dsa.topk"):
        scores = jnp.where(causal, scores, NEG_INF)
        k = min(cfg.index_topk, scores.shape[-1])
        return causal & (scores >= _kth_largest(scores, k))


def select_rows(cfg: MlaMoeConfig, scores: jax.Array, pos: jax.Array):
    """Which cached rows a slot's new token attends to, as row numbers
    (decode's form): scores (B, S) float32 over every reserved row, pos (B,)
    the token's own row → (rows (B, k) int32, ok (B, k) bool) with
    k = min(index_topk, S): the rows up to ``pos`` of largest score, ``ok``
    false where there were fewer than k of them."""
    with jax.named_scope("kt.dsa.topk"):
        s = scores.shape[-1]
        scores = jnp.where(jnp.arange(s)[None, :] <= pos[:, None], scores,
                           NEG_INF)
        _, rows = lax.top_k(scores, min(cfg.index_topk, s))
        return rows, rows <= pos[:, None]


# -- a prompt: expanded heads -------------------------------------------------


def _query_tile(cfg: MlaMoeConfig, t: int) -> int:
    """Queries a tile of a prompt's attention over t keys: all of them while
    the float32 scores stay under ``SCORE_TILE_BYTES``, else the largest
    power of two that does and divides t."""
    rows = SCORE_TILE_BYTES // (4 * cfg.n_heads * t)
    if rows >= t:
        return t
    tile = 1 << max(rows, 1).bit_length() - 1
    while t % tile:
        tile //= 2
    return tile


def _tiles(x: jax.Array, tile: int) -> jax.Array:
    """(B, T, ...) → (T/tile, B, tile, ...)"""
    return jnp.moveaxis(x.reshape(x.shape[0], -1, tile, *x.shape[2:]), 1, 0)


def flash_prefill_supported(cfg: MlaMoeConfig, t: int) -> bool:
    """Whether a prompt of t rows takes the flash kernel
    (``ops.mla_prefill_attention``) where the kernel runs: where the float32
    scores of all its queries would not be one array (:func:`_query_tile`
    tiles them), in the kernel's 128-row blocks, a head's values a
    lane-aligned slice of the output row."""
    return (_query_tile(cfg, t) < t and t % 128 == 0
            and cfg.v_head_dim % 128 == 0)


def flash_prefill_auto(cfg: MlaMoeConfig, t: int) -> bool:
    """The choice :func:`expanded_mix` makes (as ``ops.attention.
    flash_auto``): the kernel on the TPU backend for a prompt it takes, the
    XLA einsums elsewhere. Decided from the backend and the score array's
    size alone; nothing is imported here, so a rank whose prompts fit one
    score array never loads the kernel."""
    return (jax.default_backend() == "tpu"
            and flash_prefill_supported(cfg, t))


def expanded_attention(q_nope, q_pe, k_nope, k_pe, v, allowed, scale):
    """Attention over expanded heads as XLA einsums: queries q_nope
    (B, Tq, N, Dn), q_pe (B, Tq, N, Dr) over keys k_nope (B, S, N, Dn), the
    rope key every head shares k_pe (B, S, Dr), and values v (B, S, N, Dv);
    ``allowed`` (B or 1, Tq, S) bool → (B, Tq, N, Dv). Scores and softmax in
    float32, the probabilities in v's dtype. Also what the flash kernel is
    held to and what its backward pass differentiates."""
    with jax.named_scope("kt.attention"):
        logits = (jnp.einsum("btnh,bsnh->bnts", q_nope, k_nope)
                  + jnp.einsum("btnh,bsh->bnts", q_pe, k_pe)
                  ).astype(jnp.float32) * scale
        logits = jnp.where(allowed[:, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bnts,bsnh->btnh", probs, v)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _flash_attention(q_nope, q_pe, k_nope, k_pe, v, allowed, scale, tile):
    """:func:`expanded_attention` over a whole prompt (T queries over their
    own T keys), ``allowed`` (B, T, T) int8 or None for causal: one call of
    the flash kernel, imported here. Its gradient is the XLA form's, ``tile``
    queries at a time."""
    from ..ops.mla_prefill_attention import mla_flash_prefill
    return mla_flash_prefill(q_nope, q_pe, k_nope, k_pe, v, allowed,
                             scale=scale)


def _flash_attention_fwd(q_nope, q_pe, k_nope, k_pe, v, allowed, scale,
                         tile):
    return (_flash_attention(q_nope, q_pe, k_nope, k_pe, v, allowed, scale,
                             tile), (q_nope, q_pe, k_nope, k_pe, v, allowed))


def _flash_attention_bwd(scale, tile, saved, ct):
    *xs, allowed = saved
    b, t = xs[0].shape[:2]

    def tiled(q_nope, q_pe, k_nope, k_pe, v):
        def one(a):
            qn, qp, q_at, sel = a
            mask = ((jnp.arange(t)[None, :] <= q_at[:, None])[None]
                    if sel is None else sel != 0)
            return expanded_attention(qn, qp, k_nope, k_pe, v, mask, scale)
        out = lax.map(jax.checkpoint(one), (
            _tiles(q_nope, tile), _tiles(q_pe, tile),
            jnp.arange(t).reshape(-1, tile),
            None if allowed is None else _tiles(allowed, tile)))
        return jnp.moveaxis(out, 0, 1).reshape(b, t, -1)

    _, pull = jax.vjp(tiled, *xs)
    return (*pull(ct), None)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def expanded_mix(cfg: MlaMoeConfig, freqs: jax.Array) -> Callable:
    """The block's mixing operation over T tokens that attend only to
    themselves, causally (training, the plain forward, a from-zero prefill):
    keys and values expanded a head, softmax in float32; with an indexer and
    more keys than ``index_topk``, each query over its selected keys only
    (the selection as a mask). Where the scores of all queries fit one array
    (every configuration without an indexer at its buckets), plain XLA
    einsums over it (:func:`expanded_attention`). Beyond (:func:`_query_tile`)
    the selection is made a tile of queries at a time, in
    ``KEY_EXTENT_RUNS`` runs of tiles that each stop at their own last key,
    and attention is, on the TPU backend, ONE call of the flash kernel
    (``ops.mla_prefill_attention``: q/k and v of their own widths, the rope
    key shared, the selection an int8 (B, T, T) operand; no score tile
    leaves the VMEM) and, elsewhere, the same einsums a tile at a time
    (:func:`flash_prefill_auto`).
    Returns ``mix(h, lw, lora) -> (attn (B, T, N·Hv), leaves)``, the leaves
    as a row-major prompt cache with one KV head holds them: the rows
    (B, T, 1, R + Hr) and, with an indexer, its keys (B, T, 1, Di)."""
    scale = cfg.qk_head_dim ** -0.5

    def mix(h, lw, lora):
        b, t, _ = h.shape
        q_nope, q_pe, row, cq = mla_project(cfg, h, lw, freqs)
        R = cfg.kv_lora_rank
        with jax.named_scope("kt.mla.kv_latent"):
            kv = wdot(row[..., :R], lw["wkv_b"]).reshape(
                b, t, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
            k_nope, v = (kv[..., :cfg.qk_nope_head_dim],
                         kv[..., cfg.qk_nope_head_dim:])
        leaves, index = (row[:, :, None],), None
        if cfg.indexed:
            q_idx, k_idx, w_idx = index_project(cfg, h, cq, lw, freqs)
            leaves += (k_idx[:, :, None],)
            if t > cfg.index_topk:        # else every causal key is selected
                index = (q_idx, w_idx)

        def allowed(q_at, index, upto):
            """Which of the keys 0 .. ``upto`` - 1 (static; none of the
            queries sees a later one) queries at positions ``q_at`` (Tq,)
            attend to: (1 or B, Tq, upto) bool."""
            mask = (jnp.arange(upto)[None, :] <= q_at[:, None])[None]
            if index is not None:
                mask = select_mask(
                    cfg, index_scores(*index, k_idx[:, :upto]), mask)
            return mask

        def attend(q_nope, q_pe, q_at, index, upto):
            return expanded_attention(
                q_nope, q_pe, k_nope[:, :upto], row[:, :upto, R:],
                v[:, :upto], allowed(q_at, index, upto), scale)

        tile = _query_tile(cfg, t)
        if tile == t:
            return attend(q_nope, q_pe, jnp.arange(t), index, t).reshape(
                b, t, -1), leaves

        def runs(fn, *rows):
            """``fn(*row_tiles, q_at, own_index, upto)`` a tile of queries at
            a time, in runs of tiles each over the keys up to its own last
            query (the causal triangle in KEY_EXTENT_RUNS steps) → stacked
            (B, T/tile, tile, ...)."""
            n, done = t // tile, []
            per = -(-n // KEY_EXTENT_RUNS)          # tiles a run
            for first in range(0, n, per):
                lo, hi = first * tile, min(n, first + per) * tile
                own = None if index is None or hi <= cfg.index_topk else \
                    tuple(_tiles(x[:, lo:hi], tile) for x in index)
                done.append(lax.map(
                    lambda a, hi=hi: fn(*a, hi),
                    (*(_tiles(x[:, lo:hi], tile) for x in rows),
                     jnp.arange(lo, hi).reshape(-1, tile), own)))
            return jnp.moveaxis(jnp.concatenate(done, axis=0), 0, 1)

        if not flash_prefill_auto(cfg, t):
            return runs(attend, q_nope, q_pe).reshape(b, t, -1), leaves
        sel = None
        if index is not None:
            sel = runs(lambda q_at, own, upto: jnp.pad(
                jnp.broadcast_to(allowed(q_at, own, upto), (b, tile, upto)),
                ((0, 0), (0, 0), (0, t - upto))).astype(jnp.int8))
            sel = sel.reshape(b, t, t)
        with jax.named_scope("kt.attention"):
            attn = _flash_attention(q_nope, q_pe, k_nope, row[..., R:], v,
                                    sel, scale, tile)
        return attn, leaves

    return mix


def absorbed_attention(cfg: MlaMoeConfig, q_nope: jax.Array, q_pe: jax.Array,
                       wkv_b: jax.Array, rows: jax.Array, pos: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """One new token a slot against its cached rows, ``W_kvb`` absorbed:
    q_nope (B, N, Hn), q_pe (B, N, Hr), rows (B, S, R + Hr), pos (B,) the
    token's position (rows past it are masked) → (B, N·Hv). ``mask`` (B, S)
    in place of ``pos`` where the rows are not the cache's in order (the
    indexer's gathered rows). The masked-einsum path; the values are the
    rows' first R columns, taken from the small product and not from the
    rows (a slice of the rows would copy them)."""
    wk, wv = _kvb_heads(cfg, wkv_b)
    with jax.named_scope("kt.mla.absorb"):
        q_abs = jnp.einsum("bnh,rnh->bnr", q_nope, wk)
        qf = jnp.concatenate([q_abs, q_pe], axis=-1)         # (B, N, R+Hr)
    with jax.named_scope("kt.attention"):
        logits = jnp.einsum("bnc,bsc->bns", qf, rows).astype(
            jnp.float32) * cfg.qk_head_dim ** -0.5
        if mask is None:
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


def _sorted_experts(cfg: MlaMoeConfig, x, w, idx, sizes, banks):
    """Σ_k w[m, k] · SwiGLU_{idx[m, k]}(x[m]) through XLA's grouped matmul:
    the (row, choice) pairs sorted by expert, an expert its own run; the
    other layers' groups of the whole stack are empty."""
    E, K = cfg.n_held, cfg.experts_per_token
    whole, layer = banks
    m, d = x.shape
    order = jnp.argsort(idx.reshape(-1), stable=True)
    n_layers = whole["w_gate"].shape[0]
    bank = {k: v.reshape(n_layers * E, *v.shape[2:])
            for k, v in whole.items()}

    def run(sel, sizes):
        """A stretch ``sel`` of the sorted pairs, of which expert e has
        ``sizes[e]`` in a row from its start → each pair's product."""
        groups = lax.dynamic_update_slice(
            jnp.zeros((n_layers * E,), jnp.int32), sizes, (layer * E,))
        xs = x[sel // K]
        act = (jax.nn.silu(lax.ragged_dot(xs, bank["w_gate"], groups))
               * lax.ragged_dot(xs, bank["w_up"], groups))
        ys = lax.ragged_dot(act, bank["w_down"], groups)
        # rows behind the last run belong to no expert: whatever is there
        return jnp.where((jnp.arange(ys.shape[0]) < jnp.sum(sizes))[:, None],
                         ys, 0)

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


def _routed_experts(cfg: MlaMoeConfig, x: jax.Array, w: jax.Array,
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


def moe_ffn_dropless(cfg: MlaMoeConfig, h: jax.Array, lw: Dict[str, Any],
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

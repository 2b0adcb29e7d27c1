"""Decoders of window and full attention layers with fine-grained experts:
Trinity-Mini (https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/
config.json, ``afmoe``).

A layer is ``models.block.decoder_block`` with one mixing operation
(:func:`afmoe_mix`) and one of two FFNs. With ``h = rmsnorm(x)``:

- **Attention**, grouped-query heads: ``q, k, v = h W_q, h W_k, h W_v`` a
  head, then a per-head RMSNorm of q and of k (``q_norm``, ``k_norm``). A
  layer whose ``layer_types`` entry is ``sliding_attention`` (a **window
  layer**) rotates q and k (RoPE, ``rope_theta``) and lets query i see the
  keys ``i - W < j <= i`` (W = ``sliding_window``: W keys, itself
  included); a ``full_attention`` layer uses NO position embedding and sees
  every earlier key. The heads' output is multiplied by
  ``sigmoid(h W_gate)`` (a projection of its own, D -> N·Hd) before the
  block's ``W_o``.
- **FFN**: the first ``num_dense_layers`` layers a dense SwiGLU of
  ``intermediate_size``; the others the expert layer of ``models.experts``:
  ``num_experts`` routed experts of ``moe_intermediate_size`` scored by a
  sigmoid, the top ``num_experts_per_tok`` of score + bias chosen, weighted
  by their unbiased scores normalised (``route_norm``) and scaled
  (``route_scale``), and ``num_shared_experts`` shared experts beside them.
- Each branch's output is RMS-normed before its residual add
  (``post_attn_norm``, ``post_ffn_norm``; ``decoder_block`` applies them
  where a layer has them), and the embedding is scaled by sqrt(hidden_size)
  (``mup_enabled``).

The per-head q/k norms, RoPE on window layers only, the output gate, the
norms after the branches and the embedding's scale are the published
modeling code's, with no config key of their own.

What a slot keeps differs by layer kind: a full layer's K/V rows for every
position, a window layer's for the last W only, as a ring
(``serve.window_cache``). Keys are stored rotated, so a ring's row order
does not matter to the softmax.

The weights are kept a run of like layers at a time (``params["runs"]``:
the same attention kind and the same FFN; :meth:`AfmoeConfig.layer_stacks`),
each scanned whole, an expert run's banks read in place.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..exceptions import UnsupportedMechanismError
from .block import apply_rope, decoder_block, embed, rmsnorm, with_banks
from .llama import rope_freqs
from .quant import lm_head_dot, wdot

NEG_INF = -1e30
WINDOW, FULL = "sliding_attention", "full_attention"

TRINITY_MINI_LAYERS = ((WINDOW,) * 3 + (FULL,)) * 8


@dataclass(frozen=True)
class AfmoeConfig:
    """Published keys under the program's names (``config.json`` key in
    brackets where it differs)."""
    vocab_size: int = 200192
    dim: int = 2048                     # hidden_size
    n_heads: int = 32                   # num_attention_heads
    n_kv_heads: int = 4                 # num_key_value_heads
    head_dim: int = 128
    ffn_dim: int = 6144                 # intermediate_size (dense layers)
    moe_ffn_dim: int = 1024             # moe_intermediate_size
    n_experts: int = 128                # num_experts
    experts_per_token: int = 8          # num_experts_per_tok
    n_shared_experts: int = 1           # num_shared_experts
    layer_types: Tuple[str, ...] = TRINITY_MINI_LAYERS
    num_dense_layers: int = 2
    sliding_window: int = 2048
    route_scale: float = 2.826
    route_norm: bool = True
    score_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    mup_enabled: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5              # rms_norm_eps
    dtype: Any = jnp.bfloat16

    # what the serving engine keys its cache on (``serve.engine._cache_ops``)
    cache_kind = "window"
    # what ``generate._flash_prefill_wanted`` reads: a prompt's attention
    # takes the flash kernel where the shape allows, a window as its operand
    attn_impl = "auto"
    # what a decode step tallies (``serve.engine._tally_shapes``): the K/V
    # rows the window layers and the full layers read
    attn_rows_tally_shape = (2,)
    # the expert layer (``models.experts``) holds every expert
    held = None
    held_first = 0

    def __post_init__(self):
        kinds = set(self.layer_types)
        if not kinds <= {WINDOW, FULL}:
            raise UnsupportedMechanismError(
                f"layer_types {sorted(kinds)}", "window",
                f"models.afmoe runs {WINDOW!r} and {FULL!r} layers")
        for name, want in (("score_func", "sigmoid"), ("n_group", 1),
                           ("topk_group", 1)):
            if getattr(self, name) != want:
                raise UnsupportedMechanismError(
                    f"{name}={getattr(self, name)!r}", "window",
                    f"models.afmoe implements {name}={want!r} only")
        if kinds != {WINDOW, FULL}:
            raise ValueError("a window cache holds window and full layers: "
                             "layer_types needs both")
        if not 0 <= self.num_dense_layers < self.n_layers:
            raise ValueError("num_dense_layers must leave an expert layer")
        if self.n_heads % self.n_kv_heads or self.sliding_window < 1:
            raise ValueError("n_kv_heads must divide n_heads, and the window "
                             "hold at least one key")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_window(self) -> int:
        return self.layer_types.count(WINDOW)

    @property
    def n_full(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.num_dense_layers

    # -- what the expert layer reads (``models.experts``) --------------------

    @property
    def n_held(self) -> int:
        return self.n_experts

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def routed_tally_shape(self) -> tuple:
        """What ``moe_ffn_dropless`` tallies, stacked over the expert layers
        (``serve.engine.EngineStats.moe_routed_pairs``)."""
        return (self.n_moe_layers, 2, self.n_experts)

    @property
    def embedding_multiplier(self) -> float:
        """sqrt(hidden_size) where ``mup_enabled`` (``block.embed``)."""
        return self.dim ** 0.5 if self.mup_enabled else 1

    @property
    def runs(self) -> Tuple[Tuple[str, str, int, int], ...]:
        """(attention kind, "dense" or "moe", index of the run's first layer
        among that attention kind's layers, length) for each run of like
        layers, in order."""
        out, seen = [], {WINDOW: 0, FULL: 0}
        for i, kind in enumerate(self.layer_types):
            ffn = "dense" if i < self.num_dense_layers else "moe"
            if out and out[-1][:2] == [kind, ffn]:
                out[-1][3] += 1
            else:
                out.append([kind, ffn, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(r) for r in out)

    def layer_stacks(self, params: Dict[str, Any]):
        """The runs as ``models.block.layer_stacks`` hands them to every
        stack, each a stack of its own in ``params["runs"]``, an expert
        run's ``banks`` kept out of the scanned leaves (``block.with_banks``).
        The index a run starts at is counted among ITS attention kind's
        layers, which is what a layer's index addresses in the cache. A
        window run's scanned leaves carry ``window``, an empty array whose
        presence is what a scanned body reads (:func:`is_window`): a layer's
        kind is static, its index is not."""
        out = []
        for stack, (kind, _, start, n) in zip(params["runs"], self.runs):
            scanned = {k: v for k, v in stack.items() if k != "banks"}
            if kind == WINDOW:
                scanned["window"] = jnp.zeros((n, 0), jnp.float32)
            out.append((scanned, stack.get("banks"), start, n))
        return out

    def attn_rows_read(self, pos: jax.Array, live: jax.Array) -> jax.Array:
        """The K/V rows a decode step's live slots read, (window layers,
        full layers) int32: ``min(pos + 1, W)`` a slot and window layer,
        ``pos + 1`` a slot and full layer."""
        pos = jnp.where(live, pos + 1, 0)
        return jnp.stack([self.n_window * jnp.sum(
            jnp.minimum(pos, self.sliding_window)),
            self.n_full * jnp.sum(pos)]).astype(jnp.int32)

    def prompt_pairs(self, t: int) -> Tuple[int, int]:
        """The query-key pairs a prompt of ``t`` tokens attends, (window
        layers, full layers): the band's, and the causal triangle's."""
        w = self.sliding_window
        band = t * (t + 1) // 2 if t <= w else w * (w + 1) // 2 + (t - w) * w
        return self.n_window * band, self.n_full * t * (t + 1) // 2

    @classmethod
    def tiny(cls, **kw) -> "AfmoeConfig":
        """A CPU test's size: a window of 8, heads wider than dim / n_heads
        as published, window and full runs interleaved so that each kind
        has two runs."""
        d = dict(vocab_size=256, dim=64, n_heads=4, n_kv_heads=2,
                 head_dim=32, ffn_dim=128, moe_ffn_dim=32, n_experts=8,
                 experts_per_token=3,
                 layer_types=(WINDOW, WINDOW, FULL, WINDOW, FULL),
                 num_dense_layers=1, sliding_window=8)
        d.update(kw)
        return cls(**d)

    def param_count(self) -> int:
        """Every parameter: matrices, norms, the router's bias."""
        d, hd = self.dim, self.head_dim
        q = self.n_heads * hd
        attn = 2 * d * q + q * d + 2 * d * self.n_kv_heads * hd + 2 * hd \
            + 4 * d
        moe = (d * self.n_experts + self.n_experts
               + 3 * d * self.moe_ffn_dim * (self.n_experts
                                             + self.n_shared_experts))
        return (2 * self.vocab_size * d + d
                + self.num_dense_layers * (attn + 3 * d * self.ffn_dim)
                + self.n_moe_layers * (attn + moe))


def is_window(lw: Dict[str, Any]) -> bool:
    """Is this layer's (or run's) weight dict a window layer's?"""
    return "window" in lw


def afmoe_init(rng: jax.Array, cfg: AfmoeConfig) -> Dict[str, Any]:
    """``embed``, ``runs`` (one stacked dict a run of like layers),
    ``final_norm`` and ``lm_head``. Matrices N(0, 1/fan_in), the router in
    float32, its bias N(0, 0.01^2) (a zero bias would leave the selection's
    correction untested), norm weights 1."""
    d, hd, E = cfg.dim, cfg.head_dim, cfg.n_experts
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    fm, fs = cfg.moe_ffn_dim, cfg.n_shared_experts * cfg.moe_ffn_dim
    keys = iter(jax.random.split(rng, 16 * len(cfg.runs) + 2))

    def init(shape, fan_in, dtype=None):
        w = jax.random.normal(next(keys), shape, jnp.float32) / jnp.sqrt(
            fan_in)
        return w.astype(dtype or cfg.dtype)

    def swiglu(lead, f):
        return {"w_gate": init((*lead, d, f), d),
                "w_up": init((*lead, d, f), d),
                "w_down": init((*lead, f, d), f)}

    def run(ffn, n):
        out = {name: jnp.ones((n, d), jnp.float32) for name in (
            "attn_norm", "post_attn_norm", "ffn_norm", "post_ffn_norm")}
        out.update(q_norm=jnp.ones((n, hd), jnp.float32),
                   k_norm=jnp.ones((n, hd), jnp.float32),
                   wq=init((n, d, q), d), wk=init((n, d, kv), d),
                   wv=init((n, d, kv), d), w_attn_gate=init((n, d, q), d),
                   wo=init((n, q, d), q))
        if ffn == "dense":
            out.update(swiglu((n,), cfg.ffn_dim))
        else:
            out.update(router=init((n, d, E), d, jnp.float32),
                       router_bias=0.01 * jax.random.normal(
                           next(keys), (n, E), jnp.float32),
                       shared=swiglu((n,), fs), banks=swiglu((n, E), fm))
        return out

    return {"embed": init((cfg.vocab_size, d), d),
            "runs": [run(ffn, n) for _, ffn, _, n in cfg.runs],
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": init((d, cfg.vocab_size), d)}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def afmoe_mix(cfg: AfmoeConfig, freqs: jax.Array, attend: Callable):
    """The block's mixing operation: q, k, v, their per-head norms, RoPE on
    a window layer (``freqs`` as ``block.apply_rope`` takes them), the
    caller's ``attend(q, k, v, window) -> (attn, cache)`` (``window``: is
    this a window layer, static), then the sigmoid gate. Returns
    ``mix(h, lw, lora) -> (gated attn (B, T, N·Hd), cache)``."""
    hd = cfg.head_dim

    def mix(h, lw, lora):
        del lora                   # the window cache kind refuses adapters
        b, t, _ = h.shape
        window = is_window(lw)
        with jax.named_scope("kt.attn.window" if window else "kt.attn.full"):
            with jax.named_scope("kt.qkv_rope"):
                q = wdot(h, lw["wq"]).reshape(b, t, -1, hd)
                k = wdot(h, lw["wk"]).reshape(b, t, -1, hd)
                v = wdot(h, lw["wv"]).reshape(b, t, -1, hd)
                q = rmsnorm(q, lw["q_norm"], cfg.norm_eps)
                k = rmsnorm(k, lw["k_norm"], cfg.norm_eps)
                if window:
                    q, k = apply_rope(q, freqs), apply_rope(k, freqs)
            attn, cache = attend(q, k, v, window)
            with jax.named_scope("kt.attn.gate"):
                gate = jax.nn.sigmoid(
                    wdot(h, lw["w_attn_gate"]).astype(jnp.float32))
                attn = (attn.reshape(b, t, -1).astype(jnp.float32)
                        * gate).astype(h.dtype)
        return attn, cache

    return mix


def band_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   window: Optional[int], scale: float) -> jax.Array:
    """T queries over their own T keys as masked XLA einsums: causal, and
    ``i - window < j`` where a window is given. q (B, T, N, Hd), k and v
    (B, T, NKV, Hd) → (B, T, N, Hd). Scores and softmax in float32, the
    probabilities in v's dtype (as the flash kernel rounds them)."""
    b, t, nh, hd = q.shape
    nkv = k.shape[2]
    qg = q.reshape(b, t, nkv, nh // nkv, hd)
    logits = jnp.einsum("btkgh,bskh->bkgts", qg, k).astype(
        jnp.float32) * scale
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (j > i - window)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgts,bskh->btkgh", probs, v).reshape(b, t, nh, hd)


def self_attention(cfg: AfmoeConfig, q, k, v, window: bool,
                   flash: bool) -> jax.Array:
    """A prompt's attention over itself, banded on a window layer: the flash
    kernel with the window as its operand where ``flash``
    (``generate._flash_prefill_wanted``), else :func:`band_attention`."""
    w = cfg.sliding_window if window else None
    scale = cfg.head_dim ** -0.5
    with jax.named_scope("kt.attention"):
        if flash:
            from ..ops.attention import flash_attention
            return flash_attention(q, k, v, causal=True, scale=scale,
                                   window=w)
        return band_attention(q, k, v, w, scale)


# ---------------------------------------------------------------------------
# the plain forward
# ---------------------------------------------------------------------------

def afmoe_forward(params: Dict[str, Any], tokens: jax.Array,
                  cfg: AfmoeConfig) -> jax.Array:
    """tokens (B, T) → logits (B, T, V) float32."""
    from .generate import _flash_prefill_wanted, ffn_block
    t = tokens.shape[1]
    flash = _flash_prefill_wanted(cfg, t)
    mix = afmoe_mix(cfg, rope_freqs(cfg, t), lambda q, k, v, window: (
        self_attention(cfg, q, k, v, window, flash), None))
    x = embed(params, tokens, cfg)

    def body(whole, h, layer):
        lw, l = layer
        h, _, _ = decoder_block(cfg, h, lw, mix, with_banks(
            partial(ffn_block, cfg), whole, l))
        return h, None

    for stack, whole, _, n in cfg.layer_stacks(params):
        x, _ = lax.scan(partial(body, whole), x, (stack, jnp.arange(n)))
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return lm_head_dot(x, params, cfg.dtype)


__all__ = ["AfmoeConfig", "afmoe_init", "afmoe_forward", "afmoe_mix",
           "band_attention", "self_attention", "is_window", "WINDOW", "FULL",
           "TRINITY_MINI_LAYERS"]

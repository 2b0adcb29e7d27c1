"""Mixtral-style sparse MoE decoder — BASELINE config 5 (expert parallelism).

GShard/Mesh-TF dispatch formulation (the TPU-native shape): top-k routing is
expressed as dense one-hot einsums with a capacity factor, so every tensor is
static-shaped and GSPMD inserts the expert all-to-alls automatically when the
expert-stacked FFN weights are sharded over the ``expert`` mesh axis
(``parallel.sharding.MOE_RULES``). No ragged ops, no host gather — the
dispatch/combine einsums run on the MXU.

Attention/norms/embeddings reuse the Llama blocks.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from .block import decoder_block, qkv_attend, rmsnorm
from .llama import LlamaConfig, rope_freqs, self_attend


@dataclass(frozen=True)
class MoeConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    max_seq_len: int = 8192
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_impl: str = "auto"
    router_aux_weight: float = 0.01
    # Decode-time fast path: gather only the K selected experts' weights per
    # token instead of streaming all E experts (see ``moe_ffn_decode``).
    # Auto-disabled at trace time when the ambient mesh (mesh_context) has a
    # live ``expert`` axis — a data-dependent gather along the sharded E axis
    # makes GSPMD all-gather the full expert weights to every chip each step,
    # far worse than the dispatch einsums. Set False to force the dispatch
    # path for expert-sharded meshes installed outside ``use_mesh``.
    decode_gather_ffn: bool = True
    # Opt-in for MoE inside pipeline stages WITH a context axis: routing and
    # expert capacity are then computed per local sequence chunk (S/cp
    # tokens) instead of the full sequence. Per-token top-k decisions are
    # identical; only overflow-drop behavior differs (capacity pressure is
    # per-chunk), so outputs match the full-sequence router exactly whenever
    # no expert overflows. The standard sequence-parallel MoE trade.
    context_chunked_routing: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MoeConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "MoeConfig":
        d = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 ffn_dim=128, n_experts=4, experts_per_token=2, max_seq_len=128)
        d.update(kw)
        return cls(**d)

    def _llama_view(self) -> LlamaConfig:
        return LlamaConfig(vocab_size=self.vocab_size, dim=self.dim,
                           n_layers=self.n_layers, n_heads=self.n_heads,
                           n_kv_heads=self.n_kv_heads, ffn_dim=self.ffn_dim,
                           max_seq_len=self.max_seq_len,
                           rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                           dtype=self.dtype, remat=self.remat,
                           attn_impl=self.attn_impl)

    def param_count(self) -> int:
        d, f, L, E = self.dim, self.ffn_dim, self.n_layers, self.n_experts
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        ffn = 3 * d * f * E
        router = d * E
        return self.vocab_size * d * 2 + L * (attn + ffn + router + 2 * d) + d


def moe_init(rng: jax.Array, cfg: MoeConfig) -> Dict[str, Any]:
    d, L, E, f = cfg.dim, cfg.n_layers, cfg.n_experts, cfg.ffn_dim
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    k = iter(jax.random.split(rng, 16))

    def init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    return {
        "embed": init(next(k), (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "wq": init(next(k), (L, d, nh * hd), d),
            "wk": init(next(k), (L, d, nkv * hd), d),
            "wv": init(next(k), (L, d, nkv * hd), d),
            "wo": init(next(k), (L, nh * hd, d), nh * hd),
            "ffn_norm": jnp.ones((L, d), jnp.float32),
            "router": init(next(k), (L, d, E), d).astype(jnp.float32),
            "experts": {
                "w_gate": init(next(k), (L, E, d, f), d),
                "w_up": init(next(k), (L, E, d, f), d),
                "w_down": init(next(k), (L, E, f, d), f),
            },
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": init(next(k), (d, cfg.vocab_size), d),
    }


def _route(cfg: MoeConfig, x: jax.Array, lw: Dict[str, jax.Array]):
    """Shared router: softmax over expert logits, top-k, renormalized gates
    (Mixtral renormalizes over the selected experts). One definition so the
    training dispatch and the decode gather can never desynchronize."""
    logits = x.astype(jnp.float32) @ lw["router"]            # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, cfg.experts_per_token)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    return probs, gate_vals, gate_idx


def moe_ffn(cfg: MoeConfig, x: jax.Array, lw: Dict[str, jax.Array],
            ep_axis=None, tp_axis=None, token_mask=None,
            keep_capacity=None, no_drop: bool = False):
    """Top-k MoE with capacity-bounded one-hot dispatch.

    x: (B, S, D) → (B, S, D), plus scalar aux loss for load balancing.

    Outside shard_map (default) the einsums carry full expert-stacked
    weights and GSPMD inserts the expert all-to-alls from ``MOE_RULES``.
    Inside shard_map (pipeline stages) pass ``ep_axis``/``tp_axis``:
    activations are replicated over the expert axis there, so each rank
    computes the (cheap) routing for all tokens, slices the dispatch/combine
    tensors down to its LOCAL experts, runs only those experts' FFNs (the
    FLOPs), and one psum over (expert, tensor) reassembles the output — no
    all-to-all needed in this layout. Expert counts come from the local
    weight shapes so the same body serves both paths.

    ``token_mask`` (B, S) bool marks REAL tokens: masked-out (padding)
    positions never claim an expert capacity slot and are excluded from the
    aux statistics. ``keep_capacity`` (traced scalar) overrides the
    overflow-drop THRESHOLD — the static buffer stays sized by the padded
    S, but drops happen at the capacity the real length implies. Together
    they make a right-padded batch route its real tokens bit-identically
    to the unpadded one — the property bucketed serving prefill
    (``serve.engine``) depends on. Without them every position is real and
    the threshold is the buffer size (training, where shapes are exact).

    ``no_drop`` (static) sizes the buffer to ``s`` slots per expert — the
    worst case, every token on one expert — so NO token can ever overflow:
    each routes exactly as it would alone (T=1 can't drop). That is what
    makes a multi-token verify window bit-match a sequence of single-step
    decodes (``serve.speculative``). Quadratic in ``s``, so only for small
    windows — never training. Overrides ``keep_capacity``.
    """
    b, s, d = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    if no_drop:
        capacity, keep_capacity = s, None
    else:
        capacity = max(1, int(cfg.capacity_factor * s * K / E))

    # named scopes are metadata on the ops (a device trace can group time by
    # them); they change nothing the compiled program does
    with jax.named_scope("kt.moe.route"):
        probs, gate_vals, gate_idx = _route(cfg, x, lw)

    # aux load-balancing loss (Switch-style): E * Σ_e fraction_e * prob_e
    # computed on top-1 assignments
    top1 = jnp.argmax(probs, axis=-1)
    if token_mask is None:
        frac = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32),
                        axis=(0, 1))
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))
    else:
        m = token_mask.astype(jnp.float32)                        # (B, S)
        denom = jnp.sum(m) + 1e-9
        frac = jnp.einsum("bse,bs->e",
                          jax.nn.one_hot(top1, E, dtype=jnp.float32),
                          m) / denom
        aux = E * jnp.sum(frac * (jnp.einsum("bse,bs->e", probs, m) / denom))

    with jax.named_scope("kt.moe.dispatch"):
        # position of each (token, k) inside its expert's capacity buffer
        expert_onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (B,S,K,E)
        if token_mask is not None:
            expert_onehot = expert_onehot * token_mask[:, :, None, None].astype(
                jnp.int32)
        flat = expert_onehot.reshape(b, s * K, E)
        pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(b, s, K, E)
        pos_in_expert = jnp.sum(pos_in_expert * expert_onehot, axis=-1)   # (B,S,K)
        keep = pos_in_expert < (capacity if keep_capacity is None
                                else jnp.minimum(keep_capacity, capacity))

        # dispatch (B,S,E,C) and combine (B,S,E,C) tensors
        cap_onehot = jax.nn.one_hot(pos_in_expert, capacity, dtype=x.dtype)  # (B,S,K,C)
        disp = jnp.einsum("bske,bskc->bsec",
                          (expert_onehot * keep[..., None]).astype(x.dtype),
                          cap_onehot)                                     # (B,S,E,C)
        comb = jnp.einsum("bsk,bske,bskc->bsec",
                          gate_vals.astype(x.dtype),
                          (expert_onehot * keep[..., None]).astype(x.dtype),
                          cap_onehot)

    if ep_axis is not None:
        # slice dispatch/combine down to this rank's local experts BEFORE
        # the expensive routing einsums (shape through a possibly-quantized
        # leaf — shard_map training paths always pass plain arrays)
        wg_leaf = lw["experts"]["w_gate"]
        # quantized leaves (int8 or int4) are dicts whose every array
        # keeps the leading expert dim — any value yields the count
        e_local = (next(iter(wg_leaf.values())) if isinstance(wg_leaf, dict)
                   else wg_leaf).shape[0]
        start = lax.axis_index(ep_axis) * e_local
        disp = lax.dynamic_slice_in_dim(disp, start, e_local, axis=2)
        comb = lax.dynamic_slice_in_dim(comb, start, e_local, axis=2)

    # serving may hand us an int8 expert bank (models.quant): convert at
    # the einsums — the stream reads int8 from HBM either way
    from .quant import dequant
    experts = {k: dequant(v, x.dtype) for k, v in lw["experts"].items()}

    with jax.named_scope("kt.moe.dispatch"):
        # route tokens to expert buffers: (E, B, C, D)
        expert_in = jnp.einsum("bsec,bsd->ebcd", disp, x)
    with jax.named_scope("kt.moe.experts"):
        # batched expert SwiGLU over the E axis (sharded over "expert")
        h = jax.nn.silu(
            jnp.einsum("ebcd,edf->ebcf", expert_in, experts["w_gate"])) \
            * jnp.einsum("ebcd,edf->ebcf", expert_in, experts["w_up"])
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, experts["w_down"])
    with jax.named_scope("kt.moe.combine"):
        out = jnp.einsum("bsec,ebcd->bsd", comb, expert_out)
    reduce = tuple(a for a in (ep_axis, tp_axis) if a is not None)
    if reduce:
        out = lax.psum(out, reduce)
    return out, aux


def moe_prefill_keep_capacity(cfg, true_len):
    """Overflow-drop threshold for a prefill of ``true_len`` REAL tokens
    riding a longer padded bucket (None for dense configs): the value
    ``moe_ffn``'s native ``capacity`` would take at the unpadded length, so
    bucketed serving prefill (``serve.engine``) and speculative prompt
    ingest (``serve.speculative``) route bit-identically to a solo unpadded
    run. Pass as ``keep_capacity``; the static buffer stays bucket-sized."""
    kc = getattr(cfg, "capacity_factor", None)
    if kc is None:
        return None
    return jnp.maximum(1, jnp.floor(
        kc * true_len * cfg.experts_per_token / cfg.n_experts
    ).astype(jnp.int32))


def moe_ffn_decode(cfg: MoeConfig, x: jax.Array, lw: Dict[str, jax.Array]):
    """Decode-specialized top-k MoE: gather the K chosen experts' weights per
    token and run only those FFNs.

    The training path (``moe_ffn``) streams all E experts' weights from HBM
    every call — right when tokens cover most experts, pure waste at decode
    (T=1, small B) where only B*K expert FFNs have any work. Here the weight
    traffic is B*T*K expert matrices instead of E. No aux loss: nothing is
    training.

    Callers must gate on T == 1: with a single token per sequence the K
    chosen experts can never overflow a capacity slot, so this is bit-
    equivalent to the dispatch path; at T > 1 it would silently skip the
    capacity-drop semantics. Keep ``cfg.decode_gather_ffn`` off for
    expert-sharded serving (see its comment).

    x: (B, T, D) → (B, T, D).
    """
    _, gate_vals, gate_idx = _route(cfg, x, lw)              # (B, T, K)

    def gather_expert(leaf):
        """Gather the K chosen experts' matrices; for an int8 bank, gather
        int8 + scales FIRST and dequantize only the gathered slices — a
        full-bank dequant before the gather would materialize the bf16
        bank every step and invert the quantization bandwidth win."""
        from .quant import Q4KEY, QKEY, is_quantized
        if isinstance(leaf, dict) and Q4KEY in leaf:
            # the nibble-packed layout can't be gather-indexed per expert
            # without unpacking first (which would defeat the gather);
            # quantize_params_int4 keeps experts int8 for exactly this
            raise ValueError(
                "int4 expert banks are not supported on the decode gather "
                "path — quantize experts to int8 (quantize_params_int4 "
                "does this automatically)")
        if is_quantized(leaf):
            q = leaf[QKEY][gate_idx]                         # (B,T,K,...)
            s = leaf["scale"][gate_idx]
            return (q.astype(jnp.float32) * s).astype(x.dtype)
        return leaf[gate_idx]

    wg = gather_expert(lw["experts"]["w_gate"])              # (B, T, K, D, F)
    wu = gather_expert(lw["experts"]["w_up"])
    wd = gather_expert(lw["experts"]["w_down"])              # (B, T, K, F, D)
    h = jax.nn.silu(jnp.einsum("btd,btkdf->btkf", x, wg)) \
        * jnp.einsum("btd,btkdf->btkf", x, wu)
    out = jnp.einsum("btkf,btkfd->btkd", h, wd)
    return jnp.einsum("btk,btkd->btd", gate_vals.astype(x.dtype), out)


def _moe_layer(cfg: MoeConfig, carry, lw: Dict[str, jax.Array], freqs,
               tp_axis=None, ep_axis=None):
    """One MoE decoder layer, the aux loss summed beside the activations;
    with tp/ep axes set it is the shard_map-safe variant (explicit psums),
    mirroring ``llama._layer``."""
    x, aux_sum = carry
    psum = (lambda y: lax.psum(y, tp_axis)) if tp_axis else None
    x, _, aux = decoder_block(
        cfg, x, lw, qkv_attend(cfg, freqs, self_attend(cfg._llama_view())),
        partial(moe_ffn, cfg, ep_axis=ep_axis, tp_axis=tp_axis), reduce=psum)
    return (x, aux_sum + aux)


def moe_forward(params: Dict[str, Any], tokens: jax.Array, cfg: MoeConfig):
    """tokens (B, S) → (logits (B, S, V) fp32, aux_loss scalar)."""
    x = params["embed"][tokens].astype(cfg.dtype)
    freqs = rope_freqs(cfg._llama_view(), tokens.shape[1])

    def body(carry, lw):
        return _moe_layer(cfg, carry, lw, freqs), None

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return logits, aux / cfg.n_layers


def moe_loss(params, tokens, targets, cfg: MoeConfig) -> jax.Array:
    logits, aux = moe_forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll) + cfg.router_aux_weight * aux

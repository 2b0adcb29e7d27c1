"""Hybrid decoders of Mamba-2 and attention layers: Granite-4.0-H-Micro
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
``granitemoehybrid``; the Mamba-2 layer of Dao and Gu, "Transformers are
SSMs", arXiv:2405.21060).

A layer is ``models.block.decoder_block`` with one of two mixing operations,
chosen by the layer's ``layer_types`` entry, and the same SwiGLU after it.
With ``h = rmsnorm(x)``:

- **Mamba-2** (:func:`mamba_rows_mix`, :func:`mamba_step`).
  ``[z ; xBC ; dt_raw] = h W_in``; ``xBC = silu(causal_conv(xBC))``, a
  depthwise convolution of ``mamba_d_conv`` taps over time with a bias;
  ``[x ; B ; C] = xBC`` with ``x`` viewed as ``mamba_n_heads`` heads of
  ``mamba_d_head`` and B, C one group of ``mamba_d_state`` shared by all
  heads; ``dt = softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``. Per head
  i: ``S_t = exp(dt_t,i A_i) S_{t-1} + dt_t,i x_t,i ⊗ B_t`` (S: d_head ×
  d_state, S_0 = 0) and ``y_t,i = S_t C_t + D_i x_t,i``; then
  ``y = rmsnorm(y ⊙ silu(z))`` over the whole inner width (the gate before
  the norm) and ``W_out`` (the block's ``wo``).
- **Attention**: grouped-query heads with NO position embedding
  (``position_embedding_type: nope``) and the softmax scale
  ``attention_multiplier`` (``block.attention_scale``), through the engine's
  K/V operations for the attention layers alone.

Around them Granite's scalars, each read where the identity would be for
another family: ``embedding_multiplier`` on the embedding,
``residual_multiplier`` on both residual adds (``decoder_block``), the head
tied to the embedding and its logits divided by ``logits_scaling``.

A prompt runs the recurrence in its chunked form (:func:`ssd_scan`: the
``mamba_chunk_size`` tokens of a chunk by matrix products, the state passed
from chunk to chunk), stopped at the prompt's true length; a decode step
applies it once. What a slot carries between steps is its state, not rows:
``serve.state_cache``.

The weights of the layers are kept a run of like layers at a time
(``params["runs"]``, :meth:`HybridConfig.layer_stacks`): each run is scanned
whole, never sliced out of a longer stack.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..exceptions import UnsupportedMechanismError
from .block import decoder_block, dense_ffn, embed, rmsnorm, scale_logits

HI = lax.Precision.HIGHEST
# the recurrent state a slot carries (``serve.state_cache``): accumulated
# over thousands of steps, it is held in float32 whatever the model's dtype
SSM_DTYPE = jnp.float32

GRANITE_4_H_MICRO_LAYERS = (
    ("mamba",) * 5 + (("attention",) + ("mamba",) * 9) * 3
    + ("attention",) + ("mamba",) * 4)


@dataclass(frozen=True)
class HybridConfig:
    """Published keys under the program's names (``config.json`` key in
    brackets where it differs)."""
    vocab_size: int = 100352
    dim: int = 2048                       # hidden_size
    n_heads: int = 32                     # num_attention_heads
    n_kv_heads: int = 8                   # num_key_value_heads
    ffn_dim: int = 8192                   # intermediate_size
    layer_types: Tuple[str, ...] = GRANITE_4_H_MICRO_LAYERS
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    position_embedding_type: str = "nope"
    # no position embedding rotates by it; the engine's rope table, which
    # nothing here reads, is made from it
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5                # rms_norm_eps
    dtype: Any = jnp.bfloat16

    # what the serving engine keys its cache on (``serve.engine._cache_ops``)
    cache_kind = "hybrid"
    # what ``generate._flash_prefill_wanted`` reads: the attention layers'
    # heads of 64 take the flash kernel where the shape allows
    attn_impl = "auto"
    # what a decode step tallies (``serve.engine._tally_shapes``): the live
    # slots whose state it advanced
    ssm_tally_shape = ()

    def __post_init__(self):
        kinds = set(self.layer_types)
        if not kinds <= {"mamba", "attention"} or "mamba" not in kinds:
            raise UnsupportedMechanismError(
                f"layer_types {sorted(kinds)}", "hybrid",
                "models.hybrid runs 'mamba' and 'attention' layers")
        for name, want in (("mamba_n_groups", 1),
                           ("position_embedding_type", "nope")):
            if getattr(self, name) != want:
                raise UnsupportedMechanismError(
                    f"{name}={getattr(self, name)!r}", "hybrid",
                    f"models.hybrid implements {name}={want!r} only")
        if self.d_inner != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_expand * hidden_size must be "
                             "mamba_n_heads * mamba_d_head")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def conv_dim(self) -> int:
        """Width of what the convolution sees: x, B and C."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        """z, xBC and dt."""
        return self.d_inner + self.conv_dim + self.mamba_n_heads

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attention(self) -> int:
        return self.layer_types.count("attention")

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """(kind, index of the run's first layer among that kind's layers,
        length) for each run of like layers, in order."""
        out, seen = [], {"mamba": 0, "attention": 0}
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(r) for r in out)

    def layer_stacks(self, params: Dict[str, Any]):
        """The runs as ``models.block.layer_stacks`` hands them to every
        stack, each a stack of its own in ``params["runs"]``. The index a
        run starts at is counted among ITS kind's layers, which is what a
        layer's index addresses in the cache: the K/V grid holds the
        attention layers, the state grid the Mamba-2 layers."""
        return [(stack, None, start, n)
                for stack, (_, start, n) in zip(params["runs"], self.runs)]

    @classmethod
    def tiny(cls, **kw) -> "HybridConfig":
        """The published pattern's shape at a CPU test's size: attention at
        layer 5 of 10, Granite's scalars."""
        d = dict(vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, ffn_dim=128,
                 layer_types=("mamba",) * 5 + ("attention",)
                 + ("mamba",) * 4,
                 mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
                 mamba_chunk_size=8)
        d.update(kw)
        return cls(**d)

    def param_count(self) -> int:
        """Every parameter, the tied embedding once."""
        d, hd, h = self.dim, self.head_dim, self.mamba_n_heads
        mamba = (d * self.in_proj_dim + (self.mamba_d_conv + 1) * self.conv_dim
                 + 3 * h + self.d_inner + self.d_inner * d)
        attention = 2 * d * (self.n_heads + self.n_kv_heads) * hd
        layer = 3 * d * self.ffn_dim + 2 * d
        return (self.vocab_size * d + d + self.n_mamba * (mamba + layer)
                + self.n_attention * (attention + layer))


def is_mamba(lw: Dict[str, Any]) -> bool:
    """Is this layer's (or run's) weight dict a Mamba-2 layer's?"""
    return "in_proj" in lw


def hybrid_init(rng: jax.Array, cfg: HybridConfig) -> Dict[str, Any]:
    """``embed`` (tied to the head), ``runs`` (one stacked dict a run of
    like layers) and ``final_norm``. Matrices N(0, 1/fan_in); the SSM's own
    parameters as Mamba-2 initialises them: ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of ``exp U(log 1e-3, log 1e-1)``
    (floor 1e-4), ``D = 1``, norm weights 1."""
    d, f, h = cfg.dim, cfg.ffn_dim, cfg.mamba_n_heads
    keys = iter(jax.random.split(rng, 16 * len(cfg.runs) + 1))

    def init(shape, fan_in, dtype=None):
        w = jax.random.normal(next(keys), shape, jnp.float32) / jnp.sqrt(
            fan_in)
        return w.astype(dtype or cfg.dtype)

    def run(kind, n):
        out = {"attn_norm": jnp.ones((n, d), jnp.float32),
               "ffn_norm": jnp.ones((n, d), jnp.float32),
               "w_gate": init((n, d, f), d), "w_up": init((n, d, f), d),
               "w_down": init((n, f, d), f)}
        if kind == "attention":
            q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
            out.update(wq=init((n, d, q), d), wk=init((n, d, kv), d),
                       wv=init((n, d, kv), d), wo=init((n, q, d), q))
            return out
        dt = jnp.exp(jax.random.uniform(
            next(keys), (n, h), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        out.update(
            in_proj=init((n, d, cfg.in_proj_dim), d),
            conv_w=init((n, cfg.mamba_d_conv, cfg.conv_dim), cfg.mamba_d_conv),
            conv_b=init((n, cfg.conv_dim), cfg.mamba_d_conv),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.log(jax.random.uniform(next(keys), (n, h), jnp.float32,
                                             1.0, 16.0)),
            D=jnp.ones((n, h), jnp.float32),
            ssm_norm=jnp.ones((n, cfg.d_inner), jnp.float32),
            wo=init((n, cfg.d_inner, d), cfg.d_inner))
        return out

    return {"embed": init((cfg.vocab_size, d), d),
            "runs": [run(kind, n) for kind, _, n in cfg.runs],
            "final_norm": jnp.ones((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# the Mamba-2 mix
# ---------------------------------------------------------------------------

def _split_in_proj(cfg: HybridConfig, zxbcdt: jax.Array):
    """[z ; xBC ; dt_raw] along the last axis."""
    di, c = cfg.d_inner, cfg.conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + c], zxbcdt[..., di + c:]


def _split_xbc(cfg: HybridConfig, xbc: jax.Array):
    """[x ; B ; C], x as heads: (..., H, P), (..., N), (..., N)."""
    di, n = cfg.d_inner, cfg.mamba_d_state
    x = xbc[..., :di].reshape(*xbc.shape[:-1], cfg.mamba_n_heads,
                              cfg.mamba_d_head)
    return x, xbc[..., di:di + n], xbc[..., di + n:]


def _gate_norm(cfg: HybridConfig, y: jax.Array, z: jax.Array,
               lw: Dict[str, Any]) -> jax.Array:
    """rmsnorm(y ⊙ silu(z)) over the inner width, in float32, then the
    model dtype (what the block's ``wo`` multiplies)."""
    with jax.named_scope("kt.ssm.gate_norm"):
        g = y.reshape(z.shape).astype(jnp.float32) * jax.nn.silu(
            z.astype(jnp.float32))
        return rmsnorm(g, lw["ssm_norm"], cfg.norm_eps).astype(cfg.dtype)


def _dt(dt_raw: jax.Array, lw: Dict[str, Any]) -> jax.Array:
    return jax.nn.softplus(dt_raw.astype(jnp.float32) + lw["dt_bias"])


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int):
    """The recurrence over a prompt in its chunked (SSD) form, float32.
    x (B, T, H, P); dt (B, T, H), 0 where a position must leave the state
    alone (the decay is then 1 and the input 0); a (H,) = -exp(A_log);
    b, c (B, T, N). Within a chunk of ``chunk`` tokens the outputs are matrix
    products against the decay between every pair of its positions; between
    chunks the state is passed on. Returns y (B, T, H, P) without the skip
    term, and the state after the last position, (B, H, P, N)."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, t)
    if t % q:
        raise ValueError(f"a prompt of {t} rows is not whole chunks of {q}")
    log_a = dt * a                                          # (B, T, H) <= 0
    xdt = x * dt[..., None]

    def chunks(v):
        return jnp.moveaxis(v.reshape(bsz, t // q, q, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((q, q), bool))

    def step(state, inp):
        xc, lc, bc, cc = inp          # (B,Q,H,P) (B,Q,H) (B,Q,N) (B,Q,N)
        cum = jnp.cumsum(lc, axis=1)                        # (B, Q, H)
        seg = cum[:, :, None, :] - cum[:, None, :, :]       # (B, l, s, H)
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        scores = jnp.einsum("bln,bsn->bls", cc, bc, precision=HI)
        y = jnp.einsum("bls,blsh,bshp->blhp", scores, decay, xc,
                       precision=HI)
        y = y + jnp.einsum("bln,bhpn->blhp", cc, state,
                           precision=HI) * jnp.exp(cum)[..., None]
        to_end = jnp.exp(cum[:, -1:, :] - cum)              # (B, Q, H)
        state = state * jnp.exp(cum[:, -1, :])[:, :, None, None] \
            + jnp.einsum("bsn,bsh,bshp->bhpn", bc, to_end, xc, precision=HI)
        return state, y

    state, y = lax.scan(step, jnp.zeros((bsz, h, p, n), jnp.float32),
                        tuple(map(chunks, (xdt, log_a, b, c))))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t, h, p), state


def mamba_rows_mix(cfg: HybridConfig, true_len=None):
    """The block's mixing operation for a Mamba-2 layer over a prompt from
    position 0 (the plain forward, a prefill): the chunked scan, stopped at
    ``true_len`` (traced; None: every position is real). Positions at or past
    it get ``dt = 0`` and ``x = 0``, so the state handed back is the state
    after the last real token. Returns ``mix(h, lw, lora) -> (y, (conv,
    ssm))``: conv (B, d_conv - 1, conv_dim) the last real inputs of the
    convolution (zeros before position 0), ssm (B, H, P, N) float32."""
    k = cfg.mamba_d_conv

    def mix(h, lw, lora):
        del lora
        bsz, t, _ = h.shape
        real = (jnp.arange(t) < (t if true_len is None else true_len))
        with jax.named_scope("kt.ssm.in_proj"):
            z, xbc, dt_raw = _split_in_proj(cfg, h @ lw["in_proj"])
        with jax.named_scope("kt.ssm.conv"):
            padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
            end = t if true_len is None else true_len
            conv_state = lax.dynamic_slice_in_dim(padded, end, k - 1, axis=1)
            w = lw["conv_w"].astype(jnp.float32)
            out = sum(padded[:, i:i + t].astype(jnp.float32) * w[i]
                      for i in range(k))
            xbc = jax.nn.silu(out + lw["conv_b"].astype(jnp.float32))
        x, b, c = _split_xbc(cfg, xbc)
        dt = jnp.where(real[None, :, None], _dt(dt_raw, lw), 0.0)
        x = jnp.where(real[None, :, None, None], x, 0.0)
        with jax.named_scope("kt.ssm.scan"):
            y, state = ssd_scan(x, dt, -jnp.exp(lw["A_log"]), b, c,
                                cfg.mamba_chunk_size)
            y = y + lw["D"][:, None] * x
        return _gate_norm(cfg, y, z, lw), (conv_state.astype(cfg.dtype),
                                           state.astype(SSM_DTYPE))

    return mix


def ssm_update(ssm: jax.Array, decay: jax.Array, dtx: jax.Array,
               b: jax.Array, c: jax.Array):
    """The recurrence's step on the slots' states ssm (B, H, P, N): decay
    (B, H) = exp(dt·A), dtx (B, H, P) = dt·x, b and c (B, N). Returns (y
    (B, H, P) float32 without the skip term, the new state in ssm's
    dtype)."""
    new = (decay[:, :, None, None] * ssm.astype(jnp.float32)
           + dtx[..., None] * b[:, None, None, :])
    return jnp.sum(new * c[:, None, None, :], axis=-1), new.astype(ssm.dtype)


def mamba_step(cfg: HybridConfig, h: jax.Array, lw: Dict[str, Any],
               conv: jax.Array, ssm: Optional[jax.Array] = None, *,
               state_update: Optional[Callable] = None):
    """One token a slot through a Mamba-2 layer. h (B, D); conv
    (B, d_conv - 1, conv_dim) the slot's last inputs of the convolution; ssm
    (B, H, P, N) its state, advanced by :func:`ssm_update`; or, for a state
    held elsewhere (the engine's grid, in place), ``state_update(decay, dtx,
    b, c) -> (y, state)`` with :func:`ssm_update`'s operands and y. Returns
    y (B, d_inner) in the model dtype, the window shifted by the new input,
    and the new state (what ``state_update`` returned for it)."""
    if state_update is None:
        state_update = partial(ssm_update, ssm)
    with jax.named_scope("kt.ssm.in_proj"):
        z, xbc, dt_raw = _split_in_proj(cfg, h @ lw["in_proj"])
    with jax.named_scope("kt.ssm.conv"):
        window = jnp.concatenate([conv, xbc[:, None].astype(conv.dtype)],
                                 axis=1)
        out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                         lw["conv_w"].astype(jnp.float32), precision=HI)
        xbc = jax.nn.silu(out + lw["conv_b"].astype(jnp.float32))
    x, b, c = _split_xbc(cfg, xbc)
    dt = _dt(dt_raw, lw)                                    # (B, H)
    with jax.named_scope("kt.ssm.state_update"):
        decay = jnp.exp(dt * -jnp.exp(lw["A_log"]))
        y, state = state_update(decay, dt[:, :, None] * x, b, c)
        y = y + lw["D"][:, None] * x
    return _gate_norm(cfg, y, z, lw), window[:, 1:], state


# ---------------------------------------------------------------------------
# the plain forward
# ---------------------------------------------------------------------------

def attention_rows_mix(cfg: HybridConfig, k_rows, v_rows, q_pos,
                       flash_prefill: bool = False):
    """The block's mixing operation for an attention layer over a prompt
    from position 0: causal self-attention through the K/V cache's own
    row-major operation (``generate.cache_attend``: the flash kernel where
    ``flash_prefill``), no position embedding. k_rows / v_rows
    (B, T, NKV, Hd) are the layer's rows before, and come back written."""
    from .block import qkv_attend
    from .generate import cache_attend
    return qkv_attend(cfg, None, cache_attend(
        cfg, k_rows, v_rows, q_pos, flash_prefill=flash_prefill,
        causal_prefill=True))


def hybrid_forward(params: Dict[str, Any], tokens: jax.Array,
                   cfg: HybridConfig) -> jax.Array:
    """tokens (B, T) → logits (B, T, V) in float32. T a multiple of the
    chunk, or shorter than one."""
    from .generate import _flash_prefill_wanted
    from .quant import lm_head_dot
    bsz, t = tokens.shape
    x = embed(params, tokens, cfg)
    rows = jnp.zeros((bsz, t, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    flash = _flash_prefill_wanted(cfg, t)

    def body(h, lw):
        mix = (mamba_rows_mix(cfg) if is_mamba(lw) else attention_rows_mix(
            cfg, rows, rows, jnp.arange(t), flash))
        h, _, _ = decoder_block(cfg, h, lw, mix, dense_ffn)
        return h, None

    for stack, _, _, _ in cfg.layer_stacks(params):
        x, _ = lax.scan(body, x, stack)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return scale_logits(lm_head_dot(x, params, cfg.dtype), cfg)


__all__ = ["HybridConfig", "hybrid_init", "hybrid_forward", "ssd_scan",
           "mamba_rows_mix", "mamba_step", "ssm_update", "attention_rows_mix",
           "is_mamba", "GRANITE_4_H_MICRO_LAYERS"]

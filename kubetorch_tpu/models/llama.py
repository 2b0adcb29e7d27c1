"""Llama-3-family decoder in functional JAX, designed for the MXU.

TPU-first choices:
- **Stacked layers + ``lax.scan``**: every layer's weights are one leaf with a
  leading ``(L, ...)`` dim. Compile time is O(1) in depth and XLA pipelines
  the scan body; per-layer Python loops would unroll L copies of HLO.
- **bf16 everywhere on the matmul path** (MXU native), fp32 for norms/softmax
  accumulation and the final logits cross-entropy.
- **GQA** with explicit head-batched einsums — shapes stay static and large so
  XLA tiles them onto the 128x128 systolic array.
- **Rematerialization**: the scan body is wrapped in ``jax.checkpoint`` with a
  dots-saveable policy, trading FLOPs for HBM (the usual bottleneck).
- Attention dispatches to the Pallas flash kernel on TPU (``ops.attention``)
  and a pure-XLA fallback elsewhere; context-parallel meshes use ring
  attention (``parallel.ring_attention``) — both behind one flag.

Benchmark target: BASELINE.md config 3 (Llama-3-8B pretraining).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .block import (apply_rope,  # noqa: F401  (its importers' home)
                    decoder_block, dense_ffn, qkv_attend, rmsnorm)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # Named jax.checkpoint policy for the scanned layer stack (ISSUE 12):
    # "none" | "dots" | "nothing_saveable" | a policy callable. None keeps
    # the legacy behavior (remat=True → "dots"). `kt hbm audit` is the
    # tool that decides which one a config should run.
    remat_policy: Any = None
    # auto | xla | flash | ring | ulysses; "ring_local"/"ulysses_local" are
    # pipeline-internal (already-inside-shard_map dispatch, set only by
    # llama_forward_pipelined)
    attn_impl: str = "auto"
    # Llama-3.1 NTK frequency scaling as a hashable tuple (the config is a
    # jit static arg): (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings). None = plain rope_theta.
    rope_scaling: Optional[tuple] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama3_1b(cls, **kw) -> "LlamaConfig":
        d = dict(dim=2048, n_layers=16, n_heads=32, n_kv_heads=8, ffn_dim=8192)
        d.update(kw)
        return cls(**d)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        d = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 ffn_dim=128, max_seq_len=128)
        d.update(kw)
        return cls(**d)

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6·params + attn)."""
        p = self.param_count()
        attn = 12 * self.n_layers * self.dim * self.max_seq_len  # rough, seq-dependent
        return 6 * p + attn

    def param_count(self) -> int:
        d, f, L = self.dim, self.ffn_dim, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        ffn = 3 * d * f
        return self.vocab_size * d * 2 + L * (attn + ffn + 2 * d) + d


def llama_init(rng: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Initialize the param pytree. Layer weights are stacked on dim 0."""
    d, L = cfg.dim, cfg.n_layers
    hd, nh, nkv, f = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.ffn_dim
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
            "w_gate": init(next(k), (L, d, f), d),
            "w_up": init(next(k), (L, d, f), d),
            "w_down": init(next(k), (L, f, d), f),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": init(next(k), (d, cfg.vocab_size), d),
    }


def rope_freqs(cfg: LlamaConfig, seq_len: int) -> jax.Array:
    """(S, Hd/2) complex rotation table, fp32. Hd is the head's width, or
    the config's ``rope_dim`` where only a part of a head is rotated
    (``models.mla``)."""
    hd = getattr(cfg, "rope_dim", None) or cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    # getattr: callers pass MoeConfig here too (no rope_scaling field)
    rs = getattr(cfg, "rope_scaling", None)
    if rs is not None:
        # Llama-3.1 long-context NTK scaling: frequencies whose wavelength
        # exceeds the ORIGINAL training context are slowed by ``factor``,
        # short wavelengths are kept, and the band between interpolates —
        # required for 3.1/3.2 checkpoints (convert_hf maps HF
        # rope_scaling={"rope_type": "llama3", ...} here; plain-theta tables
        # would produce silently wrong logits at every position).
        factor, low_fac, high_fac, orig_ctx = rs
        wavelen = 2.0 * jnp.pi / inv
        low_wl = orig_ctx / low_fac       # longest wavelength kept ...
        high_wl = orig_ctx / high_fac     # ... after the transition band
        smooth = jnp.clip((orig_ctx / wavelen - low_fac)
                          / (high_fac - low_fac), 0.0, 1.0)
        inv = jnp.where(
            wavelen < high_wl, inv,
            jnp.where(wavelen > low_wl, inv / factor,
                      (1.0 - smooth) * inv / factor + smooth * inv))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs) + 1j * jnp.sin(freqs)


def _xla_attention(q, k, v, scale: float, causal: bool = True) -> jax.Array:
    """Reference attention, fp32 softmax. q:(B,S,N,Hd) k,v:(B,S,NKV,Hd)."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    group = nh // nkv
    q = q.reshape(b, s, nkv, group, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, s, nh, hd)


def attention(q, k, v, cfg: LlamaConfig) -> jax.Array:
    """Dispatch to the fastest attention for the current backend/mesh.

    ``auto`` resolution: a live ``context`` mesh axis (installed via
    ``parallel.mesh_context.use_mesh``) → ring attention; TPU backend and a
    shape the kernel takes → the Pallas flash kernel; otherwise the XLA
    reference implementation. Under a mesh the kernel runs per device over
    its own batch rows and heads (``parallel.kernel_shard``).
    """
    from ..ops.attention import flash_auto
    from ..parallel.mesh_context import axis_size, current_mesh

    scale = 1.0 / (cfg.head_dim ** 0.5)
    impl = cfg.attn_impl
    mesh = current_mesh()
    if impl == "auto":
        if axis_size(mesh, "context") > 1:
            impl = "ring"
        elif flash_auto(q.shape[1], q.shape[2], k.shape[2]):
            impl = "flash"
        else:
            impl = "xla"
    if impl == "ring_local":
        # caller is already inside a shard_map with a bound "context" axis
        # (e.g. a pipeline stage body); never wrap another shard_map
        from ..parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, axis_name="context", causal=True, scale=scale)
    if impl == "ulysses_local":
        from ..parallel.ulysses import ulysses_attention
        return ulysses_attention(q, k, v, axis_name="context", causal=True, scale=scale)
    if impl == "ring":
        from ..parallel.ring_attention import ring_attention, ring_attention_sharded
        if mesh is not None:
            return ring_attention_sharded(q, k, v, mesh, causal=True, scale=scale)
        # already inside a shard_map with a bound "context" axis
        return ring_attention(q, k, v, axis_name="context", causal=True, scale=scale)
    if impl == "ulysses":
        from ..parallel.ulysses import ulysses_attention, ulysses_attention_sharded
        if mesh is not None:
            return ulysses_attention_sharded(q, k, v, mesh, causal=True, scale=scale)
        return ulysses_attention(q, k, v, axis_name="context", causal=True, scale=scale)
    if impl == "flash":
        from ..parallel.kernel_shard import flash_attention_sharded
        return flash_attention_sharded(q, k, v, mesh, causal=True,
                                       scale=scale)
    if impl != "xla":
        raise ValueError(f"unknown attn_impl {impl!r}; expected "
                         "auto|xla|flash|ring|ulysses")
    return _xla_attention(q, k, v, scale)


def self_attend(cfg: LlamaConfig):
    """The block's attention operation for a stack with no cache: causal
    self-attention over the layer's own T tokens (:func:`attention`)."""
    def attend(q, k, v):
        with jax.named_scope("kt.attention"):
            return attention(q, k, v, cfg), None
    return attend


def _layer(cfg: LlamaConfig, x: jax.Array, lw: Dict[str, jax.Array],
           freqs: jax.Array, tp_axis: Optional[str] = None) -> jax.Array:
    """One decoder layer. With ``tp_axis`` set, the body is the Megatron
    tensor-parallel variant for use inside ``shard_map``: ``lw`` leaves are
    the LOCAL shards — wq/wk/wv/w_gate/w_up column-sharded, wo/w_down
    row-sharded, norms replicated — and exactly two ``psum``s run per layer
    (attention output, FFN output), explicit because GSPMD cannot see inside
    shard_map (``models.block.decoder_block`` takes the head counts from the
    local shapes, so one body serves both paths)."""
    psum = (lambda y: lax.psum(y, tp_axis)) if tp_axis else None
    x, _, _ = decoder_block(cfg, x, lw, qkv_attend(cfg, freqs, self_attend(cfg)),
                            partial(dense_ffn, reduce=psum), reduce=psum)
    return x


def llama_hidden(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """The headless forward: tokens (B, S) → final hidden states (B, S, D).

    Single source of truth for embed → scanned layers → final norm; both loss
    variants ride on it so they can never diverge.
    """
    x = params["embed"][tokens].astype(cfg.dtype)
    freqs = rope_freqs(cfg, tokens.shape[1])

    def body(carry, lw):
        return _layer(cfg, carry, lw, freqs), None

    from .common import resolve_remat_policy

    # remat_policy (named) wins over the legacy bool; remat=True with no
    # policy keeps the historical dots-saveable behavior. getattr: MoE and
    # pipeline configs ride through here without the field.
    policy = getattr(cfg, "remat_policy", None)
    if policy is not None:
        policy = resolve_remat_policy(policy)
        if policy is not None:
            body = jax.checkpoint(body, policy=policy)
    elif cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    x, _ = lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"], cfg.norm_eps)


def llama_forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S, V) fp32."""
    x = llama_hidden(params, tokens, cfg)
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


def llama_loss(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
               cfg: LlamaConfig) -> jax.Array:
    """Next-token cross-entropy, fp32 log-softmax, mean over all positions."""
    logits = llama_forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def chunked_ce(x: jax.Array, targets: jax.Array, head: jax.Array,
               chunk: int = 256) -> jax.Array:
    """Memory-efficient CE over hidden states: the LM head + log-softmax are
    applied per sequence-chunk inside a ``lax.map``, so peak memory is
    (B, chunk, V) instead of (B, S, V) — at V=128k and S=8k that's the
    difference between ~4 GB of fp32 logits per example and ~128 MB. The
    backward recomputes each chunk's logits (standard remat trade: the LM
    head matmul is cheap next to its HBM cost). Sequences that don't divide
    the chunk are padded and masked, never degraded to tiny chunks.
    Shared by the plain and pipelined loss paths.
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    mask = jnp.ones((b, s), jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    total = s + pad

    def chunk_loss(args):
        h, t, m = args                                    # (B, C, D), (B, C)
        logits = (h @ head).astype(jnp.float32)           # (B, C, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]
        return jnp.sum(ll * m)

    chunk_loss = jax.checkpoint(chunk_loss)
    n_chunks = total // chunk
    h_chunks = x.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    t_chunks = targets.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    m_chunks = mask.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    totals = lax.map(chunk_loss, (h_chunks, t_chunks, m_chunks))
    return -jnp.sum(totals) / (b * s)


def llama_loss_chunked(params: Dict[str, Any], tokens: jax.Array,
                       targets: jax.Array, cfg: LlamaConfig,
                       chunk: int = 256) -> jax.Array:
    """Next-token CE without materializing (B, S, V) logits (see
    :func:`chunked_ce`)."""
    x = llama_hidden(params, tokens, cfg)                 # (B, S, D)
    return chunked_ce(x, targets, params["lm_head"].astype(cfg.dtype), chunk)


def config_from_dict(d: Dict) -> LlamaConfig:
    from .common import config_from_dict as _generic
    return _generic(LlamaConfig, d)

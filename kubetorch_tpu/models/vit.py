"""Vision Transformer in functional JAX, MXU-first like the Llama stack.

Same TPU-first choices as ``models/llama.py`` (stacked layers + ``lax.scan``,
bf16 matmul path with fp32 norms/softmax, optional remat), applied to the
encoder family: bidirectional attention (no causal mask), LayerNorm instead
of RMSNorm, GELU MLP, learned position embeddings, mean-pool classifier
head. Patchify is a reshape/transpose (no conv needed — XLA fuses the patch
linear into one matmul, which is exactly an MXU-shaped op).

The reference ships no models at all (it is a dispatch fabric; SURVEY §2.4 —
parallelism and models live in user frameworks). Model families exist here
because on TPU the launcher owns the mesh, so it can own model sharding too:
``VIT_RULES`` drops into ``make_train_step`` exactly like ``LLAMA_RULES``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class VitConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    mlp_dim: int = 3072
    n_classes: int = 1000
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_impl: str = "auto"  # auto | xla | flash

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels

    @classmethod
    def vit_b16(cls, **kw) -> "VitConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "VitConfig":
        d = dict(image_size=32, patch_size=8, dim=64, n_layers=2, n_heads=4,
                 mlp_dim=128, n_classes=10)
        d.update(kw)
        return cls(**d)

    def param_count(self) -> int:
        d, m, L = self.dim, self.mlp_dim, self.n_layers
        attn = 4 * d * d
        return (self.patch_dim * d + self.n_patches * d
                + L * (attn + 2 * d * m + 4 * d)   # per layer: qkv+o, mlp, 2 LN
                + 2 * d                            # final LN scale + bias
                + d * self.n_classes)


def vit_init(rng: jax.Array, cfg: VitConfig) -> Dict[str, Any]:
    """Param pytree; layer weights stacked on dim 0 for ``lax.scan``."""
    d, L, m = cfg.dim, cfg.n_layers, cfg.mlp_dim
    k = iter(jax.random.split(rng, 8))

    def init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(cfg.dtype)

    return {
        "patch_embed": init(next(k), (cfg.patch_dim, d), cfg.patch_dim),
        "pos_embed": (jax.random.normal(next(k), (cfg.n_patches, d),
                                        jnp.float32) * 0.02),
        "layers": {
            "ln1_scale": jnp.ones((L, d), jnp.float32),
            "ln1_bias": jnp.zeros((L, d), jnp.float32),
            "wqkv": init(next(k), (L, d, 3 * d), d),
            "wo": init(next(k), (L, d, d), d),
            "ln2_scale": jnp.ones((L, d), jnp.float32),
            "ln2_bias": jnp.zeros((L, d), jnp.float32),
            "w_up": init(next(k), (L, d, m), d),
            "w_down": init(next(k), (L, m, d), m),
        },
        "final_ln_scale": jnp.ones((d,), jnp.float32),
        "final_ln_bias": jnp.zeros((d,), jnp.float32),
        "head": init(next(k), (d, cfg.n_classes), d),
    }


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def patchify(images: jax.Array, cfg: VitConfig) -> jax.Array:
    """(B, H, W, C) → (B, N, P²·C). Pure reshape/transpose — the patch
    projection that follows is then one big (N, P²C)@(P²C, D) matmul."""
    b, h, w, c = images.shape
    p = cfg.patch_size
    x = images.reshape(b, h // p, p, w // p, p, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p),
                                                 p * p * c)


def _encoder_attention(q, k, v, cfg: VitConfig) -> jax.Array:
    """Bidirectional attention; flash on TPU for a patch count the kernel
    tiles, XLA reference elsewhere."""
    from ..ops.attention import flash_attention, flash_auto
    from .llama import _xla_attention

    scale = cfg.head_dim ** -0.5
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "flash" if flash_auto(q.shape[1], q.shape[2],
                                     k.shape[2]) else "xla"
    if impl == "flash":
        return flash_attention(q, k, v, causal=False, scale=scale)
    if impl != "xla":
        raise ValueError(f"unknown attn_impl {impl!r}; expected "
                         "auto|xla|flash")
    return _xla_attention(q, k, v, scale, causal=False)


def _encoder_layer(cfg: VitConfig, x: jax.Array,
                   lw: Dict[str, jax.Array]) -> jax.Array:
    b, n, d = x.shape
    h = layernorm(x, lw["ln1_scale"], lw["ln1_bias"], cfg.norm_eps)
    qkv = (h @ lw["wqkv"]).reshape(b, n, 3, cfg.n_heads, cfg.head_dim)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    attn = _encoder_attention(q, k, v, cfg).reshape(b, n, d)
    x = x + attn @ lw["wo"]
    h = layernorm(x, lw["ln2_scale"], lw["ln2_bias"], cfg.norm_eps)
    return x + jax.nn.gelu(h @ lw["w_up"]) @ lw["w_down"]


def vit_forward(params: Dict[str, Any], images: jax.Array,
                cfg: VitConfig) -> jax.Array:
    """images (B, H, W, C) float → logits (B, n_classes) fp32."""
    x = patchify(images.astype(cfg.dtype), cfg) @ params["patch_embed"]
    x = (x + params["pos_embed"].astype(cfg.dtype)[None])

    def body(carry, lw):
        return _encoder_layer(cfg, carry, lw), None

    if cfg.remat:
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    x, _ = lax.scan(body, x, params["layers"])
    x = layernorm(x, params["final_ln_scale"], params["final_ln_bias"],
                  cfg.norm_eps)
    pooled = jnp.mean(x, axis=1)                      # mean-pool, no CLS
    return (pooled @ params["head"].astype(cfg.dtype)).astype(jnp.float32)


def classification_ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over (B, n_classes) fp32 logits — shared by the plain and
    pipelined loss paths so they can never drift."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def vit_loss(params: Dict[str, Any], images: jax.Array, labels: jax.Array,
             cfg: VitConfig) -> jax.Array:
    return classification_ce(vit_forward(params, images, cfg), labels)


def config_from_dict(d: Dict) -> VitConfig:
    from .common import config_from_dict as _generic
    return _generic(VitConfig, d)

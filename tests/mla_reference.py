"""A plain float32 reference of the latent-attention / fine-grained-expert
decoder (``kubetorch_tpu.models.mla``), for the tests: one sequence at a
time, expanded heads, no cache, no batching, no capacity, every expert a
loop. Takes the program's parameter tree (cast to float32) and nothing else
from it. The benchmark keeps its own copy, which makes its weights from the
seed (``benchmark/bench_reference_mla_moe.py``)."""

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (T, ..., Hr): pairs (2i, 2i+1) rotated by position · theta^(-2i/Hr)."""
    t, hr = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hr, 2, dtype=jnp.float32) / hr)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (hr // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _swiglu(x, w):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"])) * _mm(x, w["w_up"]),
               w["w_down"])


def route(cfg, h, router, bias):
    """(T, E) weights, zero off the chosen; and the chosen (T, K)."""
    s = jax.nn.sigmoid(_mm(h, router))
    _, idx = jax.lax.top_k(s + bias, cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    dense = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(w)
    return dense, idx


def attention(cfg, h, lw):
    t = h.shape[0]
    n, hn, hr, hv, r = (cfg.n_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.v_head_dim,
                        cfg.kv_lora_rank)
    q = _mm(h, lw["wq"]).reshape(t, n, hn + hr)
    q_nope, q_pe = q[..., :hn], _rope(q[..., hn:], cfg.rope_theta)
    kva = _mm(h, lw["wkv_a"])
    c = _norm(kva[:, :r], lw["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kva[:, r:], cfg.rope_theta)
    kv = _mm(c, lw["wkv_b"]).reshape(t, n, hn + hv)
    k_nope, v = kv[..., :hn], kv[..., hn:]
    s = (jnp.einsum("tnh,snh->nts", q_nope, k_nope, precision=HI)
         + jnp.einsum("tnh,sh->nts", q_pe, k_pe, precision=HI)) \
        * (hn + hr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(t, n * hv), lw["wo"])


def layer(cfg, x, lw):
    x = x + attention(cfg, _norm(x, lw["attn_norm"], cfg.norm_eps), lw)
    h = _norm(x, lw["ffn_norm"], cfg.norm_eps)
    if "router" not in lw:
        return x + _swiglu(h, lw)
    gates, _ = route(cfg, h, lw["router"], lw["router_bias"])
    y = _swiglu(h, lw["shared"])
    for e in range(cfg.n_experts):
        y = y + gates[:, e:e + 1] * _swiglu(
            h, {k: w[e] for k, w in lw["banks"].items()})
    return x + y


def forward(params, tokens, cfg):
    """tokens (T,) → logits (T, V), float32."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = p["embed"][jnp.asarray(tokens)]
    for name in ("dense_layers", "layers"):
        n = p[name]["attn_norm"].shape[0]
        for i in range(n):
            x = layer(cfg, x, jax.tree_util.tree_map(lambda a: a[i], p[name]))
    return np.asarray(_mm(_norm(x, p["final_norm"], cfg.norm_eps),
                          p["lm_head"]))

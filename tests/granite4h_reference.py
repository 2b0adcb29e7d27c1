"""A plain float32 reference of the hybrid Mamba-2 / attention decoder
(``kubetorch_tpu.models.hybrid``; Granite-4.0-H-Micro's layer), for the
tests: one sequence at a time, the recurrence run token by token (not the
program's chunked scan), full causal attention, no cache, no batching. Takes
the program's parameter tree (cast to float32) and its config's numbers, and
nothing else from it. The benchmark keeps its own copy, which makes its
weights from the seed (``benchmark/bench_reference_hybrid.py``)."""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def mamba(cfg, h, lw):
    """h (T, D) → (T, d_inner) before the block's out projection; and the
    state and convolution window after the last position."""
    t = h.shape[0]
    di, n, k = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    nh, p = cfg.mamba_n_heads, cfg.mamba_d_head
    zxbcdt = _mm(h, lw["in_proj"])
    z, xbc, dt_raw = (zxbcdt[:, :di], zxbcdt[:, di:di + cfg.conv_dim],
                      zxbcdt[:, di + cfg.conv_dim:])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + t] * lw["conv_w"][i] for i in range(k))
    u = jax.nn.silu(conv + lw["conv_b"])
    x = u[:, :di].reshape(t, nh, p)
    b, c = u[:, di:di + n], u[:, di + n:]
    dt = jax.nn.softplus(dt_raw + lw["dt_bias"])                  # (T, H)
    a = -jnp.exp(lw["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.einsum("hpn,n->hp", s, c_t, precision=HI)

    s, y = jax.lax.scan(step, jnp.zeros((nh, p, n)), (x, b, c, dt))
    y = y + lw["D"][:, None] * x
    g = y.reshape(t, di) * jax.nn.silu(z)
    return _norm(g, lw["ssm_norm"], cfg.norm_eps), s, padded[t:t + k - 1]


def attention(cfg, h, lw):
    """Causal grouped-query attention, no position embedding, the softmax
    scale ``attention_multiplier``."""
    t = h.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(h, lw["wq"]).reshape(t, nkv, nh // nkv, hd)
    k = _mm(h, lw["wk"]).reshape(t, nkv, hd)
    v = _mm(h, lw["wv"]).reshape(t, nkv, hd)
    s = jnp.einsum("tkgh,skh->kgts", q, k, precision=HI) \
        * cfg.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("kgts,skh->tkgh", jax.nn.softmax(s, -1), v, precision=HI)
    return o.reshape(t, nh * hd)


def layers(cfg, params):
    """Each layer's float32 weights, in the model's order."""
    for stack, (_, _, n) in zip(params["runs"], cfg.runs):
        for i in range(n):
            yield _f32(jax.tree_util.tree_map(lambda a: a[i], stack))


def forward(cfg, params, tokens, states=False):
    """tokens (T,) → logits (T, V); with ``states`` also each Mamba-2
    layer's (state, window) after the last token, in order."""
    e = jnp.asarray(params["embed"], jnp.float32)
    x = e[jnp.asarray(tokens)] * cfg.embedding_multiplier
    rm, kept = cfg.residual_multiplier, []
    for lw in layers(cfg, params):
        h = _norm(x, lw["attn_norm"], cfg.norm_eps)
        if "in_proj" in lw:
            y, s, w = mamba(cfg, h, lw)
            kept.append((s, w))
        else:
            y = attention(cfg, h, lw)
        x = x + rm * _mm(y, lw["wo"])
        h = _norm(x, lw["ffn_norm"], cfg.norm_eps)
        x = x + rm * _mm(jax.nn.silu(_mm(h, lw["w_gate"]))
                         * _mm(h, lw["w_up"]), lw["w_down"])
    x = _norm(x, jnp.asarray(params["final_norm"], jnp.float32),
              cfg.norm_eps)
    logits = _mm(x, e.T) / cfg.logits_scaling
    return (logits, kept) if states else logits

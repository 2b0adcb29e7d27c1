"""A plain float32 reference of the window / full attention decoder with
fine-grained experts (``kubetorch_tpu.models.afmoe``; Trinity-Mini's layer),
for the tests: one sequence at a time, full T x T masks (the band on a
window layer, the causal triangle on a full one), no cache, no ring, no
batching, every expert over every row with a gate of 0 where it was not
chosen. Takes the program's parameter tree (cast to float32) and its
config's numbers, and nothing else from it. The benchmark keeps its own
copy, which makes its weights from the seed
(``benchmark/bench_reference_afmoe.py``)."""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
WINDOW = "sliding_attention"


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rope(x, theta):
    """x (T, N, Hd): pairs (2i, 2i+1) rotated by position · theta^(-2i/Hd)."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def allowed(t, window):
    """(T, T): key j visible to query i: j <= i, and i - window < j on a
    window layer (``window`` None: no lower bound)."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    return mask if window is None else mask & (j > i - window)


def attention(cfg, h, lw, rope, window):
    """h (T, D) normed → the gated heads (T, N·Hd), before ``W_o``: q and k
    rotated where ``rope`` (a window layer), keys masked by
    :func:`allowed`."""
    t = h.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _norm(_mm(h, lw["wq"]).reshape(t, nh, hd), lw["q_norm"],
              cfg.norm_eps)
    k = _norm(_mm(h, lw["wk"]).reshape(t, nkv, hd), lw["k_norm"],
              cfg.norm_eps)
    v = _mm(h, lw["wv"]).reshape(t, nkv, hd)
    if rope:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    q = q.reshape(t, nkv, nh // nkv, hd)
    s = jnp.einsum("tkgh,skh->kgts", q, k, precision=HI) * hd ** -0.5
    s = jnp.where(allowed(t, window), s, -jnp.inf)
    o = jnp.einsum("kgts,skh->tkgh", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(t, nh * hd)
    return o * jax.nn.sigmoid(_mm(h, lw["w_attn_gate"]))


def _swiglu(x, w):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"])) * _mm(x, w["w_up"]),
               w["w_down"])


def experts(cfg, h, lw, banks):
    """The expert layer over h (T, D): sigmoid scores, the top K of score +
    bias chosen, their unbiased scores normalised and scaled, every expert
    over every row, and the shared SwiGLU."""
    scores = jax.nn.sigmoid(_mm(h, lw["router"]))
    _, idx = jax.lax.top_k(scores + lw["router_bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(scores, idx, -1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    gates = jnp.einsum("tk,tke->te", w * cfg.route_scale,
                       jax.nn.one_hot(idx, cfg.n_experts))
    out = _swiglu(h, lw["shared"])
    for e in range(cfg.n_experts):
        out = out + gates[:, e:e + 1] * _swiglu(
            h, {k: v[e] for k, v in banks.items()})
    return out


def layers(cfg, params):
    """Each layer's float32 weights (its run's banks at its index) and its
    attention kind, in the model's order."""
    for stack, (kind, _, _, n) in zip(params["runs"], cfg.runs):
        for i in range(n):
            yield kind, _f32(jax.tree_util.tree_map(lambda a: a[i], stack))


def forward(cfg, params, tokens, window=True):
    """tokens (T,) → logits (T, V). ``window`` False: every layer attends
    causally over all its keys (what a window no shorter than T gives)."""
    eps = cfg.norm_eps
    x = jnp.asarray(params["embed"], jnp.float32)[jnp.asarray(tokens)]
    if cfg.mup_enabled:
        x = x * cfg.dim ** 0.5
    for i, (kind, lw) in enumerate(layers(cfg, params)):
        w = cfg.sliding_window if window and kind == WINDOW else None
        h = _norm(x, lw["attn_norm"], eps)
        y = attention(cfg, h, lw, kind == WINDOW, w)
        x = x + _norm(_mm(y, lw["wo"]), lw["post_attn_norm"], eps)
        h = _norm(x, lw["ffn_norm"], eps)
        f = (_swiglu(h, lw) if i < cfg.num_dense_layers
             else experts(cfg, h, lw, lw["banks"]))
        x = x + _norm(f, lw["post_ffn_norm"], eps)
    x = _norm(x, jnp.asarray(params["final_norm"], jnp.float32), eps)
    return _mm(x, jnp.asarray(params["lm_head"], jnp.float32))

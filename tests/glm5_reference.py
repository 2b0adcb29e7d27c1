"""A plain float32 reference of GLM-5's decoder layer (``glm_moe_dsa``; ISSUE
35, Tentpole section 1) as ``kubetorch_tpu.models.mla`` runs it, for the
tests: one sequence at a time, expanded heads, no cache, no batching, no
tiling, the full T x T score and index matrices, every expert a loop. Takes
the program's parameter tree (cast to float32) and its config's numbers, and
nothing else from it. The benchmark keeps its own copy, which makes its
weights from the seed (``benchmark/bench_reference_dsa_moe.py``).

Per layer, ``x = rmsnorm(h)``, heads ``i``, indexer heads ``j``:

- query: ``c_q = rmsnorm(x W_qa)``, ``[q_nope_i ; q_pe_i] = c_q W_qb``,
  ``q_pe`` rotated;
- latent row: ``[c ; k_pe] = x W_kva``, ``c = rmsnorm(c)``, ``k_pe`` rotated,
  ``[k_nope_i ; v_i] = c W_kvb``;
- indexer: ``q_I_j = rope(c_q W_Iq)`` (the first ``qk_rope_head_dim`` columns
  of each head rotated), ``k_I = rope(layernorm(x W_Ik))`` likewise,
  ``w_j = (x W_Iw)_j / sqrt(heads * width)``;
  ``I(t, s) = sum_j w_tj relu(q_I_tj . k_I_s)`` for s <= t; ``S_t`` = the
  ``index_topk`` keys of largest ``I(t, .)`` among s <= t, all while
  t < ``index_topk``;
- attention: softmax over s in ``S_t`` of ``(q_nope_i . k_nope_is + q_pe_i .
  k_pe_s) / sqrt(Hn + Hr)``, values ``v_is``, then ``W_o``;
- expert layer: sigmoid scores over ALL ``n_experts``, the top K of score +
  bias, their unbiased scores normalised and scaled; the layer adds the
  products of the experts it HOLDS (``cfg.held``: first, count) and the
  shared expert; a pair routed to an absent expert adds nothing.

Departures from the published model, each also in the benchmark's
configuration file under ``assumed``: rotary pairs are (2i, 2i+1) in the
attention and in the indexer (``rope_interleave`` / ``indexer_rope_
interleave`` are relabellings of columns under seeded weights); the indexer
scores in float32 here (bfloat16 with float32 accumulation in the program),
without the Hadamard rotation and the fp8 rounding of the published
inference code (an orthogonal map of q and k leaves q . k as it is); the
LayerNorm on the indexer's key has a bias and eps 1e-6 as in
DeepSeek-V3.2-Exp's code; no multi-token-prediction layer.
"""

import jax
import jax.numpy as jnp
import numpy as np

from tests.mla_reference import HI, _mm, _norm, _rope, _swiglu, route

INDEX_NORM_EPS = 1e-6


def _rope_head(x, theta, hr):
    """x (T, ..., Di): the first ``hr`` columns rotated."""
    return jnp.concatenate([_rope(x[..., :hr], theta), x[..., hr:]], -1)


def index_scores(cfg, h, cq, lw):
    """(T, T) float32: I(t, s), a head at a time; not masked."""
    t = h.shape[0]
    hi, di, hr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _rope_head(_mm(cq, lw["idx_wq"]).reshape(t, hi, di), cfg.rope_theta,
                   hr)
    k = _mm(h, lw["idx_wk"])
    mean = k.mean(-1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(((k - mean) ** 2).mean(-1, keepdims=True)
                                    + INDEX_NORM_EPS)
         * lw["idx_k_norm"] + lw["idx_k_bias"])
    k = _rope_head(k, cfg.rope_theta, hr)
    w = _mm(h, lw["idx_w"]) * (hi * di) ** -0.5
    scores = jnp.zeros((t, t), jnp.float32)
    for j in range(hi):
        scores = scores + w[:, j:j + 1] * jax.nn.relu(
            jnp.matmul(q[:, j], k.T, precision=HI))
    return scores


def selection(cfg, scores):
    """(mask (T, T) of the keys each query attends to, margin (T,): the
    ``index_topk``-th largest causal score less the next, infinite while a
    query has no more causal keys than that)."""
    t, k = scores.shape[0], cfg.index_topk
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, scores, -jnp.inf)
    if t <= k:
        return causal, jnp.full((t,), jnp.inf)
    top = jax.lax.top_k(s, k + 1)[0]
    mask = causal & (s >= top[:, k - 1:k])
    margin = jnp.where(jnp.arange(t) < k, jnp.inf, top[:, k - 1] - top[:, k])
    return mask, margin


def attention(cfg, h, lw, detail=None):
    t = h.shape[0]
    n, hn, hr, hv, r = (cfg.n_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.v_head_dim,
                        cfg.kv_lora_rank)
    if cfg.q_lora_rank:
        cq = _norm(_mm(h, lw["wq_a"]), lw["q_norm"], cfg.norm_eps)
        q = _mm(cq, lw["wq_b"]).reshape(t, n, hn + hr)
    else:
        q = _mm(h, lw["wq"]).reshape(t, n, hn + hr)
    q_nope, q_pe = q[..., :hn], _rope(q[..., hn:], cfg.rope_theta)
    kva = _mm(h, lw["wkv_a"])
    c = _norm(kva[:, :r], lw["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kva[:, r:], cfg.rope_theta)
    kv = _mm(c, lw["wkv_b"]).reshape(t, n, hn + hv)
    k_nope, v = kv[..., :hn], kv[..., hn:]
    mask = jnp.tril(jnp.ones((t, t), bool))
    if cfg.index_n_heads:
        mask, margin = selection(cfg, index_scores(cfg, h, cq, lw))
        if detail is not None:
            detail.append({"mask": np.asarray(mask),
                           "margin": np.asarray(margin)})
    out = []
    for i in range(n):                          # a head's T x T at a time
        s = (jnp.matmul(q_nope[:, i], k_nope[:, i].T, precision=HI)
             + jnp.matmul(q_pe[:, i], k_pe.T, precision=HI)) \
            * (hn + hr) ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        out.append(jnp.matmul(p, v[:, i], precision=HI))
    return _mm(jnp.concatenate(out, -1), lw["wo"])


def layer(cfg, x, lw, detail=None, held=None):
    """``held``: (first, count) in place of ``cfg.held`` (None there: all)."""
    x = x + attention(cfg, _norm(x, lw["attn_norm"], cfg.norm_eps), lw,
                      detail)
    h = _norm(x, lw["ffn_norm"], cfg.norm_eps)
    if "router" not in lw:
        return x + _swiglu(h, lw)
    gates, _ = route(cfg, h, lw["router"], lw["router_bias"])
    first, count = held or cfg.held or (0, cfg.n_experts)
    y = _swiglu(h, lw["shared"])
    for e in range(count):                      # the banks hold these alone
        y = y + gates[:, first + e:first + e + 1] * _swiglu(
            h, {k: w[e] for k, w in lw["banks"].items()})
    return x + y


def forward(params, tokens, cfg, detail=None):
    """tokens (T,) → logits (T, V), float32. ``detail``: a list that gets,
    a layer, the selection's mask and margin."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = p["embed"][jnp.asarray(tokens)]
    for name in ("dense_layers", "layers"):
        n = p[name]["attn_norm"].shape[0]
        for i in range(n):
            x = layer(cfg, x, jax.tree_util.tree_map(lambda a: a[i], p[name]),
                      detail)
    return np.asarray(_mm(_norm(x, p["final_norm"], cfg.norm_eps),
                          p["lm_head"]))

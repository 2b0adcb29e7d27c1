"""The plain reference of ``glm-5-ep16-l6``: the forward pass of GLM-5's
decoder layer (``glm_moe_dsa``: latent attention with a query rank, a learned
sparse-attention indexer, fine-grained experts of which this chip holds a
share) in float32, "highest".

Straight ``jax.numpy``: expanded heads, no cache, no batching of requests,
no tiling of queries, the full T x T score and index matrices a head at a
time (so that 9,728 positions fit), a sort for the selection's threshold,
no capacity, every HELD expert over every row with a gate of 0 where it was
not chosen. Nothing imported from the program; weights come again from the
seed (``bench_weights_dsa_moe``), a layer and an expert at a time. The
comparison's own arithmetic (``pack``, ``number``), the embedding, the head,
the rotary embedding and the int8 control's rounding are ``bench_reference``'s
and ``bench_reference_mla_moe``'s.

Equations (the published config.json's keys; DeepSeek-V2, arXiv:2405.04434,
section 2.1 for the latent attention; DeepSeek-V3.2-Exp's technical report
and inference code for the indexer), per layer with ``x = rmsnorm(h)``,
heads ``i``, indexer heads ``j``:

- ``c_q = rmsnorm(x W_qa)``, ``[q_nope_i ; q_pe_i] = c_q W_qb``, ``q_pe``
  rotated; ``[c ; k_pe] = x W_kva``, ``c = rmsnorm(c)``, ``k_pe`` rotated;
  ``[k_nope_i ; v_i] = c W_kvb``;
- ``q_I_j = rope(c_q W_Iq)`` and ``k_I = rope(layernorm(x W_Ik))``, the first
  ``qk_rope_head_dim`` columns rotated; ``w_j = (x W_Iw)_j / sqrt(Hi Di)``;
  ``I(t, s) = sum_j w_tj relu(q_I_tj . k_I_s)``, s <= t; ``S_t``: the
  ``index_topk`` keys of largest ``I(t, .)``, all while t < ``index_topk``;
- ``p = softmax_{s in S_t}((q_nope_i . k_nope_is + q_pe_i . k_pe_s) /
  sqrt(Hn + Hr))``, ``h += concat_i(sum_s p v_is) W_o``;
- layers before ``first_k_dense_replace``: ``h += SwiGLU(rmsnorm(h))``;
- the others: ``s = sigmoid(x' W_gate)`` over all ``router_width`` experts;
  chosen = top K of ``s + e_score_correction_bias``; ``w = s[chosen] /
  (sum s[chosen] + 1e-20) * routed_scaling_factor``; ``h += sum_{e held}
  w_e SwiGLU_e(x') + SwiGLU_shared(x')``: a chosen expert that this chip
  does not hold (``held_first``, ``n_routed_experts``) adds nothing here. No token is dropped.

Departures, each also in the configuration's file under ``assumed``: rotary
pairs are (2i, 2i+1) in the attention and the indexer alike; the indexer in
float32 here, without the published Hadamard rotation and fp8 rounding; the
LayerNorm on the indexer's key has a bias and eps 1e-6; seeded weights; no
multi-token-prediction layer.

``quant="int8"`` is the control, as in ``bench_reference``: every weight
matrix rounded to int8 per output column and every activation entering a
matrix product rounded to int8 per row; router, attention and index-score
products stay float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

import bench_reference as R0
import bench_reference_mla_moe as R1
import bench_weights
import bench_weights_dsa_moe as W
from bench_reference import F32, HI, _mm, _rmsnorm, _rope, _swiglu

INDEX_NORM_EPS = 1e-6


def _rope_head(x, theta, hr):
    """x (T, N, Di): the first ``hr`` columns rotated."""
    return jnp.concatenate([_rope(x[..., :hr], theta), x[..., hr:]], -1)


def _index_scores(q, k, w):
    """q (T, Hi, Di), k (T, Di), w (T, Hi) → I (T, T), a head at a time."""
    def head(acc, qw):
        q_j, w_j = qw
        return acc + w_j[:, None] * jax.nn.relu(
            jnp.matmul(q_j, k.T, precision=HI)), None

    t = q.shape[0]
    scores, _ = lax.scan(head, jnp.zeros((t, t), F32),
                         (jnp.moveaxis(q, 1, 0), w.T))
    return scores


def _selected(scores, topk):
    """(T, T) mask of the keys each query attends to: the causal ones, and
    past ``topk`` of them those at or above the topk-th largest score."""
    t = scores.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if t <= topk:
        return causal
    s = jnp.where(causal, scores, -jnp.inf)
    kth = jnp.sort(s, axis=-1)[:, t - topk][:, None]
    return causal & (s >= kth)


def _gates(x, router, bias, cfg):
    """x (T, D) → (T, E held): each token's weight on the held experts it
    chose, 0 elsewhere; and (T,) the margin of the choice over the router's
    whole width: the K-th biased score less the next."""
    m = W.dims(cfg)
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HI))
    more, idx = lax.top_k(scores + bias, m["K"] + 1)
    margin = more[:, m["K"] - 1] - more[:, m["K"]]
    idx = idx[:, :m["K"]]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    over_all = jnp.einsum("tk,tke->te", w,
                          jax.nn.one_hot(idx, m["Er"], dtype=F32))
    return over_all[:, m["first"]:m["first"] + m["E"]], margin


@partial(jax.jit, static_argnames=("cfg_key", "quant", "dense", "probe"))
def _layer(root, layer, x, cfg_key, quant, dense, probe=False):
    """One decoder layer over x (R, T, D), request by request. Returns the
    output, each position's router margin (infinite for a dense layer) and,
    with ``probe``, (R, T) the keys of each position's selected set that a
    selection from scores of bfloat16-rounded indexer queries and keys would
    swap for others (0 while a position selects everything)."""
    cfg = dict(cfg_key)
    m = W.dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = {n: W.make_slice(root, n, layer, 0, cfg, F32) for n in W.ATTN}
    k_bias = W.make_slice(root, "idx_k_bias", layer, 0, cfg)

    def one(x):
        t = x.shape[0]
        h = _rmsnorm(x, eps)
        cq = _rmsnorm(_mm(h, w["wq_a"], quant), eps)
        q = _mm(cq, w["wq_b"], quant).reshape(t, m["nh"], m["hn"] + m["hr"])
        q_nope, q_pe = q[..., :m["hn"]], _rope(q[..., m["hn"]:], theta)
        kva = _mm(h, w["wkv_a"], quant)
        c = _rmsnorm(kva[:, :m["r"]], eps)
        k_pe = _rope(kva[:, None, m["r"]:], theta)[:, 0]
        kv = _mm(c, w["wkv_b"], quant).reshape(t, m["nh"], m["hn"] + m["hv"])
        k_nope, v = kv[..., :m["hn"]], kv[..., m["hn"]:]
        # the indexer
        q_i = _rope_head(_mm(cq, w["idx_wq"], quant).reshape(
            t, m["hi"], m["di"]), theta, m["hr"])
        k_i = _mm(h, w["idx_wk"], quant)
        mean = jnp.mean(k_i, -1, keepdims=True)
        k_i = (k_i - mean) * lax.rsqrt(
            jnp.mean((k_i - mean) ** 2, -1, keepdims=True)
            + INDEX_NORM_EPS) + k_bias
        k_i = _rope_head(k_i[:, None], theta, m["hr"])[:, 0]
        w_i = _mm(h, w["idx_w"], quant) * (m["hi"] * m["di"]) ** -0.5
        mask = _selected(_index_scores(q_i, k_i, w_i), m["topk"])
        swapped = jnp.zeros((t,), jnp.int32)
        if probe:
            # reduce_precision, not a cast there and back: XLA may drop a
            # round trip through a narrower type
            rounded = _selected(_index_scores(
                lax.reduce_precision(q_i, 8, 7),
                lax.reduce_precision(k_i, 8, 7), w_i), m["topk"])
            swapped = jnp.sum(mask & ~rounded, axis=-1, dtype=jnp.int32)

        def head(args):
            qn, qp, kn, v_i = args
            s = (jnp.matmul(qn, kn.T, precision=HI)
                 + jnp.matmul(qp, k_pe.T, precision=HI)) \
                * (m["hn"] + m["hr"]) ** -0.5
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.matmul(p, v_i, precision=HI)

        o = lax.map(head, tuple(jnp.moveaxis(a, 1, 0)
                                for a in (q_nope, q_pe, k_nope, v)))
        x = x + _mm(jnp.moveaxis(o, 0, 1).reshape(t, -1), w["wo"], quant)
        # the feed-forward part, this request's rows alone
        h = _rmsnorm(x, eps)
        if dense:
            return (x + _swiglu(h, *(W.make_slice(root, n, layer, 0, cfg, F32)
                                     for n in W.DENSE), quant),
                    jnp.full((t,), jnp.inf, F32), swapped)
        g, margin = _gates(
            h, W.make_slice(root, "router", layer, 0, cfg, F32),
            W.make_slice(root, "router_bias", layer, 0, cfg), cfg)

        def expert(carry, e):
            y = _swiglu(h, *(W.make_slice(root, n, layer, m["first"] + e,
                                          cfg, F32) for n in W.EXPERT), quant)
            return carry + g[:, e][:, None] * y, None

        routed, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(m["E"]))
        shared = _swiglu(h, *(W.make_slice(root, n, layer, 0, cfg, F32)
                              for n in W.SHARED), quant)
        return x + routed + shared, margin, swapped

    return lax.map(one, x)


KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
        "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "index_n_heads", "index_head_dim", "index_topk",
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "router_width", "held_first", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "vocab_size",
        "rms_norm_eps")


def model_key(cfg: dict) -> tuple:
    """The numbers of the configuration that the equations use, hashable
    (``rope_theta`` out of its published group). What a run must not guess
    is refused here."""
    for k, want in (("n_group", 1), ("topk_group", 1),
                    ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                    ("norm_topk_prob", True), ("moe_layer_freq", 1),
                    ("num_nextn_predict_layers", 0)):
        if cfg.get(k, want) != want:
            raise ValueError(f"the reference implements {k}={want!r}, the "
                             f"configuration says {cfg[k]!r}")
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("rope_theta", cfg["rope_parameters"]["rope_theta"]),)


def forward(seed: int, cfg: dict, tokens, prompt_lens=None, alt=None,
            quant=None, probe=False):
    """tokens (R, T) int32, right-padded → what ``bench_reference._head``
    reads at every position, each (R, T), and (R, T) the least router margin
    over the layers; with ``probe`` also (L, R, T) the swapped keys of
    :func:`_layer`. ``prompt_lens`` is not used."""
    del prompt_lens
    key = model_key(cfg)
    root = bench_weights.root_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    alt = jnp.zeros_like(tokens) if alt is None else jnp.asarray(alt,
                                                                 jnp.int32)
    head_key = R0.model_key(dict(key))
    x = R0._embed(root, tokens, head_key)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    swapped = []
    for layer in range(cfg["num_hidden_layers"]):
        x, m, s = _layer(root, jnp.int32(layer), x, key, quant,
                         layer < cfg["first_k_dense_replace"], probe)
        margin = jnp.minimum(margin, m)
        swapped.append(s)
    out = R0._head(root, x, tokens, alt, head_key, quant), margin
    return out + (jnp.stack(swapped),) if probe else out


def compare(seed: int, cfg: dict, sample, served_logprobs, t_pad: int,
            names, control=False, keep_positions=False) -> dict:
    """``bench_reference_mla_moe.compare`` over this module's
    :func:`forward`. With ``keep_positions`` (the tool that sets limits)
    also ``swapped_keys``: of the (layer, position) pairs that select, the
    share whose set a bfloat16 rounding of the indexer's queries and keys
    changes, and by how many keys on average and at most."""
    import numpy as np
    toks, p_lens, mask = R0.pack(sample, t_pad)
    lp_served = np.zeros(toks.shape, np.float32)
    for r, lps in enumerate(served_logprobs):
        lp_served[r, p_lens[r] - 1:p_lens[r] - 1 + len(lps)] = lps
    ctl = None
    if control:
        ctl = {k: np.asarray(v) for k, v in
               forward(seed, cfg, toks, quant="int8")[0].items()}
    ref, margin, *probe = forward(
        seed, cfg, toks, alt=None if ctl is None else ctl["top"],
        probe=keep_positions)
    ref = {k: np.asarray(v) for k, v in ref.items()}
    margin = np.asarray(margin)[mask]
    decided = margin >= cfg.get("router_margin", 0.0)
    gap, err = ref["gap_next"][mask], np.abs(lp_served - ref["lp_next"])[mask]
    number = R1.number
    out = {"finite": bool(np.isfinite(gap).all() and np.isfinite(err).all()),
           "tokens_compared": int(mask.sum()),
           "decided_share": float(decided.mean()),
           "numbers": {n: number(n, gap, err, decided) for n in names}}
    pos = {"gap": gap, "err": err, "margin": margin}
    if ctl is not None:
        c_gap = ref["gap_alt"][mask]
        c_err = np.abs(ctl["lp_top"] - ref["lp_alt"])[mask]
        out["control"] = {n: number(n, c_gap, c_err, decided) for n in names}
        pos.update({"control_gap": c_gap, "control_err": c_err})
    if keep_positions:
        out["positions"] = {k: v.tolist() for k, v in pos.items()}
        s = np.asarray(probe[0])[:, mask & (np.arange(t_pad)[None, :]
                                            >= cfg["index_topk"])]
        out["swapped_keys"] = {
            "pairs": int(s.size), "share_changed": float((s > 0).mean())
            if s.size else 0.0,
            "mean_swapped": float(s.mean()) if s.size else 0.0,
            "max_swapped": int(s.max()) if s.size else 0}
    return out

"""The plain reference of ``kimi-vl-a3b-l9``: the forward pass of a latent-
attention decoder with fine-grained experts in float32, "highest".

Straight ``jax.numpy``: expanded heads, no cache, no batching of requests in
attention, no capacity, every expert over every row with a gate of 0 where it
was not chosen. Nothing imported from the program; weights come again from
the seed (``bench_weights_mla_moe``), a layer and an expert at a time, so a
float32 expert layer (2.3 GB) is the most that exists. The comparison's own
arithmetic (``pack``, ``number``), the embedding, the head, the rotary
embedding and the int8 control's rounding are ``bench_reference``'s.

Published equations (DeepSeek-V2, arXiv:2405.04434, section 2.1; the
``modeling_deepseek.py`` that Kimi-VL-A3B-Instruct's config.json names), per
layer with ``h = rmsnorm(x)``, heads ``i``:

- ``q_i = [q_nope_i ; rope(q_pe_i)] = h W_q``;
  ``[c_raw ; k_pe_raw] = h W_kva``, ``c = rmsnorm(c_raw)``,
  ``k_pe = rope(k_pe_raw)`` (one for all heads);
  ``[k_nope_i ; v_i] = c W_kvb``;
  ``score_i(t, s) = (q_nope_i(t) k_nope_i(s) + q_pe_i(t) k_pe(s)) /
  sqrt(Hn + Hr)``, causal softmax, ``x += concat_i(sum_s p v_i(s)) W_o``;
- layers before ``first_k_dense_replace``: ``x += SwiGLU(rmsnorm(x))``;
- the others: ``s = sigmoid(h' W_gate)`` over all experts; chosen = top K of
  ``s + e_score_correction_bias`` (``n_group`` = ``topk_group`` = 1);
  ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``;
  ``x += sum_e w_e SwiGLU_e(h') + SwiGLU_shared(h')``. No token is dropped.

Departures, each also in the configuration's file under ``assumed``: rotary
pairs are (2i, 2i+1) (the published code permutes the rope columns before a
half-split rotation: a relabelling of columns of ``W_q`` / ``W_kva``);
seeded weights, norm weights 1.

``quant="int8"`` is the control, as in ``bench_reference``: every weight
matrix rounded to int8 per output column and every activation entering a
matrix product rounded to int8 per row; router and attention products stay
float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

import bench_reference as R0
import bench_weights
import bench_weights_mla_moe as W
from bench_reference import F32, HI, _mm, _rmsnorm, _rope, _swiglu


def _gates(x, router, bias, cfg):
    """x (T, D) → (T, E): each token's weight on the experts it chose, 0
    elsewhere; and (T,) the margin of the choice: the K-th biased score less
    the next."""
    m = W.dims(cfg)
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HI))
    more, idx = lax.top_k(scores + bias, m["K"] + 1)
    margin = more[:, m["K"] - 1] - more[:, m["K"]]
    idx = idx[:, :m["K"]]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    return jnp.einsum("tk,tke->te", w,
                      jax.nn.one_hot(idx, m["E"], dtype=F32)), margin


def _moe(h, root, layer, cfg, quant):
    """h (R, T, D): the routed experts one by one over all rows, and the
    shared SwiGLU."""
    router = W.make_slice(root, "router", layer, 0, cfg, F32)
    bias = W.make_slice(root, "router_bias", layer, 0, cfg)
    g, margin = jax.vmap(lambda r: _gates(r, router, bias, cfg))(h)

    def one(carry, e):
        y = _swiglu(h, *(W.make_slice(root, n, layer, e, cfg, F32)
                         for n in W.EXPERT), quant)
        return carry + g[..., e][..., None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h), jnp.arange(W.dims(cfg)["E"]))
    shared = _swiglu(h, *(W.make_slice(root, n, layer, 0, cfg, F32)
                          for n in W.SHARED), quant)
    return out + shared, margin


@partial(jax.jit, static_argnames=("cfg_key", "quant", "dense"))
def _layer(root, layer, x, cfg_key, quant, dense):
    """One decoder layer over x (R, T, D), attention request by request.
    Returns the output and each position's router margin (infinite for a
    dense layer)."""
    cfg = dict(cfg_key)
    m = W.dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = {n: W.make_slice(root, n, layer, 0, cfg, F32) for n in W.ATTN}

    def attend(x):
        t = x.shape[0]
        h = _rmsnorm(x, eps)
        q = _mm(h, w["wq"], quant).reshape(t, m["nh"], m["hn"] + m["hr"])
        q_nope, q_pe = q[..., :m["hn"]], _rope(q[..., m["hn"]:], theta)
        kva = _mm(h, w["wkv_a"], quant)
        c = _rmsnorm(kva[:, :m["r"]], eps)
        k_pe = _rope(kva[:, None, m["r"]:], theta)[:, 0]
        kv = _mm(c, w["wkv_b"], quant).reshape(t, m["nh"], m["hn"] + m["hv"])
        k_nope, v = kv[..., :m["hn"]], kv[..., m["hn"]:]
        s = (jnp.einsum("tnh,snh->nts", q_nope, k_nope, precision=HI)
             + jnp.einsum("tnh,sh->nts", q_pe, k_pe, precision=HI)) \
            * (m["hn"] + m["hr"]) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        o = jnp.einsum("nts,snh->tnh", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        return x + _mm(o.reshape(t, -1), w["wo"], quant)

    x = lax.map(attend, x)
    h = _rmsnorm(x, eps)
    if dense:
        return (x + _swiglu(h, *(W.make_slice(root, n, layer, 0, cfg, F32)
                                 for n in W.DENSE), quant),
                jnp.full(x.shape[:2], jnp.inf, F32))
    y, margin = _moe(h, root, layer, cfg, quant)
    return x + y, margin


KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
        "vocab_size", "rope_theta", "rms_norm_eps")


def model_key(cfg: dict) -> tuple:
    """The numbers of the configuration that the equations use, hashable.
    What a run must not guess is refused here."""
    for k, want in (("n_group", 1), ("topk_group", 1), ("q_lora_rank", None),
                    ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                    ("norm_topk_prob", True), ("moe_layer_freq", 1)):
        if cfg.get(k, want) != want:
            raise ValueError(f"the reference implements {k}={want!r}, the "
                             f"configuration says {cfg[k]!r}")
    return tuple((k, cfg[k]) for k in KEYS)


def forward(seed: int, cfg: dict, tokens, prompt_lens=None, alt=None,
            quant=None):
    """tokens (R, T) int32, right-padded → what ``bench_reference._head``
    reads at every position, each (R, T), and (R, T) the least router margin
    over the layers. ``prompt_lens`` is not used: nothing here depends on
    where a prompt ends."""
    del prompt_lens
    key = model_key(cfg)
    root = bench_weights.root_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    alt = jnp.zeros_like(tokens) if alt is None else jnp.asarray(alt,
                                                                 jnp.int32)
    head_key = R0.model_key(cfg)
    x = R0._embed(root, tokens, head_key)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    for layer in range(cfg["num_hidden_layers"]):
        x, m = _layer(root, jnp.int32(layer), x, key, quant,
                      layer < cfg["first_k_dense_replace"])
        margin = jnp.minimum(margin, m)
    return R0._head(root, x, tokens, alt, head_key, quant), margin


def number(name: str, gap, err, decided) -> float:
    """``bench_reference.number`` and, beside its names,
    ``logprob_err_p<digits>[_decided]``: the quantile 0.<digits> of the
    log-probability error over all served tokens, or over the decided ones.
    With 64 experts top-6 a bfloat16 run and the float32 reference disagree
    about a router near a tie at most positions of the stack, and each flip
    moves the logits by far more than rounding does: a mean is theirs, a
    median is not."""
    import re

    import numpy as np
    m = re.fullmatch(r"logprob_err_p(\d+)(_decided)?", name)
    if not m:
        return R0.number(name, gap, err, decided)
    over = err[decided] if m.group(2) and decided.any() else err
    return float(np.quantile(over, float("0." + m.group(1))))


def compare(seed: int, cfg: dict, sample, served_logprobs, t_pad: int,
            names, control=False, keep_positions=False) -> dict:
    """``bench_reference.compare`` over this module's :func:`forward`: the
    numbers ``names`` that decide ``correct``, the control's with
    ``control``, the per-position readings with ``keep_positions``."""
    import numpy as np
    toks, p_lens, mask = R0.pack(sample, t_pad)
    lp_served = np.zeros(toks.shape, np.float32)
    for r, lps in enumerate(served_logprobs):
        lp_served[r, p_lens[r] - 1:p_lens[r] - 1 + len(lps)] = lps
    ctl = None
    if control:
        ctl = {k: np.asarray(v) for k, v in
               forward(seed, cfg, toks, quant="int8")[0].items()}
    ref, margin = forward(seed, cfg, toks,
                          alt=None if ctl is None else ctl["top"])
    ref = {k: np.asarray(v) for k, v in ref.items()}
    margin = np.asarray(margin)[mask]
    decided = margin >= cfg.get("router_margin", 0.0)
    gap, err = ref["gap_next"][mask], np.abs(lp_served - ref["lp_next"])[mask]
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
    return out

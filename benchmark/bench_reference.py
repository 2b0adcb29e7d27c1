"""The plain reference: a decoder's forward pass in float32, "highest".

Straight ``jax.numpy``: no kernel, no cache, no batching tricks, nothing
imported from the program and nothing the program has made. Weights come
again from the seed (``bench_weights``), one layer (one expert) at a time,
so the whole model never exists in float32.

Published equations (Mistral-7B / Mixtral-8x7B model cards and papers):
pre-norm RMSNorm, grouped-query attention with rotary embeddings, SwiGLU;
for the expert layer a softmax router, the top ``K`` experts, their gates
renormalised to sum to one. Departures, each also stated in the
configuration's file:

- rotary pairs are (2i, 2i+1), the original layout, where the Hugging Face
  code pairs (i, i + Hd/2): the same function up to a fixed permutation of
  the columns of wq and wk, which seeded random weights do not see;
- the published Mixtral drops no token. The program's dispatch does, at
  prefill: with ``capacity_factor`` c, a prompt of P tokens gives each expert
  ``max(1, floor(c * P * K / E))`` places, filled in order of (token, choice);
  later choices get nothing from that expert. The reference applies the same
  rule to the prompt's positions when the configuration states
  ``capacity_factor``, and none to decoded positions (one token per step
  cannot overflow).

``quant="int8"`` is the control: the same pass with every weight matrix
rounded to int8 per output column and every activation that enters a matrix
product rounded to int8 per row (router and attention products stay
float32), the nearest precision below bfloat16 that a later PR could be
tempted by.
"""

from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

import bench_weights as W

HI = lax.Precision.HIGHEST
F32 = jnp.float32


def _fq(x, axis):
    """Round to int8 and back, symmetric, one scale per slice along axis."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant):
    if quant == "int8":
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (T, N, Hd): rotate pairs (2i, 2i+1) by position * theta^(-2i/Hd)."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v):
    """Causal, grouped: q (T, N, Hd), k and v (T, NKV, Hd)."""
    t, nh, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(t, nkv, nh // nkv, hd)
    s = jnp.einsum("tkgh,skh->kgts", qg, k, precision=HI) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgts,skh->tkgh", p, v, precision=HI).reshape(t, nh, hd)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def _gates(x, router, prompt_len, cfg):
    """x (T, D) of one request → (T, E): each token's renormalised gate on
    the experts that take it, 0 elsewhere; and (T,) the margin by which the
    router's choice of K experts stands: the K-th probability less the
    next."""
    m = W.dims(cfg)
    t, e_n, k_n = x.shape[0], m["E"], m["K"]
    probs = jax.nn.softmax(jnp.matmul(x, router, precision=HI), axis=-1)
    more, idx = lax.top_k(probs, k_n + 1)
    margin = more[:, k_n - 1] - more[:, k_n]    # how far the choice is from a tie
    vals, idx = more[:, :k_n], idx[:, :k_n]
    gates = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-9)
    onehot = jax.nn.one_hot(idx, e_n, dtype=jnp.int32)          # (T, K, E)
    keep = jnp.ones((t, k_n), bool)
    cf = cfg.get("capacity_factor")
    if cf is not None:
        in_prompt = (jnp.arange(t) < prompt_len)[:, None]
        flat = (onehot * in_prompt[..., None]).reshape(t * k_n, e_n)
        place = jnp.sum((jnp.cumsum(flat, 0) - flat) * flat, -1) \
            .reshape(t, k_n)
        cap = jnp.maximum(1, jnp.floor(
            cf * prompt_len * k_n / e_n).astype(jnp.int32))
        keep = (place < cap) | ~in_prompt
    return jnp.einsum("tk,tke->te", gates * keep, onehot.astype(F32)), margin


def _moe(x, root, layer, prompt_lens, cfg, quant):
    """x (R, T, D). Gates request by request, then the experts one by one
    over all rows: a row's gate is 0 on an expert that does not take it."""
    router = W.make_slice(root, "router", layer, 0, cfg, F32)
    g, margin = jax.vmap(lambda r, p: _gates(r, router, p, cfg))(
        x, prompt_lens)

    def one(carry, e):
        y = _swiglu(x, W.make_slice(root, "e_gate", layer, e, cfg, F32),
                    W.make_slice(root, "e_up", layer, e, cfg, F32),
                    W.make_slice(root, "e_down", layer, e, cfg, F32), quant)
        return carry + g[..., e][..., None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(W.dims(cfg)["E"]))
    return out, margin


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _layer(root, layer, x, prompt_lens, cfg_key, quant):
    """One decoder layer over x (R, T, D), attention request by request.
    Returns the layer's output and each position's router margin (infinite
    for a dense layer)."""
    cfg = dict(cfg_key)
    m = W.dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = {n: W.make_slice(root, n, layer, 0, cfg, F32)
         for n in ("wq", "wk", "wv", "wo")}
    if not m["E"]:
        w.update({n: W.make_slice(root, n, layer, 0, cfg, F32)
                  for n in ("w_gate", "w_up", "w_down")})

    def attend(x):
        t = x.shape[0]
        h = _rmsnorm(x, eps)
        q = _rope(_mm(h, w["wq"], quant).reshape(t, m["nh"], m["hd"]), theta)
        k = _rope(_mm(h, w["wk"], quant).reshape(t, m["nkv"], m["hd"]), theta)
        v = _mm(h, w["wv"], quant).reshape(t, m["nkv"], m["hd"])
        return x + _mm(_attention(q, k, v).reshape(t, -1), w["wo"], quant)

    x = lax.map(attend, x)
    h = _rmsnorm(x, eps)
    if m["E"]:
        y, margin = _moe(h, root, layer, prompt_lens, cfg, quant)
        return x + y, margin
    return (x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant),
            jnp.full(x.shape[:2], jnp.inf, F32))


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(root, x, tokens, alt, cfg_key, quant):
    """Per position t, which predicts token t + 1: under these logits, how
    far the logit of the next token ``tokens[t + 1]`` and of ``alt[t]`` lies
    below the best (``gap_*``), their log-probabilities (``lp_*``), and the
    token these logits put first (``top``) with its log-probability. Row by
    row, so the sample's logits never exist all at once."""
    cfg = dict(cfg_key)
    head = W.make_slice(root, "lm_head", 0, 0, cfg, F32)

    def take(m, i):
        return jnp.take_along_axis(m, i[:, None], -1)[:, 0]

    def one(args):
        r, nxt, a = args
        logits = _mm(_rmsnorm(r, cfg["rms_norm_eps"]), head, quant)
        lp = jax.nn.log_softmax(logits, axis=-1)
        best, top = logits.max(-1), jnp.argmax(logits, -1)
        return {"gap_next": best - take(logits, nxt), "lp_next": take(lp, nxt),
                "gap_alt": best - take(logits, a), "lp_alt": take(lp, a),
                "top": top, "lp_top": take(lp, top)}

    return lax.map(one, (x, jnp.roll(tokens, -1, axis=1), alt))


@partial(jax.jit, static_argnames=("cfg_key",))
def _embed(root, tokens, cfg_key):
    return W.make_slice(root, "embed", 0, 0, dict(cfg_key), F32)[tokens]


def model_key(cfg: dict) -> tuple:
    """The numbers of a configuration that the equations use, hashable."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "vocab_size", "rope_theta", "rms_norm_eps", "num_local_experts",
            "num_experts_per_tok", "capacity_factor")
    return tuple((k, cfg[k]) for k in keys if cfg.get(k) is not None)


def forward(seed: int, cfg: dict, tokens, prompt_lens, alt=None, quant=None):
    """tokens (R, T) int32, right-padded; prompt_lens (R,) → what
    :func:`_head` reads at every position, each (R, T), and (R, T) the
    least router margin over the layers. ``alt`` (R, T): a second token per
    position to score (the control's choice); the padding id if None."""
    key = model_key(cfg)
    root = W.root_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    alt = jnp.zeros_like(tokens) if alt is None else jnp.asarray(alt, jnp.int32)
    x = _embed(root, tokens, key)
    margin = jnp.full(tokens.shape, jnp.inf, F32)
    for layer in range(cfg["num_hidden_layers"]):
        x, m = _layer(root, jnp.int32(layer), x, prompt_lens, key, quant)
        margin = jnp.minimum(margin, m)
    return _head(root, x, tokens, alt, key, quant), margin


def pack(sample, t_pad: int):
    """[(prompt, served tokens)] → right-padded (R, t_pad) ids, prompt
    lengths and the mask of rows that predict a served token."""
    import numpy as np
    toks = np.zeros((len(sample), t_pad), np.int32)
    mask = np.zeros((len(sample), t_pad), bool)
    p_lens = np.zeros((len(sample),), np.int32)
    for r, (prompt, served) in enumerate(sample):
        seq = list(prompt) + list(served)
        if len(seq) > t_pad:
            raise ValueError(f"request of {len(seq)} tokens over {t_pad}")
        toks[r, :len(seq)] = seq
        p_lens[r] = len(prompt)
        mask[r, len(prompt) - 1:len(seq) - 1] = True
    return toks, p_lens, mask


def number(name: str, gap, err, decided) -> float:
    """One number a cell's limits file may name, over the served tokens of
    the sample. ``gap``: by how much a token's reference logit lies below
    the reference's best; ``err``: between its log-probability as served and
    as the reference has it; ``decided``: positions at which the reference's
    router is far from a tie in every layer (all, for a dense model).
    ``logit_gap_max``, ``logprob_err_mean``, or ``logit_gap_p<digits>_decided``:
    the quantile 0.<digits> of the gap over the decided positions."""
    import numpy as np
    if name == "logit_gap_max":
        return float(gap.max())
    if name == "logprob_err_mean":
        return float(err.mean())
    m = re.fullmatch(r"logit_gap_p(\d+)_decided", name)
    if not m:
        raise KeyError(f"no number named {name!r}")
    return float(np.quantile(gap[decided] if decided.any() else gap,
                             float("0." + m.group(1))))


def compare(seed: int, cfg: dict, sample, served_logprobs, t_pad: int,
            names, control=False, keep_positions=False) -> dict:
    """The numbers ``names`` that decide ``correct`` for a served model.
    ``sample`` is [(prompt ids, served ids)], ``served_logprobs`` the timed
    path's own log-probability of each served token. With ``control`` also
    the same numbers for the control (``control``): the reference in int8
    put in the program's place, judged at every position by the token it
    puts first and its own log-probability of it. ``keep_positions``: the
    per-position readings too, for the tool that sets limits."""
    import numpy as np
    toks, p_lens, mask = pack(sample, t_pad)
    lp_served = np.zeros(toks.shape, np.float32)
    for r, lps in enumerate(served_logprobs):
        lp_served[r, p_lens[r] - 1:p_lens[r] - 1 + len(lps)] = lps
    ctl = None
    if control:
        ctl = {k: np.asarray(v) for k, v in
               forward(seed, cfg, toks, p_lens, quant="int8")[0].items()}
    ref, margin = forward(seed, cfg, toks, p_lens,
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

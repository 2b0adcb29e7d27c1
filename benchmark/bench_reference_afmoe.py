"""The plain reference of ``trinity-mini-l5``: the forward pass of
Trinity-Mini's decoder (``afmoe``: window and full attention layers,
fine-grained sigmoid-routed experts) in float32, under
``jax.default_matmul_precision("highest")``.

Straight ``jax.numpy``: no cache, no ring, no batching of requests in
attention, the whole T keys of every query masked (the band on a window
layer, the causal triangle on a full one), computed a head and a block of
queries at a time so that 9,728 positions fit; no capacity, every expert
over every row with a gate of 0 where it was not chosen. Nothing imported
from the program; weights come again from the seed
(``bench_weights_afmoe``), a layer and an expert at a time. The comparison's
own arithmetic (``pack``, ``number``), the embedding, the head, the rotary
embedding and the int8 control's rounding are ``bench_reference``'s and
``bench_reference_mla_moe``'s.

Equations (the published config.json's keys and the afmoe modeling code's
five parts that have none), per layer with ``h = rmsnorm(x)``, heads ``i``:

- ``q_i = rmsnorm(h W_q)_i``, ``k_j = rmsnorm(h W_k)_j``, ``v_j = (h W_v)_j``
  (a per-head norm of q and k); on a ``sliding_attention`` layer q and k
  rotated (RoPE, ``rope_theta``), on a ``full_attention`` layer not;
- ``p = softmax_s(q_i(t) . k_j(s) / sqrt(head_dim))`` over s <= t, and on a
  sliding_attention layer over ``t - sliding_window < s`` too;
  ``o = concat_i(sum_s p v_j(s)) * sigmoid(h W_gate)``;
  ``x += rmsnorm(o W_o)``;
- ``h' = rmsnorm(x)``; the first ``num_dense_layers`` layers
  ``x += rmsnorm(SwiGLU(h'))``; the others ``s = sigmoid(h' W_router)``,
  chosen = top K of ``s + expert_bias``, ``w = s[chosen] / (sum s[chosen] +
  1e-20) * route_scale``, ``x += rmsnorm(sum_e w_e SwiGLU_e(h') +
  SwiGLU_shared(h'))``. No token is dropped;
- the embedding times sqrt(hidden_size); the head untied.

Departures, each also in the configuration's file under ``assumed``: rotary
pairs are (2i, 2i+1) (the published code rotates halves: a relabelling of
columns of ``W_q`` / ``W_k``); seeded weights, norm weights 1.

``quant="int8"`` is the control, as in ``bench_reference``: every weight
matrix rounded to int8 per output column and every activation entering a
matrix product rounded to int8 per row; router and attention products stay
float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

import bench_reference as R0
import bench_reference_mla_moe as R1
import bench_weights
import bench_weights_afmoe as W
from bench_reference import F32, HI, _mm, _rmsnorm, _rope, _swiglu

# queries a block of a head's scores: a (block, T) float32 tile at a time
QUERY_BLOCK = 1024


def _gates(x, router, bias, cfg):
    """x (M, D) → (M, E): each row's weight on the experts it chose, 0
    elsewhere; and (M,) the margin of the choice: the K-th biased score less
    the next."""
    m = W.dims(cfg)
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HI))
    more, idx = lax.top_k(scores + bias, m["K"] + 1)
    margin = more[:, m["K"] - 1] - more[:, m["K"]]
    idx = idx[:, :m["K"]]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * cfg["route_scale"]
    return jnp.einsum("tk,tke->te", w,
                      jax.nn.one_hot(idx, m["E"], dtype=F32)), margin


def _moe(h, root, layer, cfg, quant):
    """h (R, T, D): the routed experts one by one over every row, and the
    shared SwiGLU; and (R, T) the router's margins."""
    rows = h.reshape(-1, h.shape[-1])
    g, margin = _gates(rows, W.make_slice(root, "router", layer, 0, cfg, F32),
                       W.make_slice(root, "router_bias", layer, 0, cfg), cfg)

    def one(carry, e):
        y = _swiglu(rows, *(W.make_slice(root, n, layer, e, cfg, F32)
                            for n in W.EXPERT), quant)
        return carry + g[:, e][:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(rows), jnp.arange(W.dims(cfg)["E"]))
    out = out + _swiglu(rows, *(W.make_slice(root, n, layer, 0, cfg, F32)
                                for n in W.SHARED), quant)
    return out.reshape(h.shape), margin.reshape(h.shape[:2])


@partial(jax.jit, static_argnames=("cfg_key", "quant", "window", "dense"))
def _layer(root, layer, x, cfg_key, quant, window, dense):
    """One decoder layer over x (R, T, D), attention request by request.
    Returns the output and each position's router margin (infinite for a
    dense layer)."""
    cfg = dict(cfg_key)
    m = W.dims(cfg)
    eps, theta, hd = cfg["rms_norm_eps"], cfg["rope_theta"], m["hd"]
    band = m["W"] if window else None
    w = {n: W.make_slice(root, n, layer, 0, cfg, F32) for n in W.ATTN}

    def attend(x):
        t = x.shape[0]
        tile = math.gcd(t, QUERY_BLOCK)
        h = _rmsnorm(x, eps)
        q = _rmsnorm(_mm(h, w["wq"], quant).reshape(t, m["nh"], hd), eps)
        k = _rmsnorm(_mm(h, w["wk"], quant).reshape(t, m["nkv"], hd), eps)
        v = _mm(h, w["wv"], quant).reshape(t, m["nkv"], hd)
        if window:
            q, k = _rope(q, theta), _rope(k, theta)

        def head(args):
            q_i, j = args                      # a query head, its KV head
            k_j = lax.dynamic_index_in_dim(k, j, 1, keepdims=False)
            v_j = lax.dynamic_index_in_dim(v, j, 1, keepdims=False)

            def block(start):
                s = jnp.matmul(lax.dynamic_slice_in_dim(q_i, start, tile),
                               k_j.T, precision=HI) * hd ** -0.5
                i = start + jnp.arange(tile)[:, None]
                j_ = jnp.arange(t)[None, :]
                keep = j_ <= i
                if band is not None:
                    keep = keep & (j_ > i - band)
                p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
                return jnp.matmul(p, v_j, precision=HI)

            return lax.map(block, jnp.arange(0, t, tile)).reshape(t, hd)

        o = lax.map(head, (jnp.moveaxis(q, 1, 0),
                           jnp.arange(m["nh"]) // (m["nh"] // m["nkv"])))
        o = jnp.moveaxis(o, 0, 1).reshape(t, -1)
        o = o * jax.nn.sigmoid(_mm(h, w["w_attn_gate"], quant))
        return x + _rmsnorm(_mm(o, w["wo"], quant), eps)

    x = lax.map(attend, x)
    h = _rmsnorm(x, eps)
    if dense:
        y = _swiglu(h, *(W.make_slice(root, n, layer, 0, cfg, F32)
                         for n in W.DENSE), quant)
        return (x + _rmsnorm(y, eps), jnp.full(x.shape[:2], jnp.inf, F32))
    y, margin = _moe(h, root, layer, cfg, quant)
    return x + _rmsnorm(y, eps), margin


KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "num_experts_per_tok", "num_shared_experts", "route_scale",
        "sliding_window", "vocab_size", "rope_theta", "rms_norm_eps")


def model_key(cfg: dict) -> tuple:
    """The numbers of the configuration that the equations use, hashable
    (``layer_types`` as a tuple). What a run must not guess is refused
    here."""
    for k, want in (("score_func", "sigmoid"), ("route_norm", True),
                    ("n_group", 1), ("topk_group", 1),
                    ("num_expert_groups", 1), ("num_limited_groups", 1),
                    ("mup_enabled", True), ("rope_scaling", None),
                    ("tie_word_embeddings", False)):
        if cfg.get(k, want) != want:
            raise ValueError(f"the reference implements {k}={want!r}, the "
                             f"configuration says {cfg[k]!r}")
    if not set(cfg["layer_types"]) <= {W.WINDOW, W.FULL}:
        raise ValueError(f"the reference runs {W.WINDOW!r} and {W.FULL!r} "
                         f"layer_types, not "
                         f"{sorted(set(cfg['layer_types']))}")
    W.dims(cfg)
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("layer_types", tuple(cfg["layer_types"])),)


def forward(seed: int, cfg: dict, tokens, prompt_lens=None, alt=None,
            quant=None):
    """tokens (R, T) int32, right-padded → what ``bench_reference._head``
    reads at every position, each (R, T), and (R, T) the least router margin
    over the layers. ``prompt_lens`` is not used."""
    del prompt_lens
    key = model_key(cfg)
    m = W.dims(cfg)
    root = bench_weights.root_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    alt = jnp.zeros_like(tokens) if alt is None else jnp.asarray(alt,
                                                                 jnp.int32)
    head_key = R0.model_key(dict(key))
    with jax.default_matmul_precision("highest"):
        x = R0._embed(root, tokens, head_key) * float(m["d"]) ** 0.5
        margin = jnp.full(tokens.shape, jnp.inf, F32)
        for layer in range(m["L"]):
            x, mg = _layer(root, jnp.int32(layer), x, key, quant,
                           m["types"][layer] == W.WINDOW, layer < m["Ld"])
            margin = jnp.minimum(margin, mg)
        return R0._head(root, x, tokens, alt, head_key, quant), margin


def compare(seed: int, cfg: dict, sample, served_logprobs, t_pad: int,
            names, control=False, keep_positions=False) -> dict:
    """``bench_reference_mla_moe.compare`` over this module's
    :func:`forward`: the numbers ``names`` that decide ``correct``, the
    int8 control's with ``control``, the per-position readings with
    ``keep_positions``."""
    import numpy as np
    if control not in (False, True, "int8"):
        raise ValueError(f"no control {control!r}: this reference has int8")
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
           "numbers": {n: R1.number(n, gap, err, decided) for n in names}}
    pos = {"gap": gap, "err": err, "margin": margin}
    if ctl is not None:
        c_gap = ref["gap_alt"][mask]
        c_err = np.abs(ctl["lp_top"] - ref["lp_alt"])[mask]
        out["control"] = {n: R1.number(n, c_gap, c_err, decided)
                          for n in names}
        pos.update({"control_gap": c_gap, "control_err": c_err})
    if keep_positions:
        out["positions"] = {k: v.tolist() for k, v in pos.items()}
    return out

"""The plain reference of ``granite-4.0-h-micro``: the forward pass of a
hybrid Mamba-2 / attention decoder in float32, "highest".

Straight ``jax.numpy``: the recurrence run a token at a time (not the
program's chunked form), full causal attention, no cache, no batching of
requests in attention. Nothing imported from the program; weights come
again from the seed (``bench_weights_hybrid``), a layer at a time. The
comparison's own arithmetic (``pack``) and the int8 control's rounding are
``bench_reference``'s; the numbers compared (``number``) are
``bench_reference_mla_moe``'s, over every position (a dense model's
positions are all decided).

Published equations (the ``granitemoehybrid`` modelling code that
Granite-4.0-H-Micro's config.json names; the Mamba-2 layer of Dao and Gu,
arXiv:2405.21060), per layer with ``h = rmsnorm(x)``:

- a Mamba-2 layer (``layer_types`` "mamba"): ``[z ; xBC ; dt_raw] = h W_in``
  (widths 4,096 / 4,352 / 64, no bias); ``xBC = silu(conv(xBC))``, a causal
  depthwise convolution of 4 taps with a bias; ``[x ; B ; C] = xBC`` (4,096 /
  128 / 128), ``x`` as 64 heads of 64, B and C one group for all heads;
  ``dt = softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``; per head i and
  token t, ``S_t = exp(dt_t,i A_i) S_{t-1} + dt_t,i x_t,i ⊗ B_t`` (64 × 128,
  S_0 = 0), ``y_t,i = S_t C_t + D_i x_t,i``; ``y = rmsnorm(y ⊙ silu(z))``
  over all 4,096 (the gate before the norm, eps 1e-5);
  ``x += 0.22 · (y W_out)``;
- an attention layer (layers 5, 15, 25, 35): ``q = h W_q`` (32 × 64),
  ``k = h W_k``, ``v = h W_v`` (8 × 64), no position embedding;
  ``softmax(q kᵀ · 0.015625)``, causal, GQA in groups of 4;
  ``x += 0.22 · (attn W_o)``;
- every layer: ``x += 0.22 · SwiGLU(rmsnorm(x))`` of width 8,192;
- ``x_0 = 12 · E[tokens]``; ``logits = (rmsnorm(x_L) Eᵀ) / 8``, E tied.

Departures, each also in the configuration's file under ``assumed``: seeded
weights (the SSM's parameters as Mamba-2 initialises them), norm weights 1.

``quant="int8"`` is the control, as in ``bench_reference``: every weight
matrix rounded to int8 per output column and every activation entering a
matrix product rounded to int8 per row; the convolution, the recurrence and
the attention products stay float32. ``quant="state_bf16"`` is a second
control, read by ``limits_control.py``: everything float32 but the
recurrent state, rounded to bfloat16 after every token's update (the state
a cache of bfloat16 would hold), so that ``correct`` is seen to catch the
state stored below the configuration's stated float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

import bench_reference as R0
import bench_weights
import bench_weights_hybrid as W
from bench_reference import F32, HI, _mm, _rmsnorm, _swiglu
from bench_reference_mla_moe import number  # noqa: F401 — the cell's names


def _mamba(x, w, cfg, quant):
    """One request's Mamba-2 mix, sequentially: h (R, T, D) → (R, T, Di)."""
    m = W.dims(cfg)
    r, t = x.shape[:2]
    di, n, k, c = m["di"], m["n"], m["k"], m["conv"]
    zxbcdt = _mm(x, w["in_proj"], quant)
    z, xbc, dt_raw = (zxbcdt[..., :di], zxbcdt[..., di:di + c],
                      zxbcdt[..., di + c:])
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, i:i + t] * w["conv_w"][i] for i in range(k))
                    + w["conv_b"])
    xs = u[..., :di].reshape(r, t, m["h"], m["p"])
    b, cc = u[..., di:di + n], u[..., di + n:]
    dt = jax.nn.softplus(dt_raw + w["dt_bias"])                # (R, T, H)
    a = -jnp.exp(w["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp         # (R,H,P) (R,N) (R,N) (R,H)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        if quant == "state_bf16":
            s = lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("rhpn,rn->rhp", s, c_t, precision=HI)

    _, y = lax.scan(step, jnp.zeros((r, m["h"], m["p"], n), F32),
                    tuple(jnp.moveaxis(v, 1, 0) for v in (xs, b, cc, dt)))
    y = jnp.moveaxis(y, 0, 1) + xs            # D = 1
    return _rmsnorm(y.reshape(r, t, di) * jax.nn.silu(z),
                    cfg["rms_norm_eps"])


def _attention(x, w, cfg, quant):
    """One request: causal GQA, no position embedding, the config's
    softmax scale. x (T, D) → (T, N·Hd)."""
    m = W.dims(cfg)
    t = x.shape[0]
    q = _mm(x, w["wq"], quant).reshape(t, m["nkv"], m["nh"] // m["nkv"],
                                       m["hd"])
    k = _mm(x, w["wk"], quant).reshape(t, m["nkv"], m["hd"])
    v = _mm(x, w["wv"], quant).reshape(t, m["nkv"], m["hd"])
    s = jnp.einsum("tkgh,skh->kgts", q, k, precision=HI) \
        * cfg["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("kgts,skh->tkgh", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return o.reshape(t, -1)


@partial(jax.jit, static_argnames=("cfg_key", "quant", "kind"))
def _layer(root, layer, x, cfg_key, quant, kind):
    """One decoder layer over x (R, T, D)."""
    cfg = dict(cfg_key)
    eps, rm = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mlp = [W.make_slice(root, n, layer, cfg, F32) for n in W.MLP]
    h = _rmsnorm(x, eps)
    if kind == "mamba":
        w = {n: W.make_slice(root, n, layer, cfg, F32) for n in W.MAMBA}
        x = x + rm * _mm(_mamba(h, w, cfg, quant), w["wo_ssm"], quant)
    else:
        w = {n: W.make_slice(root, n, layer, cfg, F32) for n in W.ATTN}
        x = x + rm * _mm(lax.map(lambda r: _attention(r, w, cfg, quant), h),
                         w["wo"], quant)
    return x + rm * _swiglu(_rmsnorm(x, eps), *mlp, quant)


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(root, x, tokens, alt, cfg_key, quant):
    """``bench_reference._head``'s readings under the tied head, its logits
    divided by ``logits_scaling``. Row by row."""
    cfg = dict(cfg_key)
    head = W.make_slice(root, "embed", 0, cfg, F32).T

    def take(m, i):
        return jnp.take_along_axis(m, i[:, None], -1)[:, 0]

    def one(args):
        r, nxt, a = args
        logits = _mm(_rmsnorm(r, cfg["rms_norm_eps"]), head, quant) \
            / cfg["logits_scaling"]
        lp = jax.nn.log_softmax(logits, axis=-1)
        best, top = logits.max(-1), jnp.argmax(logits, -1)
        return {"gap_next": best - take(logits, nxt), "lp_next": take(lp, nxt),
                "gap_alt": best - take(logits, a), "lp_alt": take(lp, a),
                "top": top, "lp_top": take(lp, top)}

    return lax.map(one, (x, jnp.roll(tokens, -1, axis=1), alt))


KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "vocab_size",
        "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "mamba_d_conv", "mamba_expand",
        "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling")


def model_key(cfg: dict) -> tuple:
    """The numbers of the configuration that the equations use, hashable
    (``layer_types`` as a tuple). What a run must not guess is refused
    here."""
    for k, want in (("num_local_experts", 0), ("position_embedding_type",
                                                "nope"),
                    ("tie_word_embeddings", True), ("mamba_n_groups", 1),
                    ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                    ("attention_bias", False), ("hidden_act", "silu"),
                    ("normalization_function", "rmsnorm")):
        if cfg.get(k, want) != want:
            raise ValueError(f"the reference implements {k}={want!r}, the "
                             f"configuration says {cfg[k]!r}")
    if cfg.get("shared_intermediate_size",
               cfg["intermediate_size"]) != cfg["intermediate_size"]:
        raise ValueError("the reference's MLP is intermediate_size wide; "
                         "shared_intermediate_size differs")
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("layer_types", tuple(cfg["layer_types"])),)


def forward(seed: int, cfg: dict, tokens, prompt_lens=None, alt=None,
            quant=None):
    """tokens (R, T) int32, right-padded → what :func:`_head` reads at every
    position, each (R, T), and (R, T) an infinite margin (every position of
    a dense model is decided). ``prompt_lens`` is not used: nothing here
    depends on where a prompt ends."""
    del prompt_lens
    key = model_key(cfg)
    root = bench_weights.root_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    alt = jnp.zeros_like(tokens) if alt is None else jnp.asarray(alt,
                                                                 jnp.int32)
    x = R0._embed(root, tokens, R0.model_key(cfg)) \
        * cfg["embedding_multiplier"]
    for layer, kind in enumerate(cfg["layer_types"]):
        x = _layer(root, jnp.int32(layer), x, key, quant, kind)
    return (_head(root, x, tokens, alt, key, quant),
            jnp.full(tokens.shape, jnp.inf, F32))


CONTROLS = ("int8", "state_bf16")


def compare(seed: int, cfg: dict, sample, served_logprobs, t_pad: int,
            names, control=False, keep_positions=False) -> dict:
    """``bench_reference.compare`` over this module's :func:`forward`: the
    numbers ``names`` that decide ``correct``, the control's with
    ``control`` (True: int8; or one of ``CONTROLS`` by name), the
    per-position readings with ``keep_positions``."""
    import numpy as np
    toks, p_lens, mask = R0.pack(sample, t_pad)
    lp_served = np.zeros(toks.shape, np.float32)
    for r, lps in enumerate(served_logprobs):
        lp_served[r, p_lens[r] - 1:p_lens[r] - 1 + len(lps)] = lps
    ctl = None
    if control:
        quant = "int8" if control is True else control
        if quant not in CONTROLS:
            raise ValueError(f"no control {quant!r}: one of {CONTROLS}")
        ctl = {k: np.asarray(v) for k, v in
               forward(seed, cfg, toks, quant=quant)[0].items()}
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

"""Operations that a decoder of window and full attention layers with
fine-grained experts needs, from shapes alone (``trinity-mini-l5``), counted
in the published form: ``W_q``, ``W_k``, ``W_v``, the output gate's
projection and ``W_o`` once a token and layer; the dense layer's SwiGLU, or
the router over all experts, the K routed experts a token chose and the
shared expert; the untied head. Attention is ``4 · N · Hd`` operations a
(query, key) pair and layer (QK^T and PV), over the pairs the masks leave: a
full layer's causal triangle, a WINDOW layer's band alone (query i sees
``min(i + 1, W)`` keys). A prompt's or a decode step's attention over keys
a band excludes is no model work, nor are padding, garbage steps, the norms
and the gate's sigmoid. A multiply-add is two operations.
"""

from __future__ import annotations

import bench_weights_afmoe as W
from bench_flops import tokens_in  # noqa: F401


def layer_matmul_params(cfg: dict) -> dict:
    """Weights every token multiplies in one layer: {"dense", "moe"} (the
    expert layer's K routed experts included)."""
    m = W.dims(cfg)
    d, q, kv = m["d"], m["nh"] * m["hd"], m["nkv"] * m["hd"]
    attn = 3 * d * q + 2 * d * kv
    return {"dense": attn + 3 * d * m["f"],
            "moe": attn + d * m["E"] + 3 * d * m["fs"]
            + m["K"] * 3 * d * m["fm"]}


def stack_matmul_params(cfg: dict) -> int:
    m, p = W.dims(cfg), layer_matmul_params(cfg)
    return m["Ld"] * p["dense"] + (m["L"] - m["Ld"]) * p["moe"]


def pair_flops(cfg: dict) -> float:
    """One (query, key) pair through one layer's attention."""
    m = W.dims(cfg)
    return 4.0 * m["nh"] * m["hd"]


def band_pairs(first: int, n: int, window) -> float:
    """The keys ``n`` consecutive queries see, the first of which sees
    ``first`` keys (itself included) and each one more, at most ``window``
    each (None: no bound)."""
    if n <= 0:
        return 0.0
    last = first + n - 1
    if window is None or last <= window:
        return n * (first + last) / 2.0
    under = max(0, window - first + 1)        # queries that see < window
    return under * (first + window) / 2.0 + (n - under) * window


def context_pairs(cfg: dict, first: int, n: int) -> dict:
    """(query, key) pairs of ``n`` consecutive queries from a context of
    ``first`` keys, every layer of each kind: {"window", "full"}."""
    m = W.dims(cfg)
    return {"window": m["Lw"] * band_pairs(first, n, m["W"]),
            "full": m["Lf"] * band_pairs(first, n, None)}


def prefill_attention_flops(cfg: dict, prompt_len: int) -> float:
    """A prompt's attention over itself, every layer: the band's pairs on a
    window layer, the triangle's on a full one. What the prefill
    attention's roofline counts."""
    return sum(context_pairs(cfg, 1, prompt_len).values()) * pair_flops(cfg)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Causal pass over a prompt; the head only for its last position."""
    m = W.dims(cfg)
    return (prompt_len * 2.0 * stack_matmul_params(cfg)
            + prefill_attention_flops(cfg, prompt_len)
            + 2.0 * m["d"] * m["V"])


def decode_flops(cfg: dict, prompt_len: int, first: int, last: int) -> float:
    """Decode steps producing the request's tokens number first..last
    (``bench_flops.decode_flops``: token j attends from a context of
    prompt_len + j positions)."""
    n = max(0, last - first + 1)
    if n == 0:
        return 0.0
    m = W.dims(cfg)
    pairs = sum(context_pairs(cfg, prompt_len + first, n).values())
    return (n * (2.0 * stack_matmul_params(cfg) + 2.0 * m["d"] * m["V"])
            + pairs * pair_flops(cfg))

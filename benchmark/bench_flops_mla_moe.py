"""Operations and bytes that a latent-attention decoder with fine-grained
experts needs, from shapes alone (``kimi-vl-a3b-l9``), counted in the
PUBLISHED form: ``W_q``, ``W_kva`` and ``W_kvb`` once a token, ``W_o``, the
router, the K routed experts and the shared ones (or the dense layer's
SwiGLU), attention over expanded heads — ``2 * N * (Hn + Hr + Hv)`` a context
position — and the head. The extra arithmetic of the absorbed form that
decode runs (the up-projection folded into the query and the output, products
against rows of 576 where the expanded heads have 192 and 128) does not
count, nor do padding, garbage steps or anything else ``bench_flops.py``
leaves out. A multiply-add is two operations.
"""

from __future__ import annotations

import bench_weights_mla_moe as W
from bench_flops import tokens_in  # noqa: F401


def layer_matmul_params(cfg: dict) -> dict:
    """Weights one token multiplies in one layer: {"dense", "moe"}."""
    m = W.dims(cfg)
    d = m["d"]
    attn = (d * m["nh"] * (m["hn"] + m["hr"]) + d * m["c"]
            + m["r"] * m["nh"] * (m["hn"] + m["hv"]) + m["nh"] * m["hv"] * d)
    return {"dense": attn + 3 * d * m["f"],
            "moe": attn + d * m["E"] + 3 * d * (m["K"] * m["fm"] + m["fs"])}


def stack_matmul_params(cfg: dict) -> int:
    m, p = W.dims(cfg), layer_matmul_params(cfg)
    return m["Ld"] * p["dense"] + (m["L"] - m["Ld"]) * p["moe"]


def attention_flops_per_position(cfg: dict) -> float:
    """QK^T over Hn + Hr and PV over Hv, every head and layer, for one query
    against one context position."""
    m = W.dims(cfg)
    return 2.0 * m["L"] * m["nh"] * (m["hn"] + m["hr"] + m["hv"])


def token_flops(cfg: dict, context: int, head: bool = True) -> float:
    m = W.dims(cfg)
    flops = 2.0 * stack_matmul_params(cfg) \
        + attention_flops_per_position(cfg) * context
    if head:
        flops += 2.0 * m["d"] * m["V"]
    return flops


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Causal pass over a prompt; the head only for its last position."""
    m, p = W.dims(cfg), prompt_len
    return (p * 2.0 * stack_matmul_params(cfg)
            + attention_flops_per_position(cfg) * p * (p + 1) / 2
            + 2.0 * m["d"] * m["V"])


def decode_flops(cfg: dict, prompt_len: int, first: int, last: int) -> float:
    """Decode steps producing the request's tokens number first..last
    (``bench_flops.decode_flops``: token j attends to prompt_len + j
    positions)."""
    n = max(0, last - first + 1)
    if n == 0:
        return 0.0
    ctx = n * prompt_len + (first + last) * n / 2.0
    return n * token_flops(cfg, 0) + attention_flops_per_position(cfg) * ctx

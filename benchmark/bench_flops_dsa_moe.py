"""Operations that a latent-attention decoder with a query rank, a
sparse-attention indexer and a share of its routed experts needs, from
shapes alone (``glm-5-ep16-l6``), counted in the PUBLISHED form: both query
projections, ``W_kva`` and ``W_kvb`` once a token, the indexer's three
projections, ``W_o``, the router over its whole width, the shared expert (or
the dense layer's SwiGLU) and the sliced head; the indexer's score of every
causal key — ``2 * Hi * Di`` a key and layer — and attention over the
SELECTED keys alone, ``2 * N * (Hn + Hr + Hv)`` a key and layer for
``min(context, index_topk)`` keys a query.

The routed experts are counted by the pair: a (token, choice) pair that met
an expert HELD here is that expert's three products, and a pair routed to an
absent expert is no work of this chip. For decode steps the reader takes the
pairs from the program's own routing tally (``moe_routed_pairs``); a prompt's
rows are not tallied and count ``held / router_width`` of their K choices,
the share a router with no preference sends here.

The extra arithmetic of the absorbed form that decode runs, scores of
reserved rows past a slot's frontier, padding, garbage steps and a prompt's
masked upper triangle do not count. A multiply-add is two operations.
"""

from __future__ import annotations

import bench_weights_dsa_moe as W
from bench_flops import tokens_in  # noqa: F401


def layer_matmul_params(cfg: dict) -> dict:
    """Weights every token multiplies in one layer, the routed experts
    apart: {"dense", "moe"}."""
    m = W.dims(cfg)
    d, qr = m["d"], m["qr"]
    attn = (d * qr + qr * m["nh"] * (m["hn"] + m["hr"]) + d * m["c"]
            + m["r"] * m["nh"] * (m["hn"] + m["hv"]) + m["nh"] * m["hv"] * d
            + qr * m["hi"] * m["di"] + d * m["di"] + d * m["hi"])
    return {"dense": attn + 3 * d * m["f"],
            "moe": attn + d * m["Er"] + 3 * d * m["fs"]}


def stack_matmul_params(cfg: dict) -> int:
    m, p = W.dims(cfg), layer_matmul_params(cfg)
    return m["Ld"] * p["dense"] + (m["L"] - m["Ld"]) * p["moe"]


def routed_pair_flops(cfg: dict) -> float:
    """One (token, choice) pair through a held expert."""
    m = W.dims(cfg)
    return 2.0 * 3 * m["d"] * m["fm"]


def expected_pairs_per_token(cfg: dict) -> float:
    """The pairs of one token that meet a held expert, all expert layers
    together, under a router with no preference."""
    m = W.dims(cfg)
    return (m["L"] - m["Ld"]) * m["K"] * m["E"] / m["Er"]


def key_flops(cfg: dict) -> tuple:
    """(a scored key, an attended key), every layer, for one query."""
    m = W.dims(cfg)
    return (2.0 * m["L"] * m["hi"] * m["di"],
            2.0 * m["L"] * m["nh"] * (m["hn"] + m["hr"] + m["hv"]))


def context_flops(cfg: dict, first: int, n: int) -> float:
    """Scores and attention of ``n`` consecutive queries, the first of which
    sees ``first`` keys (itself included) and each one more."""
    if n <= 0:
        return 0.0
    scored, attended = key_flops(cfg)
    topk, last = W.dims(cfg)["topk"], first + n - 1
    keys = n * (first + last) / 2.0
    # of them, attended: min(context, topk) a query
    under = max(0, min(last, topk) - first + 1)      # queries seeing <= topk
    kept = under * (first + min(last, topk)) / 2.0 + (n - under) * topk
    return scored * keys + attended * kept


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Causal pass over a prompt; the head only for its last position."""
    m, p = W.dims(cfg), prompt_len
    return (p * (2.0 * stack_matmul_params(cfg)
                 + expected_pairs_per_token(cfg) * routed_pair_flops(cfg))
            + context_flops(cfg, 1, p) + 2.0 * m["d"] * m["V"])


def decode_flops(cfg: dict, prompt_len: int, first: int, last: int) -> float:
    """Decode steps producing the request's tokens number first..last
    (``bench_flops.decode_flops``: token j attends from a context of
    prompt_len + j positions), WITHOUT their routed pairs, which the reader
    adds from the tally."""
    n = max(0, last - first + 1)
    if n == 0:
        return 0.0
    m = W.dims(cfg)
    return (n * (2.0 * stack_matmul_params(cfg) + 2.0 * m["d"] * m["V"])
            + context_flops(cfg, prompt_len + first, n))

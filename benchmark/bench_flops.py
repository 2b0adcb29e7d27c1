"""Operations and bytes that the algorithm needs, from shapes alone.

Kept with the benchmark so that no later PR can move them. "Model FLOPs"
are what the published equations require for the tokens in question:
matrix products of the weights a token passes through (for an expert layer
the K experts it is routed to, and the router) and attention against the
context it sees. Recomputation, padding to a bucket, the steps a retired
slot keeps computing inside a block, and experts computed but not chosen do
not count. A multiply-add is two operations.
"""

from __future__ import annotations

import json
import os

import bench_weights as W

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """One table, keyed by ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)} (benchmark/peaks.json)")
    return table[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies in one layer."""
    m = W.dims(cfg)
    d, f = m["d"], m["f"]
    attn = 2 * d * m["nh"] * m["hd"] + 2 * d * m["nkv"] * m["hd"]
    if m["E"]:
        return attn + m["K"] * 3 * d * f + d * m["E"]
    return attn + 3 * d * f


def token_flops(cfg: dict, context: int, head: bool = True) -> float:
    """Forward pass of one token that attends to ``context`` positions
    (itself included); ``head``: with the output head's product."""
    m = W.dims(cfg)
    flops = 2.0 * m["L"] * layer_matmul_params(cfg)
    flops += 4.0 * m["L"] * m["nh"] * m["hd"] * context      # QK^T and PV
    if head:
        flops += 2.0 * m["d"] * m["V"]
    return flops


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Causal pass over a prompt; the head only for its last position."""
    m = W.dims(cfg)
    p = prompt_len
    return (p * 2.0 * m["L"] * layer_matmul_params(cfg)
            + 4.0 * m["L"] * m["nh"] * m["hd"] * p * (p + 1) / 2
            + 2.0 * m["d"] * m["V"])


def decode_flops(cfg: dict, prompt_len: int, first: int, last: int) -> float:
    """Decode steps producing the request's tokens number first..last
    (token 0 comes from the prefill). Token j is computed from the input at
    position prompt_len + j - 1 and attends to prompt_len + j positions."""
    n = max(0, last - first + 1)
    if n == 0:
        return 0.0
    ctx = n * prompt_len + (first + last) * n / 2.0
    m = W.dims(cfg)
    return (n * token_flops(cfg, 0) + 4.0 * m["L"] * m["nh"] * m["hd"] * ctx)


def tokens_in(rec: dict, a: float, b: float) -> tuple:
    """(prefilled in [a, b]?, first and last index of the decoded tokens whose
    time falls in [a, b]) for one request of the rank's log: its first token
    at ``t_first``, its last at ``t_out``, the others evenly spaced between."""
    n, t0, t1 = rec["n"], rec["t_first"], rec["t_out"]
    pre = a <= t0 <= b
    if n <= 1 or t1 <= t0:
        return pre, 1, 0
    dt = (t1 - t0) / (n - 1)
    lo = max(1, int(-(-(a - t0) // dt)))
    hi = min(n - 1, int((b - t0) // dt))
    return pre, lo, hi


def decode_attention_cost(cfg: dict, context_rows: float) -> dict:
    """The decode attention kernel over ``context_rows`` cache rows in all
    (rows summed over slots and steps), every layer: bytes are the K and V
    rows read once in the cache's type (bfloat16); q, the output and the
    row written are left out as small. Operations are QK^T and PV."""
    m = W.dims(cfg)
    return {"bytes": context_rows * m["L"] * 2 * m["nkv"] * m["hd"] * 2,
            "flops": context_rows * m["L"] * 4.0 * m["nh"] * m["hd"]}


def roofline_seconds(cost: dict, peak: dict) -> tuple:
    """Least time the chip could take, and which of the two bounds it."""
    t_ops = cost["flops"] / peak["bf16_flops_per_s"]
    t_mem = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")

"""Operations and bytes that a hybrid Mamba-2 / attention decoder needs, from
shapes alone (``granite-4.0-h-micro``), counted in the published form.

Operations of a token: the weights it multiplies in every layer (a Mamba-2
layer's ``W_in`` and ``W_out``, an attention layer's four projections, every
layer's SwiGLU), the head (the tied embedding), attention over the context
it sees in the attention layers, and the Mamba-2 layers' own terms — the
convolution's taps and the recurrence: the decay, the input's outer product
and the state's product with C, five operations an element of the state.
The chunked form a prompt runs does more arithmetic than the recurrence
needs; that, padding and the steps a retired slot keeps computing do not
count. A multiply-add is two operations.

Bytes of a decode step, a least count: the weights read once, every live
slot's state and window read and written once in the Mamba-2 layers, and
the live K/V rows its attention layers read (``bench_flops`` leaves the same
out: q, outputs, the rows written, the activations).
"""

from __future__ import annotations

import bench_weights_hybrid as W
from bench_flops import tokens_in  # noqa: F401

SSM_OPS = 5           # decay, outer product and add, product with C


def layer_matmul_params(cfg: dict) -> dict:
    """Weights one token multiplies in one layer: {"mamba", "attention"}."""
    m = W.dims(cfg)
    d, mlp = m["d"], 3 * m["d"] * m["f"]
    return {"mamba": d * m["in"] + m["di"] * d + mlp,
            "attention": (2 * d * m["nh"] * m["hd"]
                          + 2 * d * m["nkv"] * m["hd"] + mlp)}


def stack_matmul_params(cfg: dict) -> int:
    m, p = W.dims(cfg), layer_matmul_params(cfg)
    return m["Lm"] * p["mamba"] + m["La"] * p["attention"]


def ssm_flops_per_token(cfg: dict) -> float:
    """Every Mamba-2 layer's convolution and recurrence for one token."""
    m = W.dims(cfg)
    return m["Lm"] * (2.0 * m["k"] * m["conv"]
                      + SSM_OPS * m["h"] * m["p"] * m["n"])


def attention_flops_per_position(cfg: dict) -> float:
    """QK^T and PV over every attention layer's heads, for one query
    against one context position."""
    m = W.dims(cfg)
    return 4.0 * m["La"] * m["nh"] * m["hd"]


def token_flops(cfg: dict, context: int, head: bool = True) -> float:
    m = W.dims(cfg)
    flops = (2.0 * stack_matmul_params(cfg) + ssm_flops_per_token(cfg)
             + attention_flops_per_position(cfg) * context)
    if head:
        flops += 2.0 * m["d"] * m["V"]
    return flops


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Causal pass over a prompt; the head only for its last position."""
    m, p = W.dims(cfg), prompt_len
    return (p * (2.0 * stack_matmul_params(cfg) + ssm_flops_per_token(cfg))
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


def weight_bytes(cfg: dict) -> int:
    """Every parameter once, in bfloat16 (norms and the SSM's vectors, held
    in float32, are counted at two bytes: a least count)."""
    return 2 * W.param_count(cfg)


def state_bytes_per_slot_step(cfg: dict) -> int:
    """One live slot's state (float32) and window (bfloat16) read and
    written once, in every Mamba-2 layer."""
    m = W.dims(cfg)
    return m["Lm"] * 2 * (m["h"] * m["p"] * m["n"] * 4
                          + (m["k"] - 1) * m["conv"] * 2)


def kv_row_bytes(cfg: dict) -> int:
    """One cached position's K and V in every attention layer, bfloat16."""
    m = W.dims(cfg)
    return m["La"] * 2 * m["nkv"] * m["hd"] * 2


def decode_bytes(cfg: dict, steps: float, slot_steps: float,
                 rows: float) -> float:
    """The least bytes of ``steps`` decode steps in which live slots
    advanced their state ``slot_steps`` times and read ``rows`` cached
    positions in all."""
    return (steps * weight_bytes(cfg)
            + slot_steps * state_bytes_per_slot_step(cfg)
            + rows * kv_row_bytes(cfg))

"""Weight-only int8 quantization for serving.

Decode is weight-bandwidth-bound: every step streams the full parameter set
from HBM to produce one token per slot. Storing matmul weights as int8 with
per-output-channel fp32 scales halves the bytes vs bf16 — the dequantize
(``q.astype(bf16) * scale``) happens INSIDE the jitted step, per layer
inside the scan body, so HBM traffic is the int8 buffer and the convert
fuses into the dot's operand pipeline. Norms, routers, and the embedding
stay full precision (tiny, or gather-indexed).

Usage::

    from kubetorch_tpu.serve import GenerationEngine
    from kubetorch_tpu.models.quant import quantize_params

    engine = GenerationEngine(quantize_params(params), cfg, ...)

The engine (and the scanned ``generate`` path) dequantize transparently:
a quantized leaf is the dict ``{"__kt_q8__": int8, "scale": f32}`` and
``dequant`` is an identity on ordinary arrays. The semantics contract:
running on ``quantize_params(p)`` is BIT-IDENTICAL to running on
``dequantize_params(quantize_params(p))`` — quantization error is a
property of the weights, never of where the dequant runs (asserted in
tests/test_quant.py).

Reference analog: none — the reference serves user handlers and leaves
model-level optimization to user code; this is part of the beyond-parity
serving stack (docs/serving.md).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

QKEY = "__kt_q8__"
Q4KEY = "__kt_q4__"   # nibble-packed int4 (two values per int8 byte)

# leaves kept full-precision: norms are fp32 by design, the router's logits
# are precision-sensitive, and the embedding is gather-indexed (quantizing
# it saves HBM capacity but not decode bandwidth; keep exactness)
_SKIP = ("attn_norm", "ffn_norm", "final_norm", "router", "embed")


def _quantize_leaf(w: jax.Array) -> Dict[str, jax.Array]:
    """Symmetric per-output-channel int8: scale over the contraction axis
    (second-to-last), so each output column keeps its own dynamic range."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {QKEY: q, "scale": scale}


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and (QKEY in leaf or Q4KEY in leaf)


def dequant(leaf: Any, dtype=jnp.bfloat16) -> Any:
    """In-graph dequantize (int8 or nibble-packed int4); identity for
    ordinary arrays — every weight use-site on the serving path routes
    through this."""
    if isinstance(leaf, dict) and Q4KEY in leaf:
        return _dequant_int4(leaf, dtype)
    if is_quantized(leaf):
        return (leaf[QKEY].astype(jnp.float32) * leaf["scale"]).astype(dtype)
    return leaf


def head_weight(params: Dict[str, Any], dtype=jnp.bfloat16):
    """The lm_head in compute dtype, whether stored quantized or not — the
    ONE definition of head handling shared by the scanned generate path,
    the engine's decode/prefill jits, and speculative decoding (a change
    here cannot silently break their bit-identical contract). A tree with
    no ``lm_head`` ties the head to its embedding (``models.hybrid``): the
    embedding, transposed."""
    if "lm_head" not in params:
        return params["embed"].astype(dtype).T
    leaf = params["lm_head"]
    if is_quantized(leaf):
        return dequant(leaf, dtype)
    return leaf.astype(dtype)


def dequant_layer(lw: Dict[str, Any], dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Dequantize one layer's weight dict. Called at the top of the layer
    body — inside the scan, so only the current layer's weights materialize
    in the compute dtype.

    The ``experts`` subtree is left AS-IS: the MoE paths own its dequant —
    the dispatch path converts the full bank right at its einsums, while
    the decode gather path must gather int8 FIRST and dequantize only the
    K selected experts' matrices, or the whole bank would materialize in
    bf16 every step and invert the bandwidth win (``moe_ffn_decode``)."""
    out = {}
    for k, v in lw.items():
        if k == "experts":
            out[k] = v
        elif isinstance(v, dict) and Q4KEY in v:
            # int4 stays PACKED: materializing here would re-create the
            # full-precision stream the format exists to avoid — matmul
            # call sites route dicts through ``wdot`` (fused kernel)
            out[k] = v
        elif isinstance(v, dict) and not is_quantized(v):
            out[k] = dequant_layer(v, dtype)
        else:
            out[k] = dequant(v, dtype)
    return out


def _walk(tree: Any, fn, path=()) -> Any:
    if isinstance(tree, dict) and not is_quantized(tree):
        return {k: _walk(v, fn, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize every matmul weight (wq/wk/wv/wo, FFN, experts, lm_head) to
    int8 + per-channel scales; precision-sensitive leaves stay as-is."""

    def visit(path, leaf):
        name = path[-1] if path else ""
        if name in _SKIP or getattr(leaf, "ndim", 0) < 2:
            return leaf
        return _quantize_leaf(leaf)

    return _walk(params, visit)


def dequantize_params(params: Dict[str, Any],
                      dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Materialize the full-precision view (testing / migration)."""
    return _walk(params, lambda _, leaf: dequant(leaf, dtype))


def quantized_bytes(params: Dict[str, Any]) -> Dict[str, int]:
    """{'quantized': n, 'full': m} byte footprint — the HBM story."""
    sizes = {"quantized": 0, "full": 0}

    def visit(path, leaf):
        if is_quantized(leaf):
            q = leaf.get(QKEY, leaf.get(Q4KEY))
            sizes["quantized"] += q.size + 4 * leaf["scale"].size
        else:
            sizes["full"] += leaf.size * leaf.dtype.itemsize
        return leaf

    _walk(params, visit)
    return sizes


def llama_init_quantized(rng: jax.Array, cfg, bits: int = 8) -> Dict[str, Any]:
    """Initialize a Llama-family param pytree DIRECTLY in the quantized
    serving layout (``bits`` 8 or 4), one layer-slice at a time — peak HBM
    is a single (d, o) fp32 matrix plus the quantized stacks, never the
    full bf16 parameter set. This is what makes 7B-class (int8, ~7 GB) and
    13B-class (int4, ~6 GB) models servable on one 16 GB v5e chip: the
    bf16 weights alone would not fit, let alone a transient quantize pass.

    Structure-identical to ``quantize_params(llama_init(rng, cfg))`` /
    ``quantize_params_int4(...)`` (same leaves, same quantized-dict
    format); values are self-consistent per (rng, cfg, bits) but drawn
    per-slice rather than per-stack, so they differ numerically from the
    two-step path. Random-weight serving benches and HBM-budget rehearsals
    are the use case — real checkpoints arrive via ``convert_hf.load_hf``
    + ``quantize_params``/``quantize_params_int4``."""
    from functools import partial

    from jax import lax

    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    d, L = cfg.dim, cfg.n_layers
    hd, nh, nkv, f = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.ffn_dim
    quantizer = _quantize_leaf if bits == 8 else _quantize_leaf_int4

    @partial(jax.jit, static_argnames=("shape", "fan_in"))
    def init_slice_q(key, shape, fan_in):
        w = jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)
        return quantizer(w)

    @partial(jax.jit, donate_argnums=(0,))
    def write(buf, i, v):
        # donated: the caller's only reference is rebound to the result,
        # so each per-layer update is in-place — no full-stack copy, which
        # is the whole point of the slice-wise init
        return lax.dynamic_update_index_in_dim(buf, v, i, 0)

    base = jax.random.fold_in(rng, 0)
    leaf_keys = {}
    for j, name in enumerate(("embed", "wq", "wk", "wv", "wo", "w_gate",
                              "w_up", "w_down", "lm_head")):
        leaf_keys[name] = jax.random.fold_in(base, j)

    def stacked(name, in_dim, out_dim):
        acc = None
        for layer in range(L):
            leaf = init_slice_q(jax.random.fold_in(leaf_keys[name], layer),
                                (in_dim, out_dim), in_dim)
            if acc is None:
                acc = {k: jnp.zeros((L,) + v.shape, v.dtype)
                       for k, v in leaf.items()}
            acc = {k: write(acc[k], layer, leaf[k]) for k in acc}
        return acc

    embed = (jax.random.normal(leaf_keys["embed"], (cfg.vocab_size, d),
                               jnp.float32) / jnp.sqrt(d)).astype(cfg.dtype)
    head = init_slice_q(leaf_keys["lm_head"], (d, cfg.vocab_size), d)
    return {
        "embed": embed,
        "layers": {
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "wq": stacked("wq", d, nh * hd),
            "wk": stacked("wk", d, nkv * hd),
            "wv": stacked("wv", d, nkv * hd),
            "wo": stacked("wo", nh * hd, d),
            "ffn_norm": jnp.ones((L, d), jnp.float32),
            "w_gate": stacked("w_gate", d, f),
            "w_up": stacked("w_up", d, f),
            "w_down": stacked("w_down", f, d),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": head,
    }


# ---------------------------------------------------------------------------
# int4 (nibble-packed): half of int8's bytes again on the decode stream
# ---------------------------------------------------------------------------


def _quantize_leaf_int4(w: jax.Array, group: int = 128) -> Dict[str, jax.Array]:
    """Symmetric group-wise int4: groups of ``group`` rows along the
    CONTRACTION axis share a scale (per-output-channel within the group —
    4 bits needs finer scale granularity than int8's whole-column scale),
    values in [-7, 7], packed two-per-byte along the contraction axis.
    Leaf format: ``{Q4KEY: int8 (..., in/2, out), "scale":
    (..., in/group, out) f32}``."""
    wf = w.astype(jnp.float32)
    *lead, din, dout = wf.shape
    if din % 2:
        raise ValueError(f"int4 packing needs an even contraction dim, "
                         f"got {din}")
    g = min(group, din)
    while din % g:
        g //= 2
    wg = wf.reshape(*lead, din // g, g, dout)
    amax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 7.0, 1.0)
    q = jnp.clip(jnp.round(wg / scale), -7, 7).astype(jnp.int8)
    q = q.reshape(*lead, din, dout)
    # HALF-SPLIT pack: byte row r holds weight row r in the low nibble and
    # row r + in/2 in the high nibble — unpack is two contiguous halves
    # (no interleave shuffle), which is what lets the Pallas kernel stream
    # packed tiles and issue one dot per nibble plane
    lo = q[..., : din // 2, :] & jnp.int8(0x0F)
    hi = jnp.left_shift(q[..., din // 2:, :], 4)
    return {Q4KEY: lo | hi, "scale": scale.squeeze(-2)}


def _dequant_int4(leaf: Dict[str, jax.Array], dtype=jnp.bfloat16) -> jax.Array:
    """Unpack + dequantize in-graph: two arithmetic shifts recover the
    signed nibbles (sign-extend via <<4 then >>4 on int8), the group scale
    multiplies in fp32, and XLA fuses the whole chain into the consuming
    dot's operand pipeline — HBM traffic is the packed buffer."""
    p = leaf[Q4KEY]
    scale = leaf["scale"]
    *lead, half, dout = p.shape
    din = half * 2
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)       # sign-extended
    hi = jnp.right_shift(p, 4)                          # arithmetic on int8
    # half-split: rows [0, in/2) from the low nibbles, the rest from high
    q = jnp.concatenate([lo, hi], axis=-2)
    ng = scale.shape[-2]
    wf = (q.astype(jnp.float32).reshape(*lead, ng, din // ng, dout)
          * scale[..., :, None, :])
    return wf.reshape(*lead, din, dout).astype(dtype)


def quantize_params_int4(params: Dict[str, Any],
                         group: int = 128) -> Dict[str, Any]:
    """int4-quantize every matmul weight except MoE expert banks (the
    decode gather path indexes int8 leaves directly — experts stay int8,
    a mixed layout ``dequant``/``dequant_layer`` serve transparently)."""

    def visit(path, leaf):
        name = path[-1] if path else ""
        if name in _SKIP or getattr(leaf, "ndim", 0) < 2:
            return leaf
        if "experts" in path:
            return _quantize_leaf(leaf)
        return _quantize_leaf_int4(leaf, group=group)

    return _walk(params, visit)


def wdot(x: jax.Array, w: Any, dtype=None) -> jax.Array:
    """``x @ W`` for a plain weight array OR a packed-int4 leaf.

    Plain arrays multiply directly (bit-identical to the historical
    ``x @ w`` — int8 leaves never reach here packed; ``dequant_layer``
    materializes them where the convert fuses for free). Packed int4
    routes through the fused Pallas kernel (``ops.quant_matmul``) when
    the tiling fits, else the XLA dequant fallback. ``x`` may carry any
    leading dims; the result is in ``dtype`` (default ``x.dtype``)."""
    out_dtype = dtype or x.dtype
    if isinstance(w, dict) and Q4KEY in w:
        from ..ops.quant_matmul import q4_matmul, q4_supported
        p, s = w[Q4KEY], w["scale"]
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if q4_supported(x2.shape, p.shape, s.shape):
            y = q4_matmul(x2, p, s)
        else:
            y = x2 @ _dequant_int4(w, jnp.float32)
        return y.reshape(*lead, p.shape[-1]).astype(out_dtype)
    return x @ w


def lm_head_dot(x: jax.Array, params: Dict[str, Any], dtype) -> jax.Array:
    """fp32 logits ``x @ lm_head`` — the ONE head-matmul definition for
    the scanned generate path, the engine's decode/prefill jits, and
    speculative decoding (an int4 head streams packed through the kernel
    instead of materializing ~2 GB of fp rows per step on a 13B)."""
    leaf = params.get("lm_head")
    if isinstance(leaf, dict) and Q4KEY in leaf:
        return wdot(x, leaf, dtype=jnp.float32)
    return (x @ head_weight(params, dtype)).astype(jnp.float32)

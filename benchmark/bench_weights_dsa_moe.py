"""Seeded weights for a latent-attention decoder with a query rank, a
sparse-attention indexer and a SHARE of its routed experts
(``glm-5-ep16-l6``), in the layout ``kubetorch_tpu.models.mla`` takes for
such a configuration: ``embed`` / ``dense_layers`` and ``layers`` stacked on
a leading L / ``final_norm`` / ``lm_head``; ``wq_a`` / ``q_norm`` / ``wq_b``
in place of ``wq``; the indexer's ``idx_wq`` / ``idx_wk`` / ``idx_k_norm`` /
``idx_k_bias`` / ``idx_w`` in every layer; ``banks`` of the held experts
only, a router over all of them.

As in ``bench_weights.py`` (whose embedding and head these are) and
``bench_weights_mla_moe.py`` (whose leaf ids the shared names keep): every
slice has a key of its own, ``fold_in(fold_in(fold_in(root(seed), leaf),
layer), expert)``, ``layer`` counted over the whole model and ``expert`` over
the router's WHOLE width (a held expert is drawn under its global number, so
every share of one deployment draws the same model), and the plain reference
(``bench_reference_dsa_moe.py``) makes the same values again a layer and an
expert at a time. N(0, 1/fan_in) rounded to bfloat16; norm weights 1; the
router in float32; ``e_score_correction_bias`` N(0, 0.01^2) and the bias of
the LayerNorm on the indexer's key N(0, 0.1^2), both rounded to bfloat16 and
held in float32 (a zero would leave either path untested).

No jax at import time.
"""

from __future__ import annotations

import math

import bench_weights as W
import bench_weights_mla_moe as W1

LEAVES = {**W1.LEAVES, "wq_a": 40, "wq_b": 41, "idx_wq": 42, "idx_wk": 43,
          "idx_w": 44, "idx_k_bias": 45}
BIAS_STD = {"router_bias": W1.BIAS_STD, "idx_k_bias": 0.1}


def dims(cfg: dict) -> dict:
    """Sizes of the configuration file (keys as in the published
    config.json; ``n_routed_experts`` is what this chip holds, ``E`` here,
    from the global number ``held_first`` on; ``router_width`` what the
    router scores, ``Er``)."""
    return {**W1.dims(cfg), "qr": cfg["q_lora_rank"],
            "hi": cfg["index_n_heads"], "di": cfg["index_head_dim"],
            "topk": cfg["index_topk"], "Er": cfg["router_width"],
            "first": cfg["held_first"]}


def leaf_shapes(cfg: dict) -> dict:
    """leaf name -> (shape of one slice, fan_in)."""
    m = dims(cfg)
    d, qr = m["d"], m["qr"]
    out = dict(W1.leaf_shapes(cfg))
    del out["wq"]
    out.update({"wq_a": ((d, qr), d),
                "wq_b": ((qr, m["nh"] * (m["hn"] + m["hr"])), qr),
                "idx_wq": ((qr, m["hi"] * m["di"]), qr),
                "idx_wk": ((d, m["di"]), d), "idx_w": ((d, m["hi"]), d),
                "idx_k_bias": ((m["di"],), None),
                "router": ((d, m["Er"]), d),
                "router_bias": ((m["Er"],), None)})
    return out


ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "idx_wq", "idx_wk", "idx_w")
DENSE, EXPERT, SHARED = W1.DENSE, W1.EXPERT, W1.SHARED


def param_count(cfg: dict) -> dict:
    """Parameters held on this chip, by part: {"attention" (a layer),
    "expert" (one routed or shared expert), "router", "dense_layer",
    "expert_layer", "embedding_and_head", "params"}."""
    m, s = dims(cfg), leaf_shapes(cfg)
    n = lambda names: sum(math.prod(s[k][0]) for k in names)  # noqa: E731
    # the projections, and: q / kv norms, two block norms, the key's
    # LayerNorm weight and bias
    attn = n(ATTN) + m["qr"] + m["r"] + 2 * m["d"] + 2 * m["di"]
    expert = n(EXPERT)
    router = n(("router", "router_bias"))
    moe = attn + router + n(SHARED) + m["E"] * expert
    dense = attn + n(DENSE)
    head = 2 * m["V"] * m["d"]
    return {"attention": attn, "expert": expert, "router": router,
            "dense_layer": dense, "expert_layer": moe,
            "embedding_and_head": head,
            "params": head + m["d"] + m["Ld"] * dense
            + (m["L"] - m["Ld"]) * moe}


def make_slice(root, name: str, layer, expert, cfg: dict, dtype=None):
    """One slice from its own key. ``layer`` (counted over the whole model)
    and ``expert`` (its global number) may be traced."""
    import jax
    import jax.numpy as jnp
    if name in ("embed", "lm_head"):
        return W.make_slice(root, name, layer, expert, cfg, dtype)
    shape, fan_in = leaf_shapes(cfg)[name]
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(root, LEAVES[name]), layer), expert)
    w = jax.random.normal(key, shape, jnp.float32)
    if name in BIAS_STD:
        # rounded like every other leaf, so that the program's tree and the
        # reference's slice agree to the bit; kept in float32
        return (w * BIAS_STD[name]).astype(jnp.bfloat16).astype(jnp.float32)
    w = (w * fan_in ** -0.5).astype(jnp.bfloat16)
    return w if dtype is None else w.astype(dtype)


def _stack(root, name, cfg, first_layer, n_layers, experts=None, dtype=None):
    """(L[, E], *shape) for layers first_layer .. first_layer + L, a slice at
    a time; ``experts``: (first, count) of the global numbers held."""
    import jax.numpy as jnp
    from jax import lax
    first, e = experts or (0, 1)
    flat = lax.map(lambda n: make_slice(root, name, first_layer + n // e,
                                        first + n % e, cfg, dtype),
                   jnp.arange(n_layers * e))
    if experts:
        return flat.reshape(n_layers, e, *flat.shape[1:])
    return flat


def init_params(root, cfg: dict):
    """The whole tree in the program's layout. Jit it: one call."""
    import jax.numpy as jnp
    m = dims(cfg)
    d, Ld, Lm = m["d"], m["Ld"], m["L"] - m["Ld"]

    def attn(first, n):
        out = {"attn_norm": jnp.ones((n, d), jnp.float32),
               "q_norm": jnp.ones((n, m["qr"]), jnp.float32),
               "kv_norm": jnp.ones((n, m["r"]), jnp.float32),
               "idx_k_norm": jnp.ones((n, m["di"]), jnp.float32),
               "idx_k_bias": _stack(root, "idx_k_bias", cfg, first, n),
               "ffn_norm": jnp.ones((n, d), jnp.float32)}
        for name in ATTN:
            out[name] = _stack(root, name, cfg, first, n)
        return out

    def swiglu(names, first, n, experts=None):
        return {k: _stack(root, name, cfg, first, n, experts)
                for k, name in zip(DENSE, names)}

    return {
        "embed": make_slice(root, "embed", 0, 0, cfg),
        "dense_layers": {**attn(0, Ld), **swiglu(DENSE, 0, Ld)},
        "layers": {
            **attn(Ld, Lm),
            "router": _stack(root, "router", cfg, Ld, Lm, dtype=jnp.float32),
            "router_bias": _stack(root, "router_bias", cfg, Ld, Lm),
            "banks": swiglu(EXPERT, Ld, Lm, (m["first"], m["E"])),
            "shared": swiglu(SHARED, Ld, Lm)},
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": make_slice(root, "lm_head", 0, 0, cfg)}

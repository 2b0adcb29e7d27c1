"""Seeded weights for a latent-attention decoder with fine-grained experts
(``kimi-vl-a3b-l9``), in the layout ``kubetorch_tpu.models.mla`` takes:
``embed`` / ``dense_layers`` and ``layers`` stacked on a leading L /
``final_norm`` / ``lm_head``.

As in ``bench_weights.py``, whose embedding and head these are: every slice
(one leaf of one layer, or of one expert of one layer) has a key of its own,
``fold_in(fold_in(fold_in(root(seed), leaf), layer), expert)`` with ``layer``
counted over the whole model (the dense layers first), so the plain reference
(``bench_reference_mla_moe.py``) makes the same values again a layer and an
expert at a time. N(0, 1/fan_in) rounded to bfloat16; norms 1; the router in
float32; ``e_score_correction_bias`` N(0, 0.01^2), rounded to bfloat16 and held in float32 (a zero bias
would leave the correction untested).

No jax at import time.
"""

from __future__ import annotations

import math

import bench_weights as W

# leaf name -> id (embed 0 and lm_head 1 are ``bench_weights``' own)
LEAVES = {"wq": 20, "wkv_a": 21, "wkv_b": 22, "wo": 23, "w_gate": 24,
          "w_up": 25, "w_down": 26, "router": 27, "router_bias": 28,
          "e_gate": 29, "e_up": 30, "e_down": 31, "s_gate": 32, "s_up": 33,
          "s_down": 34}
BIAS_STD = 0.01


def dims(cfg: dict) -> dict:
    """Sizes of the configuration file (keys as in the published
    config.json)."""
    nh = cfg["num_attention_heads"]
    hn, hr, hv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"d": cfg["hidden_size"], "nh": nh, "hn": hn, "hr": hr, "hv": hv,
            "r": cfg["kv_lora_rank"], "c": cfg["kv_lora_rank"] + hr,
            "f": cfg["intermediate_size"], "fm": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["n_routed_experts"], "K": cfg["num_experts_per_tok"],
            "L": cfg["num_hidden_layers"], "Ld": cfg["first_k_dense_replace"],
            "V": cfg["vocab_size"]}


def leaf_shapes(cfg: dict) -> dict:
    """leaf name -> (shape of one slice, fan_in)."""
    m = dims(cfg)
    d, r = m["d"], m["r"]
    return {"wq": ((d, m["nh"] * (m["hn"] + m["hr"])), d),
            "wkv_a": ((d, m["c"]), d),
            "wkv_b": ((r, m["nh"] * (m["hn"] + m["hv"])), r),
            "wo": ((m["nh"] * m["hv"], d), m["nh"] * m["hv"]),
            "w_gate": ((d, m["f"]), d), "w_up": ((d, m["f"]), d),
            "w_down": ((m["f"], d), m["f"]),
            "router": ((d, m["E"]), d), "router_bias": ((m["E"],), None),
            "e_gate": ((d, m["fm"]), d), "e_up": ((d, m["fm"]), d),
            "e_down": ((m["fm"], d), m["fm"]),
            "s_gate": ((d, m["fs"]), d), "s_up": ((d, m["fs"]), d),
            "s_down": ((m["fs"], d), m["fs"])}


ATTN = ("wq", "wkv_a", "wkv_b", "wo")
DENSE = ("w_gate", "w_up", "w_down")
EXPERT = ("e_gate", "e_up", "e_down")
SHARED = ("s_gate", "s_up", "s_down")


def param_count(cfg: dict) -> int:
    m, s = dims(cfg), leaf_shapes(cfg)
    n = lambda names: sum(math.prod(s[k][0]) for k in names)  # noqa: E731
    attn = n(ATTN) + m["r"] + 2 * m["d"]          # kv_a norm, two norms
    moe = (m["E"] * n(EXPERT) + n(SHARED) + n(("router", "router_bias")))
    return (2 * m["V"] * m["d"] + m["d"] + m["Ld"] * (attn + n(DENSE))
            + (m["L"] - m["Ld"]) * (attn + moe))


def make_slice(root, name: str, layer, expert, cfg: dict, dtype=None):
    """One slice from its own key. ``layer`` (counted over the whole model)
    and ``expert`` may be traced."""
    import jax
    import jax.numpy as jnp
    if name in ("embed", "lm_head"):
        return W.make_slice(root, name, layer, expert, cfg, dtype)
    shape, fan_in = leaf_shapes(cfg)[name]
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(root, LEAVES[name]), layer), expert)
    w = jax.random.normal(key, shape, jnp.float32)
    if name == "router_bias":
        # rounded like every other leaf, so that the program's tree and the
        # reference's slice agree to the bit; kept in float32 as the router
        return (w * BIAS_STD).astype(jnp.bfloat16).astype(jnp.float32)
    w = (w * fan_in ** -0.5).astype(jnp.bfloat16)
    return w if dtype is None else w.astype(dtype)


def _stack(root, name, cfg, first, n_layers, n_experts, dtype):
    """(L[, E], *shape) for layers first .. first + L, a slice at a time."""
    import jax.numpy as jnp
    from jax import lax
    e = max(n_experts, 1)
    flat = lax.map(lambda n: make_slice(root, name, first + n // e, n % e,
                                        cfg, dtype),
                   jnp.arange(n_layers * e))
    if n_experts:
        return flat.reshape(n_layers, n_experts, *flat.shape[1:])
    return flat


def init_params(root, cfg: dict):
    """The whole tree in the program's layout. Jit it: one call."""
    import jax.numpy as jnp
    m = dims(cfg)
    d, Ld, Lm, E = m["d"], m["Ld"], m["L"] - m["Ld"], m["E"]

    def attn(first, n):
        out = {"attn_norm": jnp.ones((n, d), jnp.float32),
               "kv_norm": jnp.ones((n, m["r"]), jnp.float32),
               "ffn_norm": jnp.ones((n, d), jnp.float32)}
        for name in ATTN:
            out[name] = _stack(root, name, cfg, first, n, 0, None)
        return out

    def swiglu(names, first, n, experts):
        return {k: _stack(root, name, cfg, first, n, experts, None)
                for k, name in zip(DENSE, names)}

    return {
        "embed": make_slice(root, "embed", 0, 0, cfg),
        "dense_layers": {**attn(0, Ld), **swiglu(DENSE, 0, Ld, 0)},
        "layers": {
            **attn(Ld, Lm),
            "router": _stack(root, "router", cfg, Ld, Lm, 0, jnp.float32),
            "router_bias": _stack(root, "router_bias", cfg, Ld, Lm, 0, None),
            "banks": swiglu(EXPERT, Ld, Lm, E),
            "shared": swiglu(SHARED, Ld, Lm, 0)},
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": make_slice(root, "lm_head", 0, 0, cfg)}

"""Seeded weights for a decoder of window and full attention layers with
fine-grained experts (``trinity-mini-l5``), in the layout
``kubetorch_tpu.models.afmoe`` takes: ``embed``, ``runs`` (one stacked dict a
run of like layers: the same attention kind and the same FFN, an expert
run's routed experts under ``banks``), ``final_norm`` and ``lm_head``.

As in ``bench_weights.py``, whose embedding and head these are: every slice
(one leaf of one layer, or of one expert of one layer) has a key of its own,
``fold_in(fold_in(fold_in(root(seed), leaf), layer), expert)`` with ``layer``
counted over the whole model, so the plain reference
(``bench_reference_afmoe.py``) makes the same values again a layer and an
expert at a time. N(0, 1/fan_in) rounded to bfloat16; norms 1; the router in
float32; the expert bias N(0, 0.01^2), rounded to bfloat16 and held in
float32 (a zero bias would leave the correction untested).

No jax at import time.
"""

from __future__ import annotations

import math

import bench_weights as W

WINDOW, FULL = "sliding_attention", "full_attention"
# leaf name -> id (embed 0 and lm_head 1 are ``bench_weights``' own)
LEAVES = {"wq": 60, "wk": 61, "wv": 62, "wo": 63, "w_attn_gate": 64,
          "w_gate": 65, "w_up": 66, "w_down": 67, "router": 68,
          "router_bias": 69, "e_gate": 70, "e_up": 71, "e_down": 72,
          "s_gate": 73, "s_up": 74, "s_down": 75}
BIAS_STD = 0.01
ATTN = ("wq", "wk", "wv", "wo", "w_attn_gate")
DENSE = ("w_gate", "w_up", "w_down")
EXPERT = ("e_gate", "e_up", "e_down")
SHARED = ("s_gate", "s_up", "s_down")
NORMS = ("attn_norm", "post_attn_norm", "ffn_norm", "post_ffn_norm")


def dims(cfg: dict) -> dict:
    """Sizes of the configuration file (keys as in the published
    config.json)."""
    types = tuple(cfg["layer_types"])
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    return {"d": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg["intermediate_size"], "fm": cfg["moe_intermediate_size"],
            "fs": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["num_experts"], "K": cfg["num_experts_per_tok"],
            "L": cfg["num_hidden_layers"], "Ld": cfg["num_dense_layers"],
            "V": cfg["vocab_size"], "W": cfg["sliding_window"],
            "types": types, "Lw": types.count(WINDOW),
            "Lf": types.count(FULL)}


def runs(cfg: dict) -> list:
    """(attention kind, "dense" or "moe", first layer, length) for each run
    of like layers, in order (the program's ``AfmoeConfig.runs`` counts the
    first layer among its kind's; here it is the model's layer number)."""
    m, out = dims(cfg), []
    for i, kind in enumerate(m["types"]):
        ffn = "dense" if i < m["Ld"] else "moe"
        if out and out[-1][:2] == [kind, ffn]:
            out[-1][3] += 1
        else:
            out.append([kind, ffn, i, 1])
    return [tuple(r) for r in out]


def leaf_shapes(cfg: dict) -> dict:
    """leaf name -> (shape of one slice, fan_in)."""
    m = dims(cfg)
    d, q, kv = m["d"], m["nh"] * m["hd"], m["nkv"] * m["hd"]
    return {"wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
            "wo": ((q, d), q), "w_attn_gate": ((d, q), d),
            "w_gate": ((d, m["f"]), d), "w_up": ((d, m["f"]), d),
            "w_down": ((m["f"], d), m["f"]),
            "router": ((d, m["E"]), d), "router_bias": ((m["E"],), None),
            "e_gate": ((d, m["fm"]), d), "e_up": ((d, m["fm"]), d),
            "e_down": ((m["fm"], d), m["fm"]),
            "s_gate": ((d, m["fs"]), d), "s_up": ((d, m["fs"]), d),
            "s_down": ((m["fs"], d), m["fs"])}


def param_count(cfg: dict) -> int:
    """Every parameter: matrices, the router's bias, norms."""
    m, s = dims(cfg), leaf_shapes(cfg)
    n = lambda names: sum(math.prod(s[k][0]) for k in names)  # noqa: E731
    attn = n(ATTN) + len(NORMS) * m["d"] + 2 * m["hd"]
    moe = m["E"] * n(EXPERT) + n(SHARED) + n(("router", "router_bias"))
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
    d, hd, E = m["d"], m["hd"], m["E"]

    def run(ffn, first, n):
        out = {k: jnp.ones((n, d), jnp.float32) for k in NORMS}
        out.update(q_norm=jnp.ones((n, hd), jnp.float32),
                   k_norm=jnp.ones((n, hd), jnp.float32))
        for name in ATTN:
            out[name] = _stack(root, name, cfg, first, n, 0, None)
        if ffn == "dense":
            for name in DENSE:
                out[name] = _stack(root, name, cfg, first, n, 0, None)
            return out
        out["router"] = _stack(root, "router", cfg, first, n, 0, jnp.float32)
        out["router_bias"] = _stack(root, "router_bias", cfg, first, n, 0,
                                    None)
        out["shared"] = {k: _stack(root, name, cfg, first, n, 0, None)
                         for k, name in zip(DENSE, SHARED)}
        out["banks"] = {k: _stack(root, name, cfg, first, n, E, None)
                        for k, name in zip(DENSE, EXPERT)}
        return out

    return {"embed": make_slice(root, "embed", 0, 0, cfg),
            "runs": [run(ffn, first, n) for _, ffn, first, n in runs(cfg)],
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": make_slice(root, "lm_head", 0, 0, cfg)}

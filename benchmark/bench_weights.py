"""The benchmark's own seeded weights.

The program is handed a parameter tree in the layout its engine and train
step take (``embed`` / ``layers`` stacked on a leading L / ``final_norm`` /
``lm_head``); the values come from here, not from the program's ``*_init``,
so that the plain reference (``bench_reference.py``) can make the same
values again from the seed alone, one layer or one expert at a time, without
taking anything the program has made.

Every slice (one leaf of one layer, or of one expert of one layer) has a key
of its own: ``fold_in(fold_in(fold_in(root(seed), leaf), layer), expert)``.
Values are N(0, 1/fan_in) rounded to bfloat16, so a float32 copy is exact.
Norm weights are ones, as in the published initialisations.

No jax at import time: the parent process imports the service module, which
imports this one.
"""

from __future__ import annotations

import math

# leaf name -> (id, stacked over layers, per-expert)
LEAVES = {
    "embed": (0, False, False), "lm_head": (1, False, False),
    "wq": (2, True, False), "wk": (3, True, False), "wv": (4, True, False),
    "wo": (5, True, False), "w_gate": (6, True, False),
    "w_up": (7, True, False), "w_down": (8, True, False),
    "router": (9, True, False),
    "e_gate": (10, True, True), "e_up": (11, True, True),
    "e_down": (12, True, True),
}


def dims(cfg: dict) -> dict:
    """Sizes of a configuration file (keys as in the published config.json)."""
    d = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // nh
    return {"d": d, "nh": nh, "nkv": nkv, "hd": hd,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"], "E": cfg.get("num_local_experts", 0),
            "K": cfg.get("num_experts_per_tok", 0)}


def leaf_shapes(cfg: dict) -> dict:
    """leaf name -> (shape of one slice, fan_in)."""
    m = dims(cfg)
    d, f, q, kv = m["d"], m["f"], m["nh"] * m["hd"], m["nkv"] * m["hd"]
    out = {"embed": ((m["V"], d), d), "lm_head": ((d, m["V"]), d),
           "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
           "wo": ((q, d), q)}
    if m["E"]:
        out.update({"router": ((d, m["E"]), d), "e_gate": ((d, f), d),
                    "e_up": ((d, f), d), "e_down": ((f, d), f)})
    else:
        out.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                    "w_down": ((f, d), f)})
    return out


def param_count(cfg: dict) -> int:
    m = dims(cfg)
    n = 0
    for name, (shape, _) in leaf_shapes(cfg).items():
        _, stacked, per_expert = LEAVES[name]
        n += math.prod(shape) * (m["L"] if stacked else 1) * \
            (m["E"] if per_expert else 1)
    return n + m["d"] * (2 * m["L"] + 1)          # norms


def root_key(seed: int):
    """Any whole number up to a little over 2**31: a PRNGKey takes 32 signed
    bits without x64, so the high part is folded in."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def make_slice(root, name: str, layer, expert, cfg: dict, dtype=None):
    """One slice from its own key. ``layer`` / ``expert`` may be traced."""
    import jax
    import jax.numpy as jnp
    shape, fan_in = leaf_shapes(cfg)[name]
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(root, LEAVES[name][0]), layer), expert)
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    w = w.astype(jnp.bfloat16)
    return w if dtype is None else w.astype(dtype)


def _stack(root, name, cfg, n_layers, n_experts, dtype):
    """(L[, E], *shape), one slice at a time so that the float32 draw of a
    whole stacked leaf never exists."""
    import jax.numpy as jnp
    from jax import lax
    e = max(n_experts, 1)
    flat = lax.map(lambda n: make_slice(root, name, n // e, n % e, cfg, dtype),
                   jnp.arange(n_layers * e))
    if n_experts:
        return flat.reshape(n_layers, n_experts, *flat.shape[1:])
    return flat


def init_params(seed, cfg: dict):
    """The whole tree in the program's layout, bfloat16 (router and norms
    float32, as the program's own init has them). Jit it: one call."""
    import jax.numpy as jnp
    m = dims(cfg)
    root = root_key(seed) if isinstance(seed, int) else seed
    L, E, d = m["L"], m["E"], m["d"]
    layers = {"attn_norm": jnp.ones((L, d), jnp.float32),
              "ffn_norm": jnp.ones((L, d), jnp.float32)}
    for name in ("wq", "wk", "wv", "wo"):
        layers[name] = _stack(root, name, cfg, L, 0, None)
    if E:
        layers["router"] = _stack(root, "router", cfg, L, 0, jnp.float32)
        layers["experts"] = {
            "w_gate": _stack(root, "e_gate", cfg, L, E, None),
            "w_up": _stack(root, "e_up", cfg, L, E, None),
            "w_down": _stack(root, "e_down", cfg, L, E, None)}
    else:
        for name in ("w_gate", "w_up", "w_down"):
            layers[name] = _stack(root, name, cfg, L, 0, None)
    return {"embed": make_slice(root, "embed", 0, 0, cfg),
            "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": make_slice(root, "lm_head", 0, 0, cfg)}

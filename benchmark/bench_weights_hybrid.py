"""Seeded weights for a hybrid Mamba-2 / attention decoder
(``granite-4.0-h-micro``), in the layout ``kubetorch_tpu.models.hybrid``
takes: ``embed`` (tied to the head), ``runs`` (one stacked dict a run of
like layers) and ``final_norm``.

As in ``bench_weights.py``, whose embedding and attention / MLP leaves these
are: every slice (one leaf of one layer) has a key of its own,
``fold_in(fold_in(fold_in(root(seed), leaf), layer), 0)`` with ``layer``
counted over the whole model, so the plain reference
(``bench_reference_hybrid.py``) makes the same values again a layer at a
time. Matrices N(0, 1/fan_in) rounded to bfloat16; the convolution's taps and
bias N(0, 1/4) (fan_in: its taps), bfloat16; the SSM's own parameters as
Mamba-2 initialises them, in float32: ``A_log = log U(1, 16)``, ``dt_bias``
the inverse softplus of ``exp U(log 1e-3, log 1e-1)`` with a floor of 1e-4;
``D`` and the norm weights 1.

No jax at import time.
"""

from __future__ import annotations

import math

import bench_weights as W

# leaf name -> id (``bench_weights``' own leaves keep theirs)
LEAVES = {"in_proj": 40, "conv_w": 41, "conv_b": 42, "A_log": 43,
          "dt_bias": 44, "wo_ssm": 45}
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
MAMBA = ("in_proj", "conv_w", "conv_b", "A_log", "dt_bias", "wo_ssm")


def dims(cfg: dict) -> dict:
    """Sizes of the configuration file (keys as in the published
    config.json)."""
    d, h, p = (cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"])
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    di = cfg["mamba_expand"] * d
    conv = di + 2 * cfg["mamba_n_groups"] * n
    base = W.dims(cfg)
    return {**base, "di": di, "h": h, "p": p, "n": n, "k": k, "conv": conv,
            "in": di + conv + h,
            "types": tuple(cfg["layer_types"]),
            "La": cfg["layer_types"].count("attention"),
            "Lm": cfg["layer_types"].count("mamba")}


def leaf_shapes(cfg: dict) -> dict:
    """leaf name -> (shape of one slice, fan_in) for the Mamba-2 leaves;
    ``wo_ssm`` is the Mamba-2 layer's out projection (the program's ``wo``
    of such a layer), which has a key of its own."""
    m = dims(cfg)
    return {"in_proj": ((m["d"], m["in"]), m["d"]),
            "conv_w": ((m["k"], m["conv"]), m["k"]),
            "conv_b": ((m["conv"],), m["k"]),
            "A_log": ((m["h"],), None), "dt_bias": ((m["h"],), None),
            "wo_ssm": ((m["di"], m["d"]), m["di"])}


def runs(cfg: dict) -> list:
    """(kind, [whole-model index of each layer]) for each run of like
    layers, in order."""
    out = []
    for i, kind in enumerate(cfg["layer_types"]):
        if out and out[-1][0] == kind:
            out[-1][1].append(i)
        else:
            out.append((kind, [i]))
    return out


def param_count(cfg: dict) -> int:
    m, s = dims(cfg), leaf_shapes(cfg)
    mlp = 3 * m["d"] * m["f"] + 2 * m["d"]
    attn = (2 * m["d"] * m["nh"] * m["hd"] + 2 * m["d"] * m["nkv"] * m["hd"])
    mamba = sum(math.prod(s[k][0]) for k in MAMBA) + m["h"] + m["di"]
    return (m["V"] * m["d"] + m["d"] + m["Lm"] * (mamba + mlp)
            + m["La"] * (attn + mlp))


def make_slice(root, name: str, layer, cfg: dict, dtype=None):
    """One slice from its own key; ``layer`` (counted over the whole model)
    may be traced."""
    import jax
    import jax.numpy as jnp
    if name in W.LEAVES:
        return W.make_slice(root, name, layer, 0, cfg, dtype)
    shape, fan_in = leaf_shapes(cfg)[name]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        root, LEAVES[name]), layer), 0)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1))), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    w = (jax.random.normal(key, shape, jnp.float32)
         * fan_in ** -0.5).astype(jnp.bfloat16)
    return w if dtype is None else w.astype(dtype)


def _stack(root, name, cfg, layers, dtype=None):
    import jax.numpy as jnp
    from jax import lax
    return lax.map(lambda i: make_slice(root, name, i, cfg, dtype),
                   jnp.asarray(layers, jnp.int32))


def init_params(root, cfg: dict):
    """The whole tree in the program's layout. Jit it: one call."""
    import jax.numpy as jnp
    m = dims(cfg)
    d = m["d"]

    def run(kind, layers):
        n = len(layers)
        out = {"attn_norm": jnp.ones((n, d), jnp.float32),
               "ffn_norm": jnp.ones((n, d), jnp.float32)}
        out.update({k: _stack(root, k, cfg, layers) for k in MLP})
        if kind == "attention":
            out.update({k: _stack(root, k, cfg, layers) for k in ATTN})
            return out
        out.update({k: _stack(root, k, cfg, layers) for k in MAMBA
                    if k != "wo_ssm"})
        out.update(wo=_stack(root, "wo_ssm", cfg, layers),
                   D=jnp.ones((n, m["h"]), jnp.float32),
                   ssm_norm=jnp.ones((n, m["di"]), jnp.float32))
        return out

    return {"embed": make_slice(root, "embed", 0, cfg),
            "runs": [run(kind, layers) for kind, layers in runs(cfg)],
            "final_norm": jnp.ones((d,), jnp.float32)}

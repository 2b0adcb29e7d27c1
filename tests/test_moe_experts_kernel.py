"""The grouped expert kernel (``ops/moe_experts.py``; ISSUE 34), interpreted
on the CPU (``ops/backend.py:interpret_default``), against the einsum form
of ``models/mla.py:_routed_experts`` on seeded banks:

- every expert hit, some unhit, exactly one hit, none hit (every slot dead),
  a dead slot among live ones, a layer index > 0 of a stack of 3, rows that
  are not a multiple of the sublane tile; float32 to 1e-5, bfloat16 within
  the einsum form's own distance to float32;
- the work list and the banks' index map never name an expert that got no
  token, and a step past the list names the block of the step before it (so
  its DMA is elided); an unhit expert's bank may hold NaN and nothing shows;
- which calls take the kernel: the row count, the experts' shape and the
  TPU backend (elsewhere the einsums, as the attention kernels' callers);
- its gradient is the einsum form's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu.models import experts, mla
from kubetorch_tpu.ops import moe_experts as K

pytestmark = pytest.mark.level("unit")

L, E, D, F, TOPK = 3, 8, 128, 256, 3
DEAD = E                    # what ``moe_ffn_dropless`` gives a dead slot


def _banks(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)

    def init(k, shape, fan_in):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        return w.astype(dtype)

    return {"w_gate": init(ks[0], (L, E, D, F), D),
            "w_up": init(ks[1], (L, E, D, F), D),
            "w_down": init(ks[2], (L, E, F, D), F)}


def _routing(idx, seed=1):
    """(gates (M, E) float32, sizes (E,)) for chosen experts ``idx`` (M, K),
    ``DEAD`` where the row routes nowhere, as ``moe_ffn_dropless`` and
    ``_routed_experts`` make them."""
    idx = jnp.asarray(idx, jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(seed), idx.shape, jnp.float32,
                           0.1, 1.0)
    w = jnp.where(idx < E, w, 0.0)
    gates = jnp.einsum("mk,mke->me", w, jax.nn.one_hot(idx, E, dtype=w.dtype))
    sizes = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(E)[None, :],
                    axis=0, dtype=jnp.int32)
    return gates, sizes


def _rows(m, dtype, seed=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, D),
                             jnp.float32).astype(dtype)


CASES = {
    # 16 rows over 8 experts top-3, every expert somebody's choice
    "all-hit": [[(m + j) % E for j in range(TOPK)] for m in range(16)],
    "some-unhit": [[0, 2, 5], [2, 5, 7], [0, 5, 7], [2, 0, 7], [5, 7, 0]],
    "one-hit": [[4, 4, 4]] * 6,
    "none-hit": [[DEAD] * TOPK] * 4,
    "dead-slot-among-live": [[1, 3, 6], [DEAD] * TOPK, [3, 6, 7],
                             [DEAD] * TOPK, [1, 6, 7]],
    "last-expert-only": [[E - 1, DEAD, DEAD]] * 3,
    "rows-past-a-tile": [[(2 * m + j) % 6 for j in range(TOPK)]
                         for m in range(19)],
}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_einsum_form_in_float32(case, layer):
    banks = _banks(jnp.float32)
    gates, sizes = _routing(CASES[case])
    x = _rows(len(CASES[case]), jnp.float32)
    got = K.moe_experts(x, gates, banks["w_gate"], banks["w_up"],
                        banks["w_down"], layer, sizes)
    want = experts._dense_experts(x, gates, banks, layer)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=1e-5)
    if case == "none-hit":
        assert not np.asarray(got).any()


@pytest.mark.parametrize("case", ["all-hit", "some-unhit", "one-hit",
                                  "dead-slot-among-live"])
def test_kernel_in_bfloat16_is_no_farther_from_float32_than_the_einsums(case):
    """Same operands, float32 accumulation in each dot, the activation
    rounded before the down product: the kernel keeps the gate and the sum
    over the experts in float32 where the einsum form rounds both, so it
    lies within the einsum form's own distance to the float32 result."""
    bf16 = _banks(jnp.bfloat16)
    exact_banks = {k: v.astype(jnp.float32) for k, v in bf16.items()}
    gates, sizes = _routing(CASES[case])
    x = _rows(len(CASES[case]), jnp.bfloat16)
    exact = np.asarray(experts._dense_experts(x.astype(jnp.float32), gates,
                                          exact_banks, 1))
    einsums = np.asarray(experts._dense_experts(x, gates, bf16, 1), np.float32)
    got = K.moe_experts(x, gates, bf16["w_gate"], bf16["w_up"],
                        bf16["w_down"], 1, sizes)
    assert got.dtype == jnp.bfloat16
    kernel = np.asarray(got, np.float32)
    assert np.abs(kernel - exact).max() <= np.abs(einsums - exact).max() * 1.05
    assert np.abs(kernel - exact).mean() <= np.abs(einsums - exact).mean()


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_work_list_names_only_the_experts_that_got_a_token(case):
    """What the banks' index map returns over the whole grid: hit experts
    once each in their order, then the last one again (Pallas elides a DMA
    whose block index repeats), never an expert nobody chose."""
    _, sizes = _routing(CASES[case])
    ids, n_hit = (np.asarray(a) for a in K.work_list(sizes))
    hit = np.flatnonzero(np.asarray(sizes) > 0)
    assert n_hit.shape == (1,) and int(n_hit[0]) == len(hit)
    layer = np.asarray([1], np.int32)
    named = [K.bank_block(i, layer, ids, n_hit) for i in range(E)]
    assert all(b[0] == 1 and b[2:] == (0, 0) for b in named)
    experts = [int(b[1]) for b in named]
    assert experts[:len(hit)] == list(hit)
    if len(hit):
        assert set(experts) == set(hit)
        assert experts[len(hit):] == [hit[-1]] * (E - len(hit))
    else:
        assert len(set(experts)) == 1          # one block, fetched once


def test_an_unhit_experts_bank_is_never_read():
    """NaN in every bank nobody chose, and in the other layers: a product
    with a gate of zero would still be NaN."""
    gates, sizes = _routing(CASES["some-unhit"])
    unhit = np.asarray(sizes) == 0
    assert unhit.any() and not unhit.all()
    banks = {k: np.array(v) for k, v in _banks(jnp.float32).items()}
    for v in banks.values():
        v[1, unhit] = np.nan
        v[0] = v[2] = np.nan
    x = _rows(5, jnp.float32)
    got = K.moe_experts(x, gates, *(jnp.asarray(banks[k]) for k in (
        "w_gate", "w_up", "w_down")), 1, sizes)
    assert np.isfinite(np.asarray(got)).all()
    clean = _banks(jnp.float32)
    np.testing.assert_allclose(got, experts._dense_experts(x, gates, clean, 1),
                               atol=1e-5)


@pytest.mark.parametrize("d,f,itemsize,ok", [
    (2048, 1408, 2, True),          # the cell's experts, bfloat16
    (2048, 1408, 4, True),
    (128, 256, 4, True),
    (64, 32, 4, False),             # ``MlaMoeConfig.tiny``: not lane-aligned
    (2048, 1400, 2, False),
    (4096, 14336, 2, True),         # an expert of 352 MB: 7 tiles of 2,048
    (6144, 2048, 2, True),          # GLM-5's, 75.5 MB: 2 tiles of 1,024
    (131072, 256, 2, False),        # 128 columns of it do not fit the VMEM
])
def test_which_shapes_the_kernel_tiles(d, f, itemsize, ok):
    assert K.moe_experts_supported(d, f, itemsize) is ok


def test_an_expert_too_wide_for_the_vmem_goes_a_tile_of_f_at_a_time():
    assert K.f_tile(2048, 1408, 2) == 1408          # whole, one step
    assert K.f_tile(6144, 2048, 2) == 1024
    assert K.f_tile(4096, 14336, 2) == 2048
    assert K.f_tile(64, 32, 4) == 0
    # the index maps of an expert in 2 steps: gate and up move along F,
    # down along its rows; a step past the list repeats the last block
    layer, ids, n_hit = (np.asarray(a, np.int32) for a in ([1], [2, 5, 5, 5],
                                                           [2]))
    up = [tuple(int(v) for v in K.bank_block(i, layer, ids, n_hit, chunks=2))
          for i in range(8)]
    down = [tuple(int(v) for v in K.bank_block(i, layer, ids, n_hit, chunks=2,
                                               down=True)) for i in range(8)]
    assert up == [(1, 2, 0, 0), (1, 2, 0, 1), (1, 5, 0, 0)] + [(1, 5, 0, 1)] * 5
    assert down == [(1, 2, 0, 0), (1, 2, 1, 0), (1, 5, 0, 0)] + [
        (1, 5, 1, 0)] * 5


@pytest.mark.parametrize("case", ["all-hit", "some-unhit", "none-hit",
                                  "rows-past-a-tile"])
def test_the_tiled_kernel_is_the_einsum_form(case, monkeypatch):
    """The same cases with an expert in two steps of 128 of its 256
    columns (the VMEM bound lowered to force it)."""
    monkeypatch.setattr(K, "f_tile", lambda d, f, itemsize: 128)
    banks = _banks(jnp.float32)
    gates, sizes = _routing(CASES[case])
    x = _rows(len(CASES[case]), jnp.float32)
    got = K.moe_experts(x, gates, banks["w_gate"], banks["w_up"],
                        banks["w_down"], 2, sizes)
    np.testing.assert_allclose(got, experts._dense_experts(x, gates, banks, 2),
                               atol=1e-5)


@pytest.mark.parametrize("rows,d,f,backend,kernel", [
    (1, D, F, "tpu", True), (16, D, F, "tpu", True), (256, D, F, "tpu", True),
    (experts.DENSE_ROWS_MAX, D, F, "tpu", True),
    (experts.DENSE_ROWS_MAX + 1, D, F, "tpu", False),     # sorted runs
    (16, 64, 32, "tpu", False),
    # off the chip the einsums, as the attention kernels' callers choose
    (16, D, F, "cpu", False), (16, D, F, "gpu", False),
])
def test_the_kernel_is_chosen_from_rows_shapes_and_backend(monkeypatch, rows,
                                                           d, f, backend,
                                                           kernel):
    cfg = mla.MlaMoeConfig.tiny(dim=d, moe_ffn_dim=f, dtype=jnp.float32)
    lw = jax.tree_util.tree_map(
        lambda a: a[0], mla.mla_moe_init(jax.random.PRNGKey(0), cfg)["layers"])
    calls, sorted_calls = [], []
    monkeypatch.setattr(experts, "moe_experts", lambda x, *a, **k: calls.append(
        x.shape) or jnp.zeros_like(x))
    # more rows sort their pairs, and on the chip take the sorted kernel
    monkeypatch.setattr(experts, "moe_sorted", lambda xs, *a, **k: (
        sorted_calls.append(xs.shape) or jnp.zeros_like(xs)))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, rows, d))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    experts.moe_ffn_dropless(cfg, h, lw)
    assert calls == ([(rows, d)] if kernel else [])
    assert sorted_calls == ([(rows * cfg.experts_per_token, d)]
                            if rows > experts.DENSE_ROWS_MAX else [])


def test_the_gradient_is_the_einsum_forms():
    """``jax.grad`` through a call that takes the kernel: the backward pass
    differentiates the einsum form (``experts._grouped_experts``)."""
    banks = _banks(jnp.float32)
    gates, sizes = _routing(CASES["some-unhit"])
    x = _rows(5, jnp.float32)

    def loss(f, x, gates, banks):
        return jnp.sum(jnp.sin(f(x, gates, banks)))

    got = jax.grad(lambda *a: loss(
        lambda x, g, b: experts._grouped_experts(x, g, b, 2, sizes), *a),
        argnums=(0, 1, 2))(x, gates, banks)
    want = jax.grad(lambda *a: loss(
        lambda x, g, b: experts._dense_experts(x, g, b, 2), *a),
        argnums=(0, 1, 2))(x, gates, banks)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-5)

"""The sorted expert kernel (``ops/moe_experts.py:moe_sorted``,
``kt_moe_sorted``), interpreted on the CPU (``ops/backend.py:
interpret_default``), against the grouped-matmul form of
``models/experts.py`` (``_ragged_swiglu``) on seeded banks, at a row tile of
16 so that a few dozen pairs make several tiles:

- uneven runs with empty experts among them, runs that straddle tiles and a
  run longer than several tiles, every pair in one expert, pair counts that
  are not a multiple of the tile, rows past ``sum(sizes)`` (zero), a layer
  index > 0 of a stack of 3, an expert in two F-tiles; float32 to 1e-5,
  bfloat16 within the grouped matmul's own distance to float32;
- the work list: in order of tile and of expert, at most ⌈P / tm⌉ + E − 1
  items, every tile an expert's run touches once, every tile at least once,
  and a step past the list names the block of the step before it;
- a whole expert layer through the sorted path, all experts held or a share
  of them through the chunk loop (absent experts sorted last), the kernel
  against the grouped matmul; an unhit expert's bank may hold NaN;
- the gradient is the grouped matmul's.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubetorch_tpu.models import experts
from kubetorch_tpu.ops import moe_experts as K

pytestmark = pytest.mark.level("unit")

L, E, D, F, TM = 3, 8, 128, 256, 16


@pytest.fixture(autouse=True)
def small_tile(monkeypatch):
    monkeypatch.setattr(K, "SORTED_ROW_TILE", TM)


def _banks(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)

    def init(k, shape, fan_in):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        return w.astype(dtype)

    return {"w_gate": init(ks[0], (L, E, D, F), D),
            "w_up": init(ks[1], (L, E, D, F), D),
            "w_down": init(ks[2], (L, E, F, D), F)}


def _rows(p, dtype, seed=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (p, D),
                             jnp.float32).astype(dtype)


def _kernel(xs, banks, layer, sizes):
    return K.moe_sorted(xs, banks["w_gate"], banks["w_up"], banks["w_down"],
                        layer, jnp.asarray(sizes, jnp.int32))


# (sizes by expert, sorted pairs P): P at or past sum(sizes)
CASES = {
    "uneven-with-empty": ([5, 0, 20, 3, 0, 0, 17, 1], 46),
    "straddles-and-spans-tiles": ([3, 40, 0, 9, 12, 0, 0, 8], 72),
    "every-pair-one-expert": ([0, 0, 0, 64, 0, 0, 0, 0], 64),
    "not-a-multiple-of-the-tile": ([7, 7, 7, 7, 7, 7, 7, 6], 55),
    "rows-past-the-runs": ([4, 0, 7, 0, 0, 2, 0, 0], 40),
    "last-expert-only": ([0, 0, 0, 0, 0, 0, 0, 21], 21),
    "no-pair": ([0] * E, 20),
}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_grouped_matmul_in_float32(case, layer):
    sizes, p = CASES[case]
    banks = _banks(jnp.float32)
    xs = _rows(p, jnp.float32)
    got = _kernel(xs, banks, layer, sizes)
    want = experts._ragged_swiglu(xs, banks, layer,
                                  jnp.asarray(sizes, jnp.int32))
    assert got.shape == want.shape == (p, D) and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got)[sum(sizes):].any()


@pytest.mark.parametrize("case", ["uneven-with-empty",
                                  "straddles-and-spans-tiles",
                                  "rows-past-the-runs"])
def test_an_expert_in_two_f_tiles_is_the_grouped_matmul(case, monkeypatch):
    """An expert too wide for the VMEM goes a tile of its F columns a step
    (the bound lowered here to force two of 128)."""
    monkeypatch.setattr(K, "f_tile", lambda d, f, itemsize, rows=0: 128)
    sizes, p = CASES[case]
    banks = _banks(jnp.float32)
    xs = _rows(p, jnp.float32)
    np.testing.assert_allclose(
        _kernel(xs, banks, 1, sizes),
        experts._ragged_swiglu(xs, banks, 1, jnp.asarray(sizes, jnp.int32)),
        atol=1e-5)


@pytest.mark.parametrize("case", ["uneven-with-empty",
                                  "straddles-and-spans-tiles",
                                  "every-pair-one-expert"])
def test_kernel_in_bfloat16_is_no_farther_from_float32_than_the_grouped_matmul(
        case):
    """Same operands: the kernel keeps gate and up in float32 through the
    ``silu`` and rounds their product once, where the grouped matmul rounds
    gate and up each, so it lies within that form's own distance to the
    float32 result."""
    sizes, p = CASES[case]
    sizes_ = jnp.asarray(sizes, jnp.int32)
    bf16 = _banks(jnp.bfloat16)
    exact_banks = {k: v.astype(jnp.float32) for k, v in bf16.items()}
    xs = _rows(p, jnp.bfloat16)
    exact = np.asarray(experts._ragged_swiglu(xs.astype(jnp.float32),
                                              exact_banks, 1, sizes_))
    grouped = np.asarray(experts._ragged_swiglu(xs, bf16, 1, sizes_),
                         np.float32)
    got = _kernel(xs, bf16, 1, sizes)
    assert got.dtype == jnp.bfloat16
    kernel = np.asarray(got, np.float32)
    assert np.abs(kernel - exact).max() <= np.abs(grouped - exact).max() * 1.05
    assert np.abs(kernel - exact).mean() <= np.abs(grouped - exact).mean()


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_work_list_walks_each_touched_tile_once_in_order(case):
    """Items in order of tile and of expert, one for each (tile, expert)
    whose rows meet, one for each tile past the runs, none else; the list's
    length is ⌈P / tm⌉ + E − 1 and what the index maps name past its end is
    the block of its last item."""
    sizes, p = CASES[case]
    offsets, tiles, ids, n = (np.asarray(a) for a in K.sorted_work_list(
        jnp.asarray(sizes, jnp.int32), p, TM))
    n_tiles = -(-p // TM)
    assert tiles.shape == ids.shape == (n_tiles + E - 1,)
    assert list(offsets) == [0, *np.cumsum(sizes)]
    n = int(n[0])
    assert (np.diff(tiles) >= 0).all() and (np.diff(ids) >= 0).all()
    ends = np.cumsum(sizes)
    touched = sorted((t, e) for e in range(E) for t in range(n_tiles)
                     if sizes[e] and ends[e] - sizes[e] < (t + 1) * TM
                     and ends[e] > t * TM)
    past = [t for t in range(n_tiles) if t * TM >= ends[-1]]
    items = list(zip(tiles[:n].tolist(), ids[:n].tolist()))
    assert [i for i in items if i[0] not in past] == touched
    assert [t for t, _ in items if t in past] == past
    assert sorted(set(tiles[:n])) == list(range(n_tiles))
    layer = np.asarray([1], np.int32)
    for chunks in (1, 2):
        named = [tuple(int(v) for v in K.sorted_block(
            i, c, layer, offsets, tiles, ids, np.asarray([n]), TM, chunks))
            for i in range(len(tiles)) for c in range(chunks)]
        assert all(b[1] == e for b, e in zip(named[::chunks], ids))
        # from the last item with rows on, one block: nothing more fetched
        live = [i for i, (t, e) in enumerate(items) if t not in past]
        end = (live[-1] + 1) * chunks if live else 1
        assert set(named[end - 1:]) == {(1, int(ids[n - 1]), 0, chunks - 1)}
        assert all(b[3] == c for i in live for c, b in enumerate(
            named[i * chunks:(i + 1) * chunks]))


def _layer(held, seed=3):
    """(cfg, x, w, idx, sizes) of one expert layer's routing over 48 rows
    top-3, as ``moe_ffn_dropless`` makes them: of ``E`` experts, or of
    ``2 E`` where the layer holds the first ``E`` (an absent one's pairs get
    ``E`` and weight 0, and sort last)."""
    k, router = 3, 2 * E if held else E
    m = 48
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    w, idx = jax.lax.top_k(jax.random.uniform(keys[0], (m, router)), k)
    if held:
        w = jnp.where(idx < E, w, 0.0)
        idx = jnp.where(idx < E, idx, E)
    sizes = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(E)[None, :],
                    axis=0, dtype=jnp.int32)
    cfg = types.SimpleNamespace(n_held=E, experts_per_token=k,
                                held=(0, E) if held else None)
    return cfg, _rows(m, jnp.float32, seed), w, idx, sizes


@pytest.mark.parametrize("held", [False, True])
def test_a_layers_sorted_path_takes_the_kernel_where_it_runs(held,
                                                             monkeypatch):
    """``_sorted_experts`` with the kernel (``moe_sorted_auto`` steered to
    the shape check, as the TPU backend would have it) against itself with
    the grouped matmul: all experts held, and a share of them through the
    chunk loop in chunks of 16 pairs."""
    monkeypatch.setattr(experts, "HELD_PAIRS_CHUNK", 16)
    cfg, x, w, idx, sizes = _layer(held)
    banks = _banks(jnp.float32)
    calls = []
    real = experts.moe_sorted

    def counted(xs, *a):
        calls.append(xs.shape)
        return real(xs, *a)

    monkeypatch.setattr(experts, "moe_sorted", counted)
    monkeypatch.setattr(experts, "moe_sorted_auto", lambda *a: False)
    want = experts._sorted_experts(cfg, x, w, idx, sizes, (banks, 2))
    assert calls == []
    monkeypatch.setattr(experts, "moe_sorted_auto", K.moe_sorted_supported)
    got = experts._sorted_experts(cfg, x, w, idx, sizes, (banks, 2))
    # every pair in one call; a held share one a chunk (the scan's body,
    # traced once)
    assert calls == [(16, D)] if held else [(48 * 3, D)]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_an_unhit_experts_bank_is_never_read():
    """NaN in every bank no pair chose, and in the other layers: a product
    outside its run masked by a multiply would still be NaN."""
    sizes, p = CASES["uneven-with-empty"]
    unhit = np.asarray(sizes) == 0
    banks = {k: np.array(v) for k, v in _banks(jnp.float32).items()}
    for v in banks.values():
        v[1, unhit] = np.nan
        v[0] = v[2] = np.nan
    xs = _rows(p, jnp.float32)
    got = _kernel(xs, {k: jnp.asarray(v) for k, v in banks.items()}, 1,
                  sizes)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        got, experts._ragged_swiglu(xs, _banks(jnp.float32), 1,
                                    jnp.asarray(sizes, jnp.int32)),
        atol=1e-5)


def test_the_gradient_is_the_grouped_matmuls():
    """``jax.grad`` through the kernel's call: the backward pass
    differentiates the grouped matmul (``experts._sorted_swiglu``)."""
    sizes, p = CASES["straddles-and-spans-tiles"]
    sizes = jnp.asarray(sizes, jnp.int32)
    banks = _banks(jnp.float32)
    xs = _rows(p, jnp.float32)

    # a linear loss: the cotangent does not depend on the forward's output,
    # so both backward passes get the same one
    probe = _rows(p, jnp.float32, seed=5)

    def loss(f, xs, banks):
        return jnp.sum(f(xs, banks, 2, sizes) * probe)

    got = jax.grad(lambda *a: loss(experts._sorted_swiglu, *a),
                   argnums=(0, 1))(xs, banks)
    want = jax.grad(lambda *a: loss(experts._ragged_swiglu, *a),
                    argnums=(0, 1))(xs, banks)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("d,f,itemsize,ok", [
    (2048, 1024, 2, True),          # Trinity-Mini's experts
    (2048, 1408, 2, True),          # Kimi-VL's
    (6144, 2048, 2, True),          # GLM-5's, in F-tiles
    (64, 32, 4, False),             # the tiny configs: not lane-aligned
    (131072, 256, 2, False),        # 128 columns of it do not fit the VMEM
])
def test_which_shapes_the_sorted_kernel_tiles(d, f, itemsize, ok):
    assert K.moe_sorted_supported(d, f, itemsize) is ok

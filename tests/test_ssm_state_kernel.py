"""The fused Mamba-2 state step (``ops/ssm_state.py``), interpreted on the
CPU (``ops/backend.py:interpret_default``), against XLA's form that
``serve/state_cache.py:grid_mix`` takes off the TPU (the layer sliced out of
the grid, ``models/hybrid.py:ssm_update``, the layer written back):

- at ``HybridConfig.tiny()``'s state widths and at Granite's (64 heads of
  64 × 128), 2-4 slots of a grid of 3 layers: ``y`` and the advanced layer
  agree in float32, every other layer is bit for bit what it was, and
  nothing leaves float32;
- a traced layer inside a ``lax.scan`` whose carry is the grid, as
  ``serve/engine.py:_decode_step_impl`` runs it;
- a tiny hybrid engine with the kernel forced serves the greedy tokens and
  log-probabilities it serves through XLA's form, over several decode
  blocks;
- which calls take the kernel: the TPU backend and a state it tiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from kubetorch_tpu.models.hybrid import HybridConfig, hybrid_init, ssm_update
from kubetorch_tpu.ops import ssm_state as K

pytestmark = pytest.mark.level("unit")

LAYERS = 3
TINY = HybridConfig.tiny()
WIDTHS = {"tiny": (TINY.mamba_n_heads, TINY.mamba_d_head,
                   TINY.mamba_d_state),
          "granite": (64, 64, 128)}


def _operands(slots, widths, seed=0):
    """A grid (LAYERS, slots, H, P, N) and one step's decay, dt·x, B, C as
    ``models.hybrid.mamba_step`` makes them: decay in (0, 1], the rest of
    either sign."""
    h, p, n = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    grid = jax.random.normal(ks[0], (LAYERS, slots, h, p, n), jnp.float32)
    decay = jnp.exp(-jax.random.uniform(ks[1], (slots, h), jnp.float32,
                                        0.0, 2.0))
    dtx = 0.1 * jax.random.normal(ks[2], (slots, h, p), jnp.float32)
    b = jax.random.normal(ks[3], (slots, n), jnp.float32)
    c = jax.random.normal(ks[4], (slots, n), jnp.float32)
    return grid, (decay, dtx, b, c)


def _xla_form(grid, layer, decay, dtx, b, c):
    """``state_cache.grid_mix``'s form off the TPU."""
    s = lax.dynamic_index_in_dim(grid, layer, 0, keepdims=False)
    y, s = ssm_update(s, decay, dtx, b, c)
    return y, lax.dynamic_update_index_in_dim(grid, s, layer, 0)


def _close(got, want):
    """Float32's rounding apart: y sums N products in another order."""
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("slots,layer", [(2, 0), (3, 2), (4, 1)])
def test_the_kernel_is_xlas_form_in_float32(widths, slots, layer):
    grid, step = _operands(slots, WIDTHS[widths], seed=slots)
    y, out = jax.jit(K.ssm_state_step)(grid, jnp.int32(layer), *step)
    want_y, want = _xla_form(grid, layer, *step)
    assert y.dtype == out.dtype == jnp.float32
    assert y.shape == want_y.shape and out.shape == grid.shape
    _close(y, want_y)
    _close(out[layer], want[layer])
    for other in set(range(LAYERS)) - {layer}:
        np.testing.assert_array_equal(out[other], grid[other])


def test_a_traced_layer_in_a_scan_carrying_the_grid():
    """Every layer of the grid advanced in turn, the layer an element of the
    scanned ``xs`` and the grid the carry, as the decode step scans a run of
    Mamba-2 layers: the kernel's grid and outputs are XLA's form's."""
    grid, _ = _operands(2, WIDTHS["granite"])
    steps = [_operands(2, WIDTHS["granite"], seed=10 + l)[1]
             for l in range(LAYERS)]
    xs = (jnp.arange(LAYERS, dtype=jnp.int32),
          *(jnp.stack(a) for a in zip(*steps)))

    def run(update):
        def body(g, x):
            layer, *step = x
            y, g = update(g, layer, *step)
            return g, y
        return jax.jit(lambda g: lax.scan(body, g, xs))(grid)

    got, got_y = run(K.ssm_state_step)
    want, want_y = run(_xla_form)
    _close(got_y, want_y)
    _close(got, want)


def test_a_tiny_engine_serves_the_same_through_the_kernel():
    """The engine's decode blocks with the chip's choice forced (the kernel,
    interpreted) against XLA's form: three requests of different lengths
    over blocks of 4, the same greedy tokens and the same log-probabilities,
    and the kernel was traced into the decode block."""
    from kubetorch_tpu.serve import GenerationEngine
    from kubetorch_tpu.serve import engine as E
    from kubetorch_tpu.serve import state_cache

    cfg = HybridConfig.tiny(dtype=jnp.float32)
    params = hybrid_init(jax.random.PRNGKey(0), cfg)
    prompts = [np.random.RandomState(i).randint(0, 256, n).tolist()
               for i, n in enumerate((5, 13, 20))]

    def serve():
        eng = GenerationEngine(params, cfg, slots=3, max_len=64,
                               prefill_buckets=(16, 32), decode_block=4)
        handles = [eng.submit(p, max_new_tokens=14) for p in prompts]
        while eng.step():
            pass
        return [(h.result(timeout=0), h.logprobs) for h in handles]

    traced = []
    kernel = state_cache.ssm_state_step
    E._decode_block.clear_cache()
    want = serve()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(state_cache, "ssm_state_step_auto", lambda *_: True)
        mp.setattr(state_cache, "ssm_state_step",
                   lambda *a: (traced.append(a[0].shape), kernel(*a))[1])
        E._decode_block.clear_cache()
        got = serve()
    E._decode_block.clear_cache()
    assert traced and all(s == (cfg.n_mamba, 3, cfg.mamba_n_heads,
                                cfg.mamba_d_head, cfg.mamba_d_state)
                          for s in traced)
    for (toks, lps), (want_toks, want_lps) in zip(got, want):
        assert toks == want_toks and len(toks) == 14
        np.testing.assert_allclose(lps, want_lps, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("widths,ok", [
    ((64, 64, 128), True),            # Granite-4.0-H-Micro
    ((128, 64, 128), True),
    ((4, 32, 16), False),             # the tiny config's: N under a lane row
    ((64, 60, 128), False),           # P off the sublane tile
    ((6, 64, 128), False),            # dt·x's heads off the sublane tile
])
def test_which_states_the_kernel_tiles(widths, ok):
    assert K.ssm_state_supported(*widths) is ok
    # the CPU backend takes XLA's form whatever the widths
    assert K.ssm_state_step_auto(*widths) is False

"""Pipeline parallelism: GPipe output must equal the sequential forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.level("release")  # jit-heavy matrix: full tier only

from kubetorch_tpu.models.llama import LlamaConfig, llama_forward, llama_init
from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh


@pytest.fixture(scope="module")
def pipe_mesh(cpu_mesh_devices):
    import numpy as _np
    from jax.sharding import Mesh

    devices = _np.asarray(jax.devices()[:4]).reshape(4)
    return Mesh(devices, ("pipe",))


CFG = LlamaConfig.tiny(n_layers=4, attn_impl="xla", dtype=jnp.float32,
                       remat=False)
CFG_AUTO = LlamaConfig.tiny(n_layers=4, attn_impl="auto", dtype=jnp.float32,
                            remat=False)


def _sharded_params(params, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(path_is_layer, leaf):
        spec = P("pipe") if path_is_layer else P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return {
        "embed": place(False, params["embed"]),
        "layers": jax.tree_util.tree_map(lambda l: place(True, l),
                                         params["layers"]),
        "final_norm": place(False, params["final_norm"]),
        "lm_head": place(False, params["lm_head"]),
    }


def test_pipelined_forward_matches_sequential(pipe_mesh):
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    params = llama_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, CFG.vocab_size)
    ref = llama_forward(params, tokens, CFG)

    sharded = _sharded_params(params, pipe_mesh)
    out = jax.jit(lambda p, t: llama_forward_pipelined(
        p, t, CFG, pipe_mesh, n_microbatches=4))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pipelined_microbatch_count_flexible(pipe_mesh):
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    params = llama_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, CFG.vocab_size)
    ref = llama_forward(params, tokens, CFG)
    sharded = _sharded_params(params, pipe_mesh)
    # more microbatches than stages (smaller bubbles)
    out = jax.jit(lambda p, t: llama_forward_pipelined(
        p, t, CFG, pipe_mesh, n_microbatches=8))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_pipelined_grads_match(pipe_mesh):
    from kubetorch_tpu.parallel.pipeline import llama_loss_pipelined
    from kubetorch_tpu.models.llama import llama_loss

    params = llama_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, CFG.vocab_size)
    targets = jnp.roll(tokens, -1, 1)
    g_ref = jax.grad(llama_loss)(params, tokens, targets, CFG)

    sharded = _sharded_params(params, pipe_mesh)
    g_pipe = jax.jit(jax.grad(lambda p, t, y: llama_loss_pipelined(
        p, t, y, CFG, pipe_mesh, n_microbatches=4)))(sharded, tokens, targets)
    np.testing.assert_allclose(np.asarray(g_pipe["layers"]["wq"]),
                               np.asarray(g_ref["layers"]["wq"]),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(g_pipe["embed"]),
                               np.asarray(g_ref["embed"]),
                               rtol=5e-4, atol=5e-4)


def test_invalid_configs(pipe_mesh):
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    params = _sharded_params(llama_init(jax.random.PRNGKey(0), CFG), pipe_mesh)
    tokens = jnp.zeros((8, 16), jnp.int32)
    with pytest.raises(ValueError, match="not divisible by"):
        bad = LlamaConfig.tiny(n_layers=3, attn_impl="xla",
                               dtype=jnp.float32, remat=False)
        llama_forward_pipelined(params, tokens, bad, pipe_mesh)
    with pytest.raises(ValueError, match="microbatches"):
        llama_forward_pipelined(params, tokens, CFG, pipe_mesh,
                                n_microbatches=3)
    with pytest.raises(ValueError, match="context"):
        uly = LlamaConfig.tiny(n_layers=4, attn_impl="ulysses",
                               dtype=jnp.float32, remat=False)
        llama_forward_pipelined(params, tokens, uly, pipe_mesh)


# ---------------------------------------------------------------------------
# Composition: pipe × data × tensor on one mesh (PARITY gap closed)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def composed_mesh(cpu_mesh_devices):
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=2, pipe=2, tensor=2),
                      devices=jax.devices()[:8])


def _composed_params(params, mesh):
    from kubetorch_tpu.parallel.pipeline import llama_pipeline_shardings

    return jax.tree_util.tree_map(
        jax.device_put, params, llama_pipeline_shardings(params, mesh))


def test_composed_forward_matches_sequential(composed_mesh):
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    params = llama_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                CFG.vocab_size)
    ref = llama_forward(params, tokens, CFG)
    sharded = _composed_params(params, composed_mesh)
    out = jax.jit(lambda p, t: llama_forward_pipelined(
        p, t, CFG, composed_mesh, n_microbatches=2))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_composed_grads_match(composed_mesh):
    from kubetorch_tpu.models.llama import llama_loss
    from kubetorch_tpu.parallel.pipeline import llama_loss_pipelined

    params = llama_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                CFG.vocab_size)
    targets = jnp.roll(tokens, -1, 1)
    g_ref = jax.grad(llama_loss)(params, tokens, targets, CFG)
    sharded = _composed_params(params, composed_mesh)
    g = jax.jit(jax.grad(lambda p, t, y: llama_loss_pipelined(
        p, t, y, CFG, composed_mesh, n_microbatches=2)))(
        sharded, tokens, targets)
    for k in ("wq", "wo", "w_down"):
        np.testing.assert_allclose(np.asarray(g["layers"][k]),
                                   np.asarray(g_ref["layers"][k]),
                                   rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(g["embed"]),
                               np.asarray(g_ref["embed"]),
                               rtol=5e-4, atol=5e-4)


@pytest.fixture(scope="module")
def zero3_mesh(cpu_mesh_devices):
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(fsdp=2, pipe=2, tensor=2),
                      devices=jax.devices()[:8])


def test_zero3_pipeline_params_sharded_and_forward_matches(zero3_mesh):
    """fsdp×pipe×tensor: stage weights are stored ZeRO-3-sharded (layer dim
    over pipe, d_model over fsdp, Megatron dim over tensor) and the stage
    body's per-layer all-gather reproduces the sequential forward."""
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    params = llama_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                CFG.vocab_size)
    ref = llama_forward(params, tokens, CFG)
    sharded = _composed_params(params, zero3_mesh)
    # (L/pipe, D/fsdp, N*Hd/tensor) — the ZeRO-3 memory win
    assert sharded["layers"]["wq"].addressable_shards[0].data.shape == \
        (CFG.n_layers // 2, CFG.dim // 2, CFG.n_heads * CFG.head_dim // 2)
    out = jax.jit(lambda p, t: llama_forward_pipelined(
        p, t, CFG, zero3_mesh, n_microbatches=2))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_zero3_pipeline_grads_match(zero3_mesh):
    """Weight grads reduce-scatter back over fsdp (all_gather transpose) and
    still equal the sequential reference."""
    from kubetorch_tpu.models.llama import llama_loss
    from kubetorch_tpu.parallel.pipeline import llama_loss_pipelined

    params = llama_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                CFG.vocab_size)
    targets = jnp.roll(tokens, -1, 1)
    g_ref = jax.grad(llama_loss)(params, tokens, targets, CFG)
    sharded = _composed_params(params, zero3_mesh)
    g = jax.jit(jax.grad(lambda p, t, y: llama_loss_pipelined(
        p, t, y, CFG, zero3_mesh, n_microbatches=2)))(
        sharded, tokens, targets)
    for k in ("wq", "wo", "w_down", "attn_norm"):
        np.testing.assert_allclose(np.asarray(g["layers"][k]),
                                   np.asarray(g_ref["layers"][k]),
                                   rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(g["lm_head"]),
                               np.asarray(g_ref["lm_head"]),
                               rtol=5e-4, atol=5e-4)


@pytest.fixture(scope="module")
def cp_mesh(cpu_mesh_devices):
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(context=2, pipe=2, tensor=2),
                      devices=jax.devices()[:8])


def test_ring_attention_inside_pipeline_matches_sequential(cp_mesh):
    """cp×pipe×tp: the sequence shards over the context axis and the stage
    body runs ring attention (per-rank RoPE slice included)."""
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    cfg_auto = CFG_AUTO
    params = llama_init(jax.random.PRNGKey(0), cfg_auto)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg_auto.vocab_size)
    ref = llama_forward(params, tokens, cfg_auto)
    sharded = _composed_params(params, cp_mesh)
    out = jax.jit(lambda p, t: llama_forward_pipelined(
        p, t, cfg_auto, cp_mesh, n_microbatches=2))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_pipeline_grads_match(cp_mesh):
    from kubetorch_tpu.models.llama import llama_loss
    from kubetorch_tpu.parallel.pipeline import llama_loss_pipelined

    cfg_auto = CFG_AUTO
    params = llama_init(jax.random.PRNGKey(0), cfg_auto)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                cfg_auto.vocab_size)
    targets = jnp.roll(tokens, -1, 1)
    g_ref = jax.grad(llama_loss)(params, tokens, targets, cfg_auto)
    sharded = _composed_params(params, cp_mesh)
    g = jax.jit(jax.grad(lambda p, t, y: llama_loss_pipelined(
        p, t, y, cfg_auto, cp_mesh, n_microbatches=2)))(
        sharded, tokens, targets)
    for k in ("wq", "wo", "w_down"):
        np.testing.assert_allclose(np.asarray(g["layers"][k]),
                                   np.asarray(g_ref["layers"][k]),
                                   rtol=5e-4, atol=5e-4)


def test_cp_pipeline_validation(cp_mesh, pipe_mesh):
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    # seq not divisible by context size
    cfg_auto = CFG_AUTO
    params = _composed_params(llama_init(jax.random.PRNGKey(0), cfg_auto),
                              cp_mesh)
    with pytest.raises(ValueError, match="seq_len"):
        llama_forward_pipelined(params, jnp.zeros((8, 15), jnp.int32),
                                cfg_auto, cp_mesh)
    # explicit ring without a live context axis
    ring = LlamaConfig.tiny(n_layers=4, attn_impl="ring",
                            dtype=jnp.float32, remat=False)
    params4 = _sharded_params(llama_init(jax.random.PRNGKey(0), ring),
                              pipe_mesh)
    with pytest.raises(ValueError, match="context"):
        llama_forward_pipelined(params4, jnp.zeros((8, 16), jnp.int32),
                                ring, pipe_mesh)


def test_ulysses_inside_pipeline_matches_sequential(cpu_mesh_devices):
    """data×cp×pipe with attn_impl='ulysses': the stage body head-scatters
    via all-to-all instead of the ring."""
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    mesh = build_mesh(MeshSpec(data=2, context=2, pipe=2),
                      devices=jax.devices()[:8])
    cfg_u = LlamaConfig.tiny(n_layers=4, attn_impl="ulysses",
                             dtype=jnp.float32, remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg_u)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg_u.vocab_size)
    ref = llama_forward(params, tokens, CFG)
    sharded = _composed_params(params, mesh)
    out = jax.jit(lambda p, t: llama_forward_pipelined(
        p, t, cfg_u, mesh, n_microbatches=2))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_pipeline_tp_head_guard(cp_mesh):
    """tp shrinks local head counts below the ulysses degree → clear error."""
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    cfg_u = LlamaConfig.tiny(n_layers=4, attn_impl="ulysses",
                             dtype=jnp.float32, remat=False)
    params = _composed_params(llama_init(jax.random.PRNGKey(0), cfg_u),
                              cp_mesh)
    with pytest.raises(ValueError, match="ulysses"):
        llama_forward_pipelined(params, jnp.zeros((8, 16), jnp.int32),
                                cfg_u, cp_mesh)


def test_composed_tp_divisibility_validated(composed_mesh):
    from kubetorch_tpu.parallel.pipeline import llama_forward_pipelined

    # n_kv_heads=1 not divisible by tensor=2
    bad = LlamaConfig.tiny(n_layers=4, n_heads=2, n_kv_heads=1,
                           attn_impl="xla", dtype=jnp.float32, remat=False)
    params = _composed_params(llama_init(jax.random.PRNGKey(0), bad),
                              composed_mesh)
    with pytest.raises(ValueError, match="tensor"):
        llama_forward_pipelined(params, jnp.zeros((8, 16), jnp.int32), bad,
                                composed_mesh)


# ---------------------------------------------------------------------------
# MoE: expert parallelism inside pipeline stages
# ---------------------------------------------------------------------------


def _moe_cfg():
    from kubetorch_tpu.models.moe import MoeConfig

    return MoeConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False,
                          n_layers=4, n_experts=4)


def test_moe_pipeline_logits_match_sequential(cpu_mesh_devices):
    """ep×pipe×tp: local-expert slice + psum combine reproduces the GSPMD
    forward exactly (aux differs at O(1/M) — documented microbatch mean)."""
    from kubetorch_tpu.models.moe import moe_forward, moe_init
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.parallel.pipeline import (moe_forward_pipelined,
                                                 moe_pipeline_shardings)

    cfg = _moe_cfg()
    mesh = build_mesh(MeshSpec(expert=2, pipe=2, tensor=2),
                      devices=jax.devices()[:8])
    params = moe_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    ref_logits, ref_aux = moe_forward(params, tokens, cfg)
    sharded = jax.tree_util.tree_map(
        jax.device_put, params, moe_pipeline_shardings(params, mesh))
    # expert weights actually sharded: (L/pipe, E/ep, D, F/tp)
    assert sharded["layers"]["experts"]["w_gate"].addressable_shards[0] \
        .data.shape == (2, 2, cfg.dim, cfg.ffn_dim // 2)
    logits, aux = jax.jit(lambda p, t: moe_forward_pipelined(
        p, t, cfg, mesh, n_microbatches=2))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=3e-4, atol=3e-4)
    assert np.isfinite(float(aux)) and 0.2 < float(aux) < 5.0


def test_moe_pipeline_grads_match_with_expert_axis(cpu_mesh_devices):
    """Grads through the in-stage expert slice + psum (the manual-EP
    backward: slice transpose scatters, psum transposes to identity)."""
    from kubetorch_tpu.models.moe import moe_init, moe_loss
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.parallel.pipeline import (moe_loss_pipelined,
                                                 moe_pipeline_shardings)

    cfg = _moe_cfg()
    mesh = build_mesh(MeshSpec(expert=2, pipe=2, tensor=2),
                      devices=jax.devices()[:8])
    params = moe_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 16), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, 1)
    g_ref = jax.grad(moe_loss)(params, tokens, targets, cfg)
    sharded = jax.tree_util.tree_map(
        jax.device_put, params, moe_pipeline_shardings(params, mesh))
    g = jax.jit(jax.grad(lambda p, t, y: moe_loss_pipelined(
        p, t, y, cfg, mesh, n_microbatches=2)))(sharded, tokens, targets)
    for leaf in ("w_gate", "w_down"):
        np.testing.assert_allclose(
            np.asarray(g["layers"]["experts"][leaf]),
            np.asarray(g_ref["layers"]["experts"][leaf]),
            rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(g["layers"]["router"]),
                               np.asarray(g_ref["layers"]["router"]),
                               rtol=2e-3, atol=2e-3)


def test_moe_pipeline_grads_match(cpu_mesh_devices):
    from kubetorch_tpu.models.moe import moe_init, moe_loss
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.parallel.pipeline import (moe_loss_pipelined,
                                                 moe_pipeline_shardings)

    cfg = _moe_cfg()
    mesh = build_mesh(MeshSpec(data=2, fsdp=2, pipe=2),
                      devices=jax.devices()[:8])
    params = moe_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, 1)
    g_ref = jax.grad(moe_loss)(params, tokens, targets, cfg)
    sharded = jax.tree_util.tree_map(
        jax.device_put, params, moe_pipeline_shardings(params, mesh))
    g = jax.jit(jax.grad(lambda p, t, y: moe_loss_pipelined(
        p, t, y, cfg, mesh, n_microbatches=2)))(sharded, tokens, targets)
    for k in ("wq", "wo"):
        np.testing.assert_allclose(np.asarray(g["layers"][k]),
                                   np.asarray(g_ref["layers"][k]),
                                   rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(g["layers"]["experts"]["w_down"]),
        np.asarray(g_ref["layers"]["experts"]["w_down"]),
        rtol=2e-3, atol=2e-3)


def test_moe_pipeline_expert_divisibility(cpu_mesh_devices):
    from kubetorch_tpu.models.moe import MoeConfig, moe_init
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.parallel.pipeline import (moe_forward_pipelined,
                                                 moe_pipeline_shardings)

    cfg = MoeConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False,
                         n_layers=4, n_experts=3)
    mesh = build_mesh(MeshSpec(expert=2, pipe=2, data=2),
                      devices=jax.devices()[:8])
    params = moe_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="expert"):
        moe_forward_pipelined(params, jnp.zeros((8, 16), jnp.int32), cfg,
                              mesh)
    # MoE × context inside a stage: guarded (chunk-local routing diverges)
    cfg4 = MoeConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False,
                          n_layers=4, n_experts=4)
    cp_mesh = build_mesh(MeshSpec(context=2, pipe=2, expert=2),
                         devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="context"):
        moe_forward_pipelined(moe_init(jax.random.PRNGKey(0), cfg4),
                              jnp.zeros((8, 16), jnp.int32), cfg4, cp_mesh)


# ---------------------------------------------------------------------------
# Interleaved (virtual-stage) schedule
# ---------------------------------------------------------------------------


def test_interleaved_pipeline_matches_sequential(composed_mesh):
    """V=2 virtual stages on data×pipe×tp: strided chunk layout + double
    ring loop reproduces the sequential forward and grads."""
    from kubetorch_tpu.models.llama import llama_loss
    from kubetorch_tpu.parallel.pipeline import (llama_forward_pipelined,
                                                 llama_loss_pipelined,
                                                 llama_pipeline_place)

    cfg = LlamaConfig.tiny(n_layers=8, attn_impl="xla", dtype=jnp.float32,
                           remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    ref = llama_forward(params, tokens, cfg)
    placed = llama_pipeline_place(params, cfg_mesh := composed_mesh,
                                  n_virtual=2)
    # strided layout: (V, P-sharded, lpc, ...) per leaf
    assert placed["layers"]["wq"].shape[:3] == (2, 2, 2)
    out = jax.jit(lambda p, t: llama_forward_pipelined(
        p, t, cfg, cfg_mesh, n_microbatches=4, n_virtual=2))(placed, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)

    targets = jnp.roll(tokens, -1, 1)
    g_ref = jax.grad(llama_loss)(params, tokens, targets, cfg)
    g = jax.jit(jax.grad(lambda p, t, y: llama_loss_pipelined(
        p, t, y, cfg, cfg_mesh, n_microbatches=4, n_virtual=2)))(
        placed, tokens, targets)
    gw = np.asarray(g["layers"]["wq"])
    # undo (V, P, lpc): global layer l = (v*P + p)*lpc + i
    recon = np.concatenate([gw[v, p] for v in range(2) for p in range(2)],
                           axis=0)
    np.testing.assert_allclose(recon, np.asarray(g_ref["layers"]["wq"]),
                               rtol=5e-4, atol=5e-4)


def test_interleaved_validation(composed_mesh):
    from kubetorch_tpu.parallel.pipeline import (llama_forward_pipelined,
                                                 llama_pipeline_place)

    cfg = LlamaConfig.tiny(n_layers=8, attn_impl="xla", dtype=jnp.float32,
                           remat=False)
    placed = llama_pipeline_place(llama_init(jax.random.PRNGKey(0), cfg),
                                  composed_mesh, n_virtual=2)
    # microbatches must advance in blocks of P (batch sized so the generic
    # batch-divisibility check passes and the schedule check is reached)
    with pytest.raises(ValueError, match="divisible by pipe"):
        llama_forward_pipelined(placed, jnp.zeros((12, 16), jnp.int32), cfg,
                                composed_mesh, n_microbatches=3, n_virtual=2)
    tokens = jnp.zeros((8, 16), jnp.int32)
    # layer count must divide pipe × virtual
    bad = LlamaConfig.tiny(n_layers=6, attn_impl="xla", dtype=jnp.float32,
                           remat=False)
    with pytest.raises(ValueError, match="virtual"):
        llama_forward_pipelined(placed, tokens, bad, composed_mesh,
                                n_microbatches=4, n_virtual=2)


def test_moe_interleaved_matches_sequential(cpu_mesh_devices):
    """MoE + interleaved virtual stages: ep×pipe×tp with V=2 chunk layout
    reproduces the sequential logits; aux flows through the interleaved
    bubble mask."""
    from kubetorch_tpu.models.moe import MoeConfig, moe_forward, moe_init
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.parallel.pipeline import (moe_forward_pipelined,
                                                 moe_pipeline_place)

    cfg = MoeConfig.tiny(attn_impl="xla", dtype=jnp.float32, remat=False,
                         n_layers=8, n_experts=4)
    mesh = build_mesh(MeshSpec(expert=2, pipe=2, tensor=2),
                      devices=jax.devices()[:8])
    params = moe_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    ref, _ = moe_forward(params, tokens, cfg)
    placed = moe_pipeline_place(params, mesh, n_virtual=2)
    logits, aux = jax.jit(lambda p, t: moe_forward_pipelined(
        p, t, cfg, mesh, n_microbatches=4, n_virtual=2))(placed, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=4e-4, atol=4e-4)
    assert np.isfinite(float(aux)) and 0.2 < float(aux) < 5.0


def test_moe_context_chunked_routing(cpu_mesh_devices):
    """cp×ep×pipe MoE: with the context_chunked_routing opt-in the stage
    runs ring attention + per-chunk routing; at no-overflow capacity the
    chunk-local router is exactly the full-sequence router."""
    from kubetorch_tpu.models.moe import MoeConfig, moe_forward, moe_init
    from kubetorch_tpu.parallel.mesh import MeshSpec, build_mesh
    from kubetorch_tpu.parallel.pipeline import (moe_forward_pipelined,
                                                 moe_pipeline_place)

    kw = dict(attn_impl="xla", dtype=jnp.float32, remat=False, n_layers=4,
              n_experts=4, capacity_factor=4.0)
    cfg = MoeConfig.tiny(context_chunked_routing=True, **kw)
    mesh = build_mesh(MeshSpec(context=2, expert=2, pipe=2),
                      devices=jax.devices()[:8])
    params = moe_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    ref, _ = moe_forward(params, tokens, MoeConfig.tiny(**kw))
    placed = moe_pipeline_place(params, mesh)
    logits, aux = jax.jit(lambda p, t: moe_forward_pipelined(
        p, t, cfg, mesh, n_microbatches=2))(placed, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=4e-4, atol=4e-4)
    assert np.isfinite(float(aux))

    # without the opt-in: clear error
    with pytest.raises(ValueError, match="context_chunked_routing"):
        moe_forward_pipelined(placed, tokens, MoeConfig.tiny(**kw), mesh,
                              n_microbatches=2)


def test_train_step_with_pipeline_and_accumulation(zero3_mesh):
    """The whole training stack composes: make_train_step drives the
    pipelined loss on a ZeRO-3 pipe mesh with gradient accumulation, state
    sharded by PIPE_LLAMA_RULES, and the loss moves."""
    import optax

    from kubetorch_tpu.parallel.pipeline import (PIPE_LLAMA_RULES,
                                                 llama_loss_pipelined)
    from kubetorch_tpu.train import init_train_state, make_train_step

    cfg = CFG
    opt = optax.adam(1e-2)
    step = make_train_step(
        lambda p, t, y: llama_loss_pipelined(p, t, y, cfg, zero3_mesh,
                                             n_microbatches=2),
        optimizer=opt, mesh=zero3_mesh, rules=PIPE_LLAMA_RULES,
        accum_steps=2)
    state = step.shard_state(
        init_train_state(llama_init(jax.random.PRNGKey(0), cfg), opt))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": jax.device_put(tokens, step.batch_sharding),
             "targets": jax.device_put(jnp.roll(tokens, -1, 1),
                                       step.batch_sharding)}
    state, m1 = step(state, batch)
    state, m2 = step(state, batch)
    assert float(m2["loss"]) < float(m1["loss"])
    # params stayed in the rule-table layout (no silent reshuffle)
    assert state.params["layers"]["wq"].sharding.spec == \
        jax.sharding.PartitionSpec("pipe", "fsdp", "tensor")

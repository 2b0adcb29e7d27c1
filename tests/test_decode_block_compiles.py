"""Compile gate for the decode block (ROADMAP S2): ``_decode_block`` lowered
for ONE described TPU v5e device at the benchmark cells' widths must read
and write the KV grid where it lies.

Nothing runs and no chip is needed: the TPU's compiler is installed here and
compiles for a chip that is described, not attached (``on-chip-measurement``
§2). What is asserted, on ``compiled.as_text()``:

- no ``copy``, ``transpose``, ``dynamic-slice`` or ``scatter`` (bare or
  inside a fusion) whose result is a layer of the cache or the whole grid;
- the only producers of a grid-shaped array are in-place updates
  (``dynamic-update-slice``);
- ``kt_decode_attention`` is a ``tpu_custom_call`` whose K and V operands
  are the two whole grids;
- both grids are input/output-aliased (the block donates them).

The topology is described inside a fixture, and skipped from there where it
cannot be: nothing touches the TPU library while a module is imported.
"""

import re

import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.level("unit")

# both cells' engine: 16 slots × 2,048 rows, 8 KV heads of 128, block of 8
SLOTS, S_MAX, NKV, HD, BLOCK, LAYERS = 16, 2048, 8, 128, 8, 2
WIDTHS = dict(vocab_size=32768, dim=4096, n_layers=LAYERS, n_heads=32,
              n_kv_heads=NKV, ffn_dim=14336, max_seq_len=S_MAX,
              rope_theta=1e6, norm_eps=1e-5)
LAYER_DIMS = sorted((SLOTS, NKV, S_MAX, HD))
GRID_DIMS = sorted((LAYERS, SLOTS, NKV, S_MAX, HD))

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\((.*)")


def _instructions(text):
    """(name, dims without 1s sorted, opcode, rest of line) of every
    instruction with one array result, fused computations' bodies too."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            dims = sorted(int(d) for d in m.group(3).split(",")
                          if d and d != "1")
            yield m.group(1), dims, m.group(4), m.group(5)


def grid_traffic(text):
    """The instructions that move a layer of the cache or the whole grid:
    what the gate forbids. Kept apart from the test so that a builder can
    run it over another program's text (the parent's)."""
    moving = ("copy", "copy-start", "transpose", "dynamic-slice", "scatter",
              "gather", "slice", "concatenate", "pad", "select", "broadcast")
    passing = ("parameter", "get-tuple-element", "bitcast",
               "dynamic-update-slice")
    bad = []
    for name, dims, op, _ in _instructions(text):
        if dims not in (LAYER_DIMS, GRID_DIMS):
            continue
        if op in moving or (dims == GRID_DIMS and op not in passing):
            bad.append(f"{op} {name} {dims}")
    return bad


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_block(one_chip, monkeypatch):
    """``_decode_block`` compiled for the described chip, as text. The CPU
    process is steered onto the chip's branch here, not by an option of the
    program: the kernel path, compiled by Mosaic."""
    from kubetorch_tpu.ops import decode_attention as kernel_mod
    from kubetorch_tpu.serve import engine as E
    monkeypatch.setattr(E, "_decode_kernel_wanted", lambda: True)
    monkeypatch.setattr(kernel_mod, "interpret_default", lambda: False)
    # an entry compiled for a described chip cannot be read back without one
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(cfg, init):
        params = shaped(jax.eval_shape(
            lambda: init(jax.random.PRNGKey(0), cfg)))
        cache = shaped(jax.eval_shape(
            lambda: E.init_grid_cache(cfg, SLOTS, S_MAX)))
        # the engine's common decode signature (aot_cache.warm_engine)
        return E._decode_block.lower(
            params, cache, arg((SLOTS,), jnp.int32), arg((SLOTS,), jnp.int32),
            arg((2,), jnp.uint32), arg((SLOTS,), jnp.float32), cfg,
            n_steps=BLOCK, skeys=arg((SLOTS, 2), jnp.uint32),
        ).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", cached)


def _configs():
    from kubetorch_tpu.models.llama import LlamaConfig, llama_init
    from kubetorch_tpu.models.moe import MoeConfig, moe_init
    return {"mistral-7b": (LlamaConfig(**WIDTHS), llama_init),
            "mixtral-8x7b": (MoeConfig(n_experts=8, experts_per_token=2,
                                       capacity_factor=1.25, **WIDTHS),
                             moe_init)}


@pytest.mark.parametrize("model", ["mistral-7b", "mixtral-8x7b"])
def test_decode_block_leaves_the_grid_where_it_lies(compile_block, model):
    text = compile_block(*_configs()[model])
    by_name = {name: (dims, op) for name, dims, op, _ in _instructions(text)}

    assert grid_traffic(text) == []

    updates = [n for n, (dims, op) in by_name.items()
               if dims == GRID_DIMS and op == "dynamic-update-slice"]
    # K and V: one row per slot per layer visit, written in place
    assert len(updates) >= 2 * SLOTS, updates

    # the kernel is Mosaic's, and reads the two whole grids
    calls = [(name, rest) for name, _, op, rest in _instructions(text)
             if op == "custom-call" and name.startswith("kt_decode_attention")]
    assert len(calls) == 1, [c[0] for c in calls]
    name, rest = calls[0]
    assert 'custom_call_target="tpu_custom_call"' in rest
    operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
    grids = [o for o in operands if by_name.get(o, ([], ""))[0] == GRID_DIMS]
    assert len(grids) == 2, operands
    assert all(by_name[o][1] == "dynamic-update-slice" for o in grids), grids

    # both grids donated: each output grid aliases its parameter
    assert _aliased_cache_params(text, 2)


def _aliased_cache_params(text, n):
    """The ``n`` cache parameters of the program, each aliased to an output
    (the block donates them)."""
    header = text.split("\n", 1)[0]
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}", header.split("input_output_alias=")[1]
        .split("entry_computation_layout")[0])}
    cache_params = {int(re.match(r"(\d+)\)", rest).group(1))
                    for name, dims, op, rest in _instructions(text)
                    if op == "parameter" and name.startswith("cache_")}
    assert len(cache_params) == n and cache_params <= aliased, (
        cache_params, aliased)
    return True


def test_latent_decode_block_writes_its_rows_in_place(one_chip, compile_block):
    """The third configuration (``kimi-vl-a3b-l9``: latent rows of 576, a
    dense layer before the expert layers, 64 experts of 1,408) at the cell's
    widths: the latent grid is updated in place and aliased, nothing copies,
    transposes or scatters a layer of it or the whole of it, and the expert
    layers are ONE scan body (three products over the banks, not three a
    layer) that reads a layer's banks where they lie."""
    from kubetorch_tpu.models.mla import MlaMoeConfig, mla_moe_init
    from kubetorch_tpu.serve import engine as E
    layers = 4                                       # 1 dense + 3 expert
    cfg = MlaMoeConfig(n_layers=layers, max_seq_len=S_MAX)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(
        lambda: mla_moe_init(jax.random.PRNGKey(0), cfg)))
    cache = shaped(jax.eval_shape(
        lambda: E._cache_ops(cfg).init_grid(cfg, SLOTS, S_MAX)))
    text = E._decode_block.lower(
        params, cache, arg((SLOTS,), jnp.int32), arg((SLOTS,), jnp.int32),
        arg((2,), jnp.uint32), arg((SLOTS,), jnp.float32), cfg,
        n_steps=BLOCK, skeys=arg((SLOTS, 2), jnp.uint32),
        tally=arg(cfg.routed_tally_shape, jnp.int32),
        live=arg((SLOTS,), jnp.bool_)).compile().as_text()

    layer_dims = sorted((SLOTS, S_MAX, cfg.latent_dim))
    grid_dims = sorted((layers, SLOTS, S_MAX, cfg.latent_dim))
    moving = ("copy", "copy-start", "transpose", "scatter", "gather",
              "concatenate", "pad", "select", "broadcast")
    passing = ("parameter", "get-tuple-element", "bitcast",
               "dynamic-update-slice")
    bad = [f"{op} {name}" for name, dims, op, _ in _instructions(text)
           if (dims in (layer_dims, grid_dims) and op in moving)
           or (dims == grid_dims and op not in passing)]
    assert bad == []
    updates = [n for n, dims, op, _ in _instructions(text)
               if dims == grid_dims and op == "dynamic-update-slice"]
    assert len(updates) >= SLOTS, updates        # a row a slot, in place
    assert _aliased_cache_params(text, 1)
    # sixteen rows go through every expert as plain products (gate, up and
    # down over (E, 16, ·)): one scan body, so three of them and not three a
    # layer; the grouped kernel is a prompt's, and no bank is copied for it
    products = [n for n, dims, op, _ in _instructions(text)
                if op == "convolution" and dims in (
                    sorted((cfg.n_experts, SLOTS, cfg.moe_ffn_dim)),
                    sorted((cfg.n_experts, SLOTS, cfg.dim)))]
    assert len(products) == 3, products
    assert "ragged-dot" not in text
    bank = sorted((cfg.n_experts, cfg.dim, cfg.moe_ffn_dim))
    assert [n for n, dims, op, _ in _instructions(text)
            if dims == bank and op in moving] == []

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

For the latent configuration (``kimi-vl-a3b-l9``), its decode block and its
prefills of the buckets 256 and 512: the expert layers' scan
body holds ONE ``kt_moe_experts`` call, whose bank operands are the whole
stacks, and nothing makes an array the size of an expert, a layer's bank or
the stack (ISSUE 34). And the census: the dense, Mixtral and Kimi-VL shaped
decode blocks and their bucket-256 (Kimi-VL: and 512) prefills compile to the
multiset of (opcode, result type)
kept in ``tests/assets/compile_census.json``, which a PR that does not mean
to touch them leaves as it is; a PR that does makes the file again with
``JAX_PLATFORMS=cpu python -m tests.test_decode_block_compiles`` from the
root of the repo.

The topology is described inside a fixture, and skipped from there where it
cannot be: nothing touches the TPU library while a module is imported.
"""

import collections
import contextlib
import hashlib
import json
import os
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


def described_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def one_chip():
    try:
        return described_chip()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


class _ChipPrograms:
    """The engine's programs compiled for the described chip, as text, each
    compiled once a module."""

    def __init__(self, one_chip):
        self.one_chip = one_chip
        self.texts = {}

    def shaped(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=self.one_chip), tree)

    def arg(self, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self.one_chip)

    def params(self, cfg, init):
        return self.shaped(jax.eval_shape(
            lambda: init(jax.random.PRNGKey(0), cfg)))

    def decode(self, cfg, init, s_max=S_MAX):
        """``_decode_block``, the engine's common decode signature
        (``aot_cache.warm_engine``; a latent cache's has the routing tally
        and the live mask beside it)."""
        from kubetorch_tpu.serve import engine as E
        if ("decode", cfg) not in self.texts:
            arg = self.arg
            cache = self.shaped(jax.eval_shape(
                lambda: E._cache_ops(cfg).init_grid(cfg, SLOTS, s_max)))
            extra = {}
            if E._tally_shapes(cfg):
                extra = dict(tally=self.shaped(jax.eval_shape(
                    lambda: E._init_tally(cfg))),
                    live=arg((SLOTS,), jnp.bool_))
            self.texts["decode", cfg] = E._decode_block.lower(
                self.params(cfg, init), cache, arg((SLOTS,), jnp.int32),
                arg((SLOTS,), jnp.int32), arg((2,), jnp.uint32),
                arg((SLOTS,), jnp.float32), cfg, n_steps=BLOCK,
                skeys=arg((SLOTS, 2), jnp.uint32), **extra,
            ).compile().as_text()
        return self.texts["decode", cfg]

    def prefill(self, cfg, init, bucket):
        """``_prefill`` of one prompt padded to ``bucket``."""
        from kubetorch_tpu.serve import engine as E
        if ("prefill", cfg, bucket) not in self.texts:
            arg = self.arg
            self.texts["prefill", cfg, bucket] = E._prefill.lower(
                self.params(cfg, init), arg((1, bucket), jnp.int32),
                arg((), jnp.int32), arg((2,), jnp.uint32),
                arg((1,), jnp.float32), cfg).compile().as_text()
        return self.texts["prefill", cfg, bucket]


@contextlib.contextmanager
def steered_onto_the_chip(one_chip):
    """The CPU process steered onto the chip's branch here, not by an option
    of the program: the kernel paths, compiled by Mosaic."""
    from kubetorch_tpu.models import generate, mla
    from kubetorch_tpu.ops import attention, decode_attention, moe_experts
    from kubetorch_tpu.serve import engine as E
    # an entry compiled for a described chip cannot be read back without one
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(E, "_decode_kernel_wanted", lambda: True)
        mp.setattr(mla, "moe_experts_auto", moe_experts.moe_experts_supported)
        mp.setattr(generate, "_FLASH_PREFILL_FLAG", "1")
        for kernel_mod in (attention, decode_attention, moe_experts):
            mp.setattr(kernel_mod, "interpret_default", lambda: False)
        yield _ChipPrograms(one_chip)
    jax.config.update("jax_enable_compilation_cache", cached)


@pytest.fixture(scope="module")
def chip_programs(one_chip):
    with steered_onto_the_chip(one_chip) as programs:
        yield programs


@pytest.fixture
def compile_block(chip_programs):
    return chip_programs.decode


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


KIMI_LAYERS = 4                                      # 1 dense + 3 expert


def _kimi():
    from kubetorch_tpu.models.mla import MlaMoeConfig, mla_moe_init
    return MlaMoeConfig(n_layers=KIMI_LAYERS, max_seq_len=S_MAX), mla_moe_init


def test_latent_decode_block_writes_its_rows_in_place(compile_block):
    """The third configuration (``kimi-vl-a3b-l9``: latent rows of 576, a
    dense layer before the expert layers, 64 experts of 1,408) at the cell's
    widths: the latent grid is updated in place and aliased, and nothing
    copies, transposes or scatters a layer of it or the whole of it."""
    cfg, init = _kimi()
    text = compile_block(cfg, init)

    layer_dims = sorted((SLOTS, S_MAX, cfg.latent_dim))
    grid_dims = sorted((KIMI_LAYERS, SLOTS, S_MAX, cfg.latent_dim))
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


@pytest.mark.parametrize("program", ["decode_block", "prefill_256",
                                     "prefill_512"])
def test_latent_programs_stream_their_banks_through_one_kernel_call(
        chip_programs, program):
    """A decode step's sixteen rows and a short prompt's bucket take the
    grouped kernel (``ops/moe_experts.py``): the expert layers are ONE scan
    body, so one ``kt_moe_experts`` call and not one a layer, Mosaic's, whose
    bank operands are the three whole stacks as the program was given them;
    no einsum over every bank and no grouped matmul is left, and nothing but
    a parameter passed on has the size of an expert, of a layer's bank or of
    the stack (PR 33's first version lost 23 ms a step to a
    ``dynamic-slice_bitcast_fusion`` of a layer's bank)."""
    cfg, init = _kimi()
    rows = SLOTS if program == "decode_block" else int(program[-3:])
    text = (chip_programs.decode(cfg, init) if program == "decode_block"
            else chip_programs.prefill(cfg, init, rows))
    by_name = {name: (dims, op) for name, dims, op, _ in _instructions(text)}
    E_, D, F = cfg.n_experts, cfg.dim, cfg.moe_ffn_dim

    calls = [(name, rest) for name, _, op, rest in _instructions(text)
             if op == "custom-call" and "kt_moe_experts" in name]
    assert len(calls) == 1, [c[0] for c in calls]
    name, rest = calls[0]
    assert 'custom_call_target="tpu_custom_call"' in rest
    stack = sorted((cfg.n_moe_layers, E_, D, F))
    operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
    stacks = [o for o in operands if by_name.get(o, ([], ""))[0] == stack]
    assert len(stacks) == 3, operands
    assert all(by_name[o][1] in ("parameter", "get-tuple-element")
               for o in stacks), [by_name[o] for o in stacks]

    sized = (sorted((D, F)), sorted((E_, D, F)), stack)
    passing = ("parameter", "get-tuple-element", "bitcast")
    assert [f"{op} {n} {dims}" for n, dims, op, _ in _instructions(text)
            if dims in sized and op not in passing] == []
    assert "ragged-dot" not in text
    assert [n for n, dims, op, _ in _instructions(text)
            if op == "convolution" and dims in (
                sorted((E_, rows, F)), sorted((E_, rows, D)))] == []


GLM_S_MAX, GLM_LAYERS = 9728, 3                      # 1 dense + 2 expert


def _glm5():
    """``glm-5-ep16-l6``'s widths, three layers of its six."""
    from kubetorch_tpu.models.mla import MlaMoeConfig, mla_moe_init
    return MlaMoeConfig(
        vocab_size=19360, dim=6144, n_layers=GLM_LAYERS, n_heads=64,
        kv_lora_rank=512, q_lora_rank=2048, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, ffn_dim=12288, moe_ffn_dim=2048,
        n_experts=256, held=(0, 16), experts_per_token=8, n_shared_experts=1,
        routed_scaling_factor=2.5, max_seq_len=GLM_S_MAX, rope_theta=1e6,
        index_n_heads=32, index_head_dim=128, index_topk=2048), mla_moe_init


def test_sparse_latent_decode_block_gathers_from_the_grid_where_it_lies(
        chip_programs):
    """The fourth configuration (``glm-5-ep16-l6``) at the cell's widths,
    16 slots of 9,728 rows: both cache leaves are updated in place and
    aliased; the selected rows are gathered from the stacked latent grid
    itself (no layer of it is sliced or copied on the way); the held experts
    stream through ONE ``kt_moe_experts`` call, whose F-tile fits the VMEM,
    with the three whole stacks as operands and no bank-sized array made.
    What it does NOT hold (PERF.md section 7): XLA keeps the latent grid
    row-minor between blocks and relays it for the gather once on the way
    into a block and once on the way out, and it materialises the layer's
    slice of the index keys that the scoring pass reads."""
    cfg, init = _glm5()
    text = chip_programs.decode(cfg, init, GLM_S_MAX)
    by_name = {name: (dims, op) for name, dims, op, _ in _instructions(text)}
    rows = sorted((GLM_LAYERS, SLOTS, GLM_S_MAX, cfg.latent_dim))
    keys = sorted((GLM_LAYERS, SLOTS, GLM_S_MAX, cfg.index_head_dim))
    for grid in (rows, keys):
        updates = [n for n, (dims, op) in by_name.items()
                   if dims == grid and op == "dynamic-update-slice"]
        assert len(updates) >= SLOTS, (grid, updates)
    assert _aliased_cache_params(text, 2)

    # the gather: (slots x index_topk, latent_dim) rows out of the grid
    picked = sorted((SLOTS * cfg.index_topk, cfg.latent_dim))
    gathers = [(n, rest) for n, dims, op, rest in _instructions(text)
               if dims == picked and op in ("fusion", "gather")
               and "kt.dsa.gather" in rest]
    assert gathers, "no gather of the selected rows"
    for name, rest in gathers:
        operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
        assert any(by_name.get(o, ([], ""))[0] == rows for o in operands), (
            name, operands)
    a_layer = sorted((SLOTS, GLM_S_MAX, cfg.latent_dim))
    assert [f"{op} {n}" for n, dims, op, _ in _instructions(text)
            if dims == a_layer] == []

    # the held experts: one kernel call over the whole stacks, F tiled
    from kubetorch_tpu.ops.moe_experts import f_tile
    assert f_tile(cfg.dim, cfg.moe_ffn_dim, 2) == 1024
    calls = [(name, rest) for name, _, op, rest in _instructions(text)
             if op == "custom-call" and "kt_moe_experts" in name]
    assert len(calls) == 1, [c[0] for c in calls]
    stack = sorted((cfg.n_moe_layers, cfg.n_held, cfg.dim, cfg.moe_ffn_dim))
    operands = re.findall(r"%([\w.\-]+)", calls[0][1].split(")")[0])
    assert len([o for o in operands
                if by_name.get(o, ([], ""))[0] == stack]) == 3, operands
    sized = (sorted((cfg.n_held, cfg.dim, cfg.moe_ffn_dim)), stack)
    passing = ("parameter", "get-tuple-element", "bitcast")
    assert [f"{op} {n} {dims}" for n, dims, op, _ in _instructions(text)
            if dims in sized and op not in passing] == []


# -- the census of the older cells' programs -----------------------------------

CENSUS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "assets", "compile_census.json")
_ANY = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|[a-z0-9]+\[[^\]]*\])"
                  r"\S* ([\w\-]+)\(")


def census(text) -> dict:
    """{"opcode result-type": count} over every instruction of a compiled
    program, fused computations' bodies too; layouts left out, a tuple type
    as a digest of itself."""
    out = collections.Counter()
    for line in text.splitlines():
        m = _ANY.match(line)
        if m:
            kind = re.sub(r"\{[^}]*\}", "", m.group(1))
            if kind.startswith("("):
                kind = "tuple:" + hashlib.sha1(kind.encode()).hexdigest()[:12]
            out[f"{m.group(2)} {kind}"] += 1
    return dict(sorted(out.items()))


def older_cells_programs(programs, only=None) -> dict:
    """{name: text}: the decode block and the bucket-256 prefill of the dense
    and the Mixtral configuration at the cells' widths, and of the Kimi-VL
    shaped one with its bucket-512 prefill (``only``: one)."""
    out = {}
    for model, (cfg, init) in {**_configs(), "kimi-vl-a3b": _kimi()}.items():
        for which, compile_it in (
                ("decode_block", lambda: programs.decode(cfg, init)),
                ("prefill_256", lambda: programs.prefill(cfg, init, 256)),
                ("prefill_512", lambda: programs.prefill(cfg, init, 512))):
            if only in (None, f"{model}.{which}") and (
                    which != "prefill_512" or model == "kimi-vl-a3b"):
                out[f"{model}.{which}"] = compile_it()
    return out


@pytest.mark.parametrize("program", [
    "mistral-7b.decode_block", "mistral-7b.prefill_256",
    "mixtral-8x7b.decode_block", "mixtral-8x7b.prefill_256",
    "kimi-vl-a3b.decode_block", "kimi-vl-a3b.prefill_256",
    "kimi-vl-a3b.prefill_512"])
def test_the_older_cells_programs_keep_their_census(chip_programs, program):
    """A change to the latent family (a query rank, an indexer, a share of
    the experts: each a path of its own config) leaves the accepted cells'
    device programs as they were: the same instructions making the same
    arrays, as counted at PR 33's commit for the two older cells and at PR
    34's for the Kimi-VL shaped one."""
    with open(CENSUS_FILE) as f:
        want = json.load(f)[program]
    got = census(older_cells_programs(chip_programs, only=program)[program])
    moved = {k: (want.get(k, 0), got.get(k, 0))
             for k in set(want) | set(got) if want.get(k, 0) != got.get(k, 0)}
    assert moved == {}, f"(kept, compiled) counts that differ: {moved}"


if __name__ == "__main__":
    # the census written anew, for a PR that means to change those programs
    with steered_onto_the_chip(described_chip()) as chip:
        kept = {name: census(text)
                for name, text in older_cells_programs(chip).items()}
    with open(CENSUS_FILE, "w") as out_file:
        json.dump(kept, out_file, indent=0)
    print({name: sum(counts.values()) for name, counts in kept.items()})

"""Pipeline parallelism: GPipe over a ``pipe`` mesh axis via shard_map.

TPU-first formulation: the model's layer-stacked params (every leaf is
``(L, ...)`` for ``lax.scan``) shard their **layer dimension** over the
``pipe`` axis — stage p holds layers ``[p·L/P, (p+1)·L/P)`` with no
re-packing. Activations flow stage→stage with ``lax.ppermute`` (one ICI hop
per microbatch per boundary); the GPipe schedule is a ``lax.scan`` over
``M + P - 1`` timesteps, so the whole pipeline is one compiled program —
no host round-trips between microbatches.

Differentiable end-to-end (scan + ppermute transpose cleanly), so the same
function trains; remat inside the stage body keeps bubble memory bounded.

Neither the reference nor torch launchers can express this: it exists here
because parallelism is a launcher-level concern on TPU (SURVEY §2.4).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import (AXIS_CONTEXT, AXIS_EXPERT, AXIS_FSDP, AXIS_PIPE,
                   AXIS_TENSOR, live_axes as _live_axes)
from .sharding import (BATCH_AXES as _BATCH_AXES, LLAMA_RULES, VIT_RULES,
                       ShardingRules)


def _reduce_stage_aux(aux_acc, mesh, axis):
    """Epilogue for the stage-aux channel (shared by both schedules): sum
    over stages (pipe), average over axes that see different data (batch
    shards, sequence shards); replicated axes (tensor/expert) compute
    identical aux already."""
    aux = lax.psum(aux_acc, axis)
    reduce_axes = tuple(a for a in (*_BATCH_AXES, AXIS_CONTEXT)
                        if a in _live_axes(mesh))
    if reduce_axes:
        aux = lax.pmean(aux, reduce_axes)
    return aux


def gpipe(stage_fn: Callable, mesh, *, axis: str = "pipe",
          n_microbatches: int, in_specs, params_specs, out_specs=None,
          stage_aux: bool = False):
    """Build a pipelined ``f(stage_params, x) -> y`` over ``mesh[axis]``.

    ``stage_fn(stage_params, x) -> y`` consumes one stage's params (the
    layer-dim shard) and one microbatch activation, both local. ``x`` is
    globally (M*mb, ...) — reshaped to microbatches internally. The result is
    replicated across the pipe axis.

    With ``stage_aux=True``, ``stage_fn`` returns ``(y, aux_scalar)`` and the
    pipelined function returns ``(y, aux_sum)``: the fp32 scalar summed over
    every REAL (stage, microbatch) execution — bubble ticks (a stage running
    garbage before/after its window) are masked out — then psummed over the
    pipe axis. Used for MoE router load-balancing losses.
    """
    from jax.sharding import PartitionSpec as P


    def pipelined(stage_params, x):
        M = n_microbatches

        def per_device(local_params, x_local):
            p = lax.axis_index(axis)
            n_stages = lax.axis_size(axis)
            xs = x_local.reshape(M, x_local.shape[0] // M, *x_local.shape[1:])

            def timestep(carry, t):
                recv, outputs, aux_acc = carry
                mb = t - p                       # my microbatch at this tick
                in_window = (mb >= 0) & (mb < M)
                # stage 0 pulls fresh input; later stages consume the wire
                fresh = lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
                inp = jnp.where(p == 0, fresh, recv)
                if stage_aux:
                    out, aux = stage_fn(local_params, inp)
                    # bubble ticks run garbage; only real executions count
                    aux_acc = aux_acc + jnp.where(
                        in_window, aux.astype(jnp.float32), 0.0)
                else:
                    out = stage_fn(local_params, inp)
                # rotate outputs one stage forward (ring; the wrap-around
                # value into stage 0 is ignored by the `where` above)
                send = lax.ppermute(
                    out, axis,
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
                # last stage records finished microbatch `mb` when valid
                valid = (p == n_stages - 1) & in_window
                idx = jnp.clip(mb, 0, M - 1)
                current = lax.dynamic_index_in_dim(outputs, idx, 0,
                                                   keepdims=False)
                outputs = lax.dynamic_update_index_in_dim(
                    outputs, jnp.where(valid, out, current), idx, 0)
                return (send, outputs, aux_acc), None

            init = (jnp.zeros_like(xs[0]),
                    jnp.zeros((M, *xs.shape[1:]), xs.dtype),
                    jnp.zeros((), jnp.float32))
            (_, outputs, aux_acc), _ = lax.scan(timestep, init,
                                                jnp.arange(M + n_stages - 1))
            # only the last stage holds real outputs; replicate via psum
            outputs = lax.psum(
                jnp.where(p == n_stages - 1, outputs,
                          jnp.zeros_like(outputs)), axis)
            outputs = outputs.reshape(x_local.shape)
            if stage_aux:
                return outputs, _reduce_stage_aux(aux_acc, mesh, axis)
            return outputs

        specs_out = out_specs if out_specs is not None else in_specs
        if stage_aux:
            specs_out = (specs_out, P())
        return jax.shard_map(per_device, mesh=mesh,
                    in_specs=(params_specs, in_specs),
                    # NOT `or`: an empty PartitionSpec (replicated) is falsy
                    out_specs=specs_out,
                    check_vma=False)(stage_params, x)

    return pipelined


def gpipe_interleaved(chunk_fn: Callable, mesh, *, axis: str = "pipe",
                      n_microbatches: int, n_virtual: int, in_specs,
                      params_specs, out_specs=None, stage_aux: bool = False):
    """Interleaved (virtual-stage) pipeline schedule over ``mesh[axis]``.

    Each device holds ``n_virtual`` layer CHUNKS instead of one contiguous
    stage — global chunk ``c`` lives on device ``c mod P`` (local param
    leaves carry a leading ``(V, 1, ...)`` chunk dim; the size-1 dim is the
    sharded pipe dim of the host-side ``(V, P, ...)`` layout) — and every
    activation loops the ring ``V`` times. Microbatches advance in blocks
    of ``P``: at shifted time ``s = t - p`` device ``p`` runs virtual chunk
    ``v = (s // P) mod V`` on microbatch ``(s // (P·V))·P + s % P``; the
    ring wrap-around from the last device back to device 0 legitimately
    carries loop ``v``'s output into loop ``v+1``. Total ticks =
    ``M·V + P - 1``, so the bubble is ``P - 1`` ticks of 1/V-sized chunks —
    V× smaller than GPipe at the same per-device layer count (Megatron's
    interleaved schedule, expressed as one ``lax.scan``).

    ``chunk_fn(chunk_params, x) -> y`` consumes ONE chunk's params (the V
    dim already indexed out) and one microbatch activation. Requires
    ``M % P == 0`` (microbatches advance in blocks of P). ``stage_aux``
    behaves as in :func:`gpipe` (per-chunk aux scalar, bubble-masked).
    """
    from jax.sharding import PartitionSpec as P

    P_size = _live_axes(mesh).get(axis, 1)
    if n_microbatches % P_size:
        raise ValueError(f"interleaved schedule needs microbatches="
                         f"{n_microbatches} divisible by pipe={P_size}")

    def pipelined(stage_params, x):
        M, V = n_microbatches, n_virtual
        ticks = M * V + P_size - 1

        def per_device(local_params, x_local):
            p = lax.axis_index(axis)
            n_stages = lax.axis_size(axis)
            xs = x_local.reshape(M, x_local.shape[0] // M, *x_local.shape[1:])
            # (V, 1, ...) local leaves → (V, ...): drop the sharded pipe dim
            chunks = jax.tree_util.tree_map(
                lambda a: a.reshape(a.shape[0], *a.shape[2:]), local_params)

            def timestep(carry, t):
                recv, outputs, aux_acc = carry
                s = t - p
                k = s // n_stages                  # = block·V + v
                v = k % V
                mb = (k // V) * n_stages + s % n_stages
                in_window = (s >= 0) & (s < M * V)
                fresh = lax.dynamic_index_in_dim(
                    xs, jnp.clip(mb, 0, M - 1), axis=0, keepdims=False)
                # device 0 at v==0 starts a fresh microbatch; everything
                # else (incl. device 0 at v>0) consumes the wire
                inp = jnp.where((p == 0) & (v == 0), fresh, recv)
                chunk_params = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(
                        a, jnp.clip(v, 0, V - 1), axis=0, keepdims=False),
                    chunks)
                if stage_aux:
                    out, aux = chunk_fn(chunk_params, inp)
                    aux_acc = aux_acc + jnp.where(
                        in_window, aux.astype(jnp.float32), 0.0)
                else:
                    out = chunk_fn(chunk_params, inp)
                send = lax.ppermute(
                    out, axis,
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
                valid = (p == n_stages - 1) & (v == V - 1) & in_window
                idx = jnp.clip(mb, 0, M - 1)
                current = lax.dynamic_index_in_dim(outputs, idx, 0,
                                                   keepdims=False)
                outputs = lax.dynamic_update_index_in_dim(
                    outputs, jnp.where(valid, out, current), idx, 0)
                return (send, outputs, aux_acc), None

            init = (jnp.zeros_like(xs[0]),
                    jnp.zeros((M, *xs.shape[1:]), xs.dtype),
                    jnp.zeros((), jnp.float32))
            (_, outputs, aux_acc), _ = lax.scan(timestep, init,
                                                jnp.arange(ticks))
            outputs = lax.psum(
                jnp.where(p == n_stages - 1, outputs,
                          jnp.zeros_like(outputs)), axis)
            outputs = outputs.reshape(x_local.shape)
            if stage_aux:
                return outputs, _reduce_stage_aux(aux_acc, mesh, axis)
            return outputs

        specs_out = out_specs if out_specs is not None else in_specs
        if stage_aux:
            specs_out = (specs_out, P())
        return jax.shard_map(per_device, mesh=mesh,
                    in_specs=(params_specs, in_specs),
                    out_specs=specs_out,
                    check_vma=False)(stage_params, x)

    return pipelined


# ---------------------------------------------------------------------------
# Llama integration
# ---------------------------------------------------------------------------

# Llama layout on a pipe(+data/fsdp/tensor) mesh: layer stack sharded on the
# layer dim over pipe, the Megatron dim over tensor, and the d_model dim over
# fsdp (ZeRO-3: the stage body all-gathers one layer's weights at a time and
# the gather's transpose reduce-scatters the grads — scaling-book FSDP+PP).
# embed/lm_head shard like LLAMA_RULES and run under GSPMD outside the
# shard_map. Axis pruning for size-1/absent axes lives in
# ShardingRules.spec_for.
# Layer-stack rules take precedence (matched first, `layers/` prefix);
# embed/lm_head/final-norm fall through to the non-pipelined LLAMA_RULES so
# the two paths can never place them differently.
PIPE_LLAMA_RULES = ShardingRules(rules=[
    (r"layers/(wq|wk|wv|w_gate|w_up)$", (AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR)),
    (r"layers/(wo|w_down)$",            (AXIS_PIPE, AXIS_TENSOR, AXIS_FSDP)),
    (r"layers/.*norm$",                 (AXIS_PIPE,)),
] + LLAMA_RULES.rules)

# The pipelined activation: batch dim over the data-like axes, sequence dim
# over the context axis (ring attention runs inside the stage body).
_PIPE_ACT_RULES = ShardingRules(rules=[(r"^x$", (_BATCH_AXES, AXIS_CONTEXT))])


def _build_pipeline_runner(stage_fn, mesh, M: int, n_virtual: int,
                           act_spec, layer_specs, stage_aux: bool):
    """Pick the schedule and wire the specs — shared by every model family."""
    if n_virtual > 1:
        return gpipe_interleaved(
            stage_fn, mesh, axis="pipe", n_microbatches=M,
            n_virtual=n_virtual, in_specs=act_spec,
            params_specs=_virtual_layer_specs(layer_specs, n_virtual),
            out_specs=act_spec, stage_aux=stage_aux)
    return gpipe(stage_fn, mesh, axis="pipe", n_microbatches=M,
                 in_specs=act_spec, params_specs=layer_specs,
                 out_specs=act_spec, stage_aux=stage_aux)


def _resolve_stage_attn(cfg, live, tp: int, seq_len: int):
    """Resolve ``cfg.attn_impl`` for use INSIDE a pipeline stage's shard_map.

    With a live context axis, attention MUST be context-parallel (a local-
    chunk flash/xla would silently attend over 1/cp of the sequence): ulysses
    when requested, the ring otherwise — via the ``*_local`` already-inside-
    shard_map dispatches. Without one, ring/ulysses are rejected and "auto"
    resolves to flash (TPU) / xla, since "auto" consults the ambient mesh
    context which must not route to a nested shard_map. Works for any config
    dataclass carrying attn_impl/n_heads/n_kv_heads (Llama, MoE, ...).
    """
    import dataclasses as _dc

    if cfg.attn_impl not in ("auto", "xla", "flash", "ring", "ulysses"):
        raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}; expected "
                         "auto|xla|flash|ring|ulysses")
    cp = live.get("context", 1)
    if cp > 1:
        if seq_len % cp:
            raise ValueError(f"seq_len={seq_len} not divisible by "
                             f"context={cp}")
        if cfg.attn_impl == "ulysses":
            # ulysses scatters the LOCAL (post-tp) heads over the context axis
            if (cfg.n_heads // tp) % cp or (cfg.n_kv_heads // tp) % cp:
                raise ValueError(
                    f"ulysses needs context={cp} to divide the per-tensor-"
                    f"shard head counts {cfg.n_heads}/{tp} and "
                    f"{cfg.n_kv_heads}/{tp}; use ring attention instead")
            return _dc.replace(cfg, attn_impl="ulysses_local")
        return _dc.replace(cfg, attn_impl="ring_local")
    if cfg.attn_impl in ("ring", "ulysses"):
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} in a pipeline needs a live "
            "context axis (mesh context size > 1); use xla/flash otherwise")
    if cfg.attn_impl == "auto":
        from ..ops.attention import flash_auto
        impl = "flash" if flash_auto(seq_len, cfg.n_heads,
                                     cfg.n_kv_heads) else "xla"
        return _dc.replace(cfg, attn_impl=impl)
    return cfg


def _validate_stage_divisibility(cfg, n_stages: int, tp: int, fsdp: int,
                                 n_virtual: int = 1) -> None:
    """Shared pipe/tensor/fsdp divisibility checks for pipelined models."""
    if cfg.n_layers % (n_stages * n_virtual):
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pipe={n_stages}"
            + (f" × virtual={n_virtual}" if n_virtual > 1 else ""))
    if tp > 1 and (cfg.n_kv_heads % tp or cfg.ffn_dim % tp):
        raise ValueError(f"tensor={tp} must divide n_kv_heads="
                         f"{cfg.n_kv_heads} and ffn_dim={cfg.ffn_dim}")
    if fsdp > 1 and cfg.dim % fsdp:
        raise ValueError(f"fsdp={fsdp} must divide dim={cfg.dim}")


def _validate_pipe_batch(batch: int, live, n_microbatches: int) -> None:
    dp = 1
    for a in _BATCH_AXES:
        dp *= live.get(a, 1)
    local_batch = batch // dp
    if batch % dp or local_batch % n_microbatches:
        raise ValueError(
            f"batch={batch} must divide over dp={dp} into local "
            f"batches divisible by microbatches={n_microbatches}")


def _make_zero3_gather(layer_specs, fsdp: int):
    """Build the in-stage ZeRO-3 gather for one layer's (scan-stripped) param
    tree: each fsdp-sharded leaf is all-gathered on the dim the rule table
    puts "fsdp" at (minus the stripped pipe dim). Under the remat wrapper the
    gathered copies are recomputed in backward, where the gather's transpose
    reduce-scatters the weight grads back over fsdp. One implementation for
    every pipelined model family."""

    def path_key(path):
        return tuple(str(getattr(p, "key", p)) for p in path)

    gather_dims = {path_key(path): list(spec).index("fsdp") - 1
                   for path, spec in
                   jax.tree_util.tree_leaves_with_path(layer_specs)
                   if fsdp > 1 and "fsdp" in spec}

    def gather_layer(lw):
        if not gather_dims:
            return lw

        def gather(path, leaf):
            dim = gather_dims.get(path_key(path))
            if dim is None:
                return leaf
            return lax.all_gather(leaf, "fsdp", axis=dim, tiled=True)

        return jax.tree_util.tree_map_with_path(gather, lw)

    return gather_layer


def _local_freqs(freqs, h, cp: int):
    """RoPE positions are global; slice this context-rank's window of the
    (S, Hd/2) table for its local sequence chunk."""
    if cp <= 1:
        return freqs
    s_local = h.shape[1]
    return lax.dynamic_slice_in_dim(
        freqs, lax.axis_index("context") * s_local, s_local, axis=0)


def llama_pipeline_specs(params, mesh):
    """PartitionSpec pytree placing a llama param tree per ``PIPE_LLAMA_RULES``."""
    return PIPE_LLAMA_RULES.tree_specs(params, mesh)


def llama_pipeline_shardings(params, mesh):
    """``NamedSharding`` pytree for ``llama_pipeline_specs`` (device_put-able)."""
    return PIPE_LLAMA_RULES.tree_shardings(params, mesh)


def _virtual_layer_specs(layer_specs, n_virtual: int):
    """Spec for the interleaved ``(V, P, lpc, …)`` layer layout: the layer
    dim's pipe sharding moves to dim 1 (chunk c on device c mod P), V and
    lpc replicated, trailing dims keep their rule-table placement."""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(
        lambda spec: P(None, list(spec)[0], None, *list(spec)[1:]),
        layer_specs)


def _pipeline_place(params, mesh, specs, n_virtual: int):
    """Place a param tree for the (optionally interleaved) pipeline.

    ``n_virtual == 1``: device_put per ``specs``. ``n_virtual > 1``: each
    layer-stacked leaf under ``params["layers"]`` is reshaped ``(L, …) →
    (V, P, L/(P·V), …)`` so global chunk ``c`` lands on device ``c mod P``
    (the strided layout the interleaved schedule needs), then device_put;
    everything outside ``layers`` keeps its rule-table placement.
    """
    from jax.sharding import NamedSharding

    if n_virtual == 1:
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf,
                                              NamedSharding(mesh, spec)),
            params, specs)
    p_size = _live_axes(mesh).get("pipe", 1)

    def reshape(leaf):
        if leaf.shape[0] % (p_size * n_virtual):
            raise ValueError(
                f"n_layers={leaf.shape[0]} not divisible by pipe={p_size} "
                f"× virtual={n_virtual}")
        lpc = leaf.shape[0] // (p_size * n_virtual)
        return leaf.reshape(n_virtual, p_size, lpc, *leaf.shape[1:])

    placed = dict(params)
    vspecs = _virtual_layer_specs(specs["layers"], n_virtual)
    placed["layers"] = jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(reshape(leaf),
                                          NamedSharding(mesh, spec)),
        params["layers"], vspecs)
    for key in params:
        if key != "layers":
            placed[key] = jax.tree_util.tree_map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(mesh, spec)),
                params[key], specs[key])
    return placed


def llama_pipeline_place(params, mesh, n_virtual: int = 1):
    """Place a llama param tree for the (optionally interleaved) pipeline."""
    return _pipeline_place(params, mesh, llama_pipeline_specs(params, mesh),
                           n_virtual)


def llama_hidden_pipelined(params, tokens, cfg, mesh, *,
                           n_microbatches: Optional[int] = None,
                           n_virtual: int = 1):
    """Llama forward with layers pipelined over the mesh's ``pipe`` axis,
    composing with data parallelism (batch dim over ``data``/``fsdp``/``dcn``),
    ZeRO-3 parameter sharding (``fsdp`` axis: stage weights stored sharded,
    one layer all-gathered at a time, grads reduce-scattered), and Megatron
    tensor parallelism (``tensor`` axis) inside each stage.

    ``n_virtual > 1`` switches to the interleaved (virtual-stage) schedule:
    each device holds V strided layer chunks and the bubble shrinks V×
    (:func:`gpipe_interleaved`). Params must then be placed with
    ``llama_pipeline_place(params, mesh, n_virtual)`` — layer leaves carry
    the ``(V, P, lpc, …)`` layout.

    Embedding / final norm / LM head stay under GSPMD outside the shard_map
    (they are a tiny fraction of FLOPs); only the layer stack is staged.
    """
    from ..models.llama import _layer, rmsnorm, rope_freqs

    live = _live_axes(mesh)
    n_stages = live.get("pipe", 1)
    tp = live.get("tensor", 1)
    fsdp = live.get("fsdp", 1)
    _validate_stage_divisibility(cfg, n_stages, tp, fsdp, n_virtual)
    cfg = _resolve_stage_attn(cfg, live, tp, tokens.shape[1])
    cp = live.get("context", 1)
    M = n_microbatches or n_stages
    _validate_pipe_batch(tokens.shape[0], live, M)

    x = params["embed"][tokens].astype(cfg.dtype)
    freqs = rope_freqs(cfg, tokens.shape[1])

    tp_axis = "tensor" if tp > 1 else None
    layer_specs = llama_pipeline_specs(params, mesh)["layers"]
    gather_layer = _make_zero3_gather(layer_specs, fsdp)

    def stage_fn(local_layers, h):
        fr = _local_freqs(freqs, h, cp)

        def body(carry, lw):
            return _layer(cfg, carry, gather_layer(lw), fr,
                          tp_axis=tp_axis), None
        body = jax.checkpoint(body)
        out, _ = lax.scan(body, h, local_layers)
        return out
    act_spec = _PIPE_ACT_RULES.spec_for("x", mesh)
    run = _build_pipeline_runner(stage_fn, mesh, M, n_virtual, act_spec,
                                 layer_specs, stage_aux=False)
    x = run(params["layers"], x)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps)


def llama_forward_pipelined(params, tokens, cfg, mesh, **kw):
    """Pipelined forward to logits (see :func:`llama_hidden_pipelined`)."""
    x = llama_hidden_pipelined(params, tokens, cfg, mesh, **kw)
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)


def llama_loss_pipelined(params, tokens, targets, cfg, mesh, *,
                         chunk: int = 256, **kw):
    """Pipelined next-token CE WITHOUT materializing the (B, S, V) fp32
    logits: the pipelined hidden states feed the shared per-chunk LM-head
    loss (``models.llama.chunked_ce``) — same HBM win as the non-pipelined
    ``llama_loss_chunked``."""
    from ..models.llama import chunked_ce

    x = llama_hidden_pipelined(params, tokens, cfg, mesh, **kw)
    return chunked_ce(x, targets, params["lm_head"].astype(cfg.dtype), chunk)


# ---------------------------------------------------------------------------
# MoE integration: expert parallelism inside pipeline stages
# ---------------------------------------------------------------------------

# MoE layer stack on a pipe(+data/fsdp/expert/tensor) mesh: attention weights
# as in the llama table; expert-stacked FFN weights additionally shard their
# expert dim over "expert" (the stage body slices dispatch/combine to local
# experts and psums the output — activations are replicated over the expert
# axis in this layout, so no all-to-all is needed); router replicated (fp32,
# tiny, and every rank routes identically).
PIPE_MOE_RULES = ShardingRules(rules=[
    (r"layers/(wq|wk|wv)$",            (AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR)),
    (r"layers/wo$",                    (AXIS_PIPE, AXIS_TENSOR, AXIS_FSDP)),
    (r"layers/experts/w_(gate|up)$",
     (AXIS_PIPE, AXIS_EXPERT, AXIS_FSDP, AXIS_TENSOR)),
    (r"layers/experts/w_down$",
     (AXIS_PIPE, AXIS_EXPERT, AXIS_TENSOR, AXIS_FSDP)),
    (r"layers/router$",                (AXIS_PIPE,)),
    (r"layers/.*norm$",                (AXIS_PIPE,)),
] + LLAMA_RULES.rules)


def moe_pipeline_specs(params, mesh):
    return PIPE_MOE_RULES.tree_specs(params, mesh)


def moe_pipeline_shardings(params, mesh):
    """``NamedSharding`` pytree for an MoE param tree on a pipe mesh."""
    return PIPE_MOE_RULES.tree_shardings(params, mesh)


def moe_pipeline_place(params, mesh, n_virtual: int = 1):
    """Place an MoE param tree for the (optionally interleaved) pipeline."""
    return _pipeline_place(params, mesh, moe_pipeline_specs(params, mesh),
                           n_virtual)


def moe_hidden_pipelined(params, tokens, cfg, mesh, *,
                         n_microbatches: Optional[int] = None,
                         n_virtual: int = 1):
    """MoE headless forward (final-normed hidden states + aux) with layers
    pipelined over ``pipe``, experts sharded over
    ``expert`` INSIDE each stage, composing with data/fsdp/tensor exactly as
    :func:`llama_hidden_pipelined`. Returns ``(hidden, aux)``: the
    final-normed (B, S, D) hidden states in ``cfg.dtype`` (the LM head is
    applied by the forward/loss wrappers) and the router load-balancing
    loss averaged over microbatches and layers (bubble ticks masked by
    :func:`gpipe`'s ``stage_aux`` channel).

    Note: ``aux`` is a product of batch means, so the microbatch average
    differs from the sequential full-batch value at O(1/M) — the hidden
    states are bit-comparable, the aux regularizer is statistically
    equivalent.
    """
    from ..models.llama import rmsnorm, rope_freqs
    from ..models.moe import _moe_layer

    live = _live_axes(mesh)
    n_stages = live.get("pipe", 1)
    tp = live.get("tensor", 1)
    fsdp = live.get("fsdp", 1)
    ep = live.get("expert", 1)
    _validate_stage_divisibility(cfg, n_stages, tp, fsdp, n_virtual)
    if ep > 1 and cfg.n_experts % ep:
        raise ValueError(f"expert={ep} must divide n_experts="
                         f"{cfg.n_experts}")
    cp = live.get("context", 1)
    if cp > 1 and not cfg.context_chunked_routing:
        # in-stage MoE routing assigns expert capacity per local sequence
        # chunk, which diverges from full-sequence routing whenever an
        # expert overflows — require the explicit opt-in
        raise ValueError(
            "MoE inside pipeline stages with a context axis routes per "
            "sequence chunk; opt in with "
            "MoeConfig(context_chunked_routing=True) or use a context-free "
            "mesh")
    cfg = _resolve_stage_attn(cfg, live, tp, tokens.shape[1])
    M = n_microbatches or n_stages
    _validate_pipe_batch(tokens.shape[0], live, M)

    x = params["embed"][tokens].astype(cfg.dtype)
    freqs = rope_freqs(cfg._llama_view(), tokens.shape[1])

    tp_axis = "tensor" if tp > 1 else None
    ep_axis = "expert" if ep > 1 else None
    layer_specs = moe_pipeline_specs(params, mesh)["layers"]
    gather_layer = _make_zero3_gather(layer_specs, fsdp)

    def stage_fn(local_layers, h):
        fr = _local_freqs(freqs, h, cp)

        def body(carry, lw):
            return _moe_layer(cfg, carry, gather_layer(lw), fr,
                              tp_axis=tp_axis, ep_axis=ep_axis), None
        body = jax.checkpoint(body)
        (out, aux), _ = lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                 local_layers)
        return out, aux

    act_spec = _PIPE_ACT_RULES.spec_for("x", mesh)
    run = _build_pipeline_runner(stage_fn, mesh, M, n_virtual, act_spec,
                                 layer_specs, stage_aux=True)
    x, aux = run(params["layers"], x)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux / (M * cfg.n_layers)


def moe_forward_pipelined(params, tokens, cfg, mesh, **kw):
    """Pipelined MoE forward to ``(logits, aux)``."""
    x, aux = moe_hidden_pipelined(params, tokens, cfg, mesh, **kw)
    return (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32), aux


def moe_loss_pipelined(params, tokens, targets, cfg, mesh, *,
                       chunk: int = 256, **kw):
    """Pipelined MoE next-token CE + router aux, with the per-chunk LM-head
    loss (never materializes (B, S, V) fp32 logits)."""
    from ..models.llama import chunked_ce

    x, aux = moe_hidden_pipelined(params, tokens, cfg, mesh, **kw)
    ce = chunked_ce(x, targets, params["lm_head"].astype(cfg.dtype), chunk)
    return ce + cfg.router_aux_weight * aux


# ---------------------------------------------------------------------------
# ViT integration: the encoder family pipelines with the same machinery
# ---------------------------------------------------------------------------

# ViT encoder stack on a pipe(+data/fsdp/tensor) mesh: qkv/mlp matrices take
# the Megatron layout, LayerNorm scale/bias replicated per stage;
# patch_embed/pos_embed/head fall through to VIT_RULES so pipelined and
# plain paths can't diverge.
PIPE_VIT_RULES = ShardingRules(rules=[
    (r"layers/(wqkv|w_up)$", (AXIS_PIPE, AXIS_FSDP, AXIS_TENSOR)),
    (r"layers/(wo|w_down)$", (AXIS_PIPE, AXIS_TENSOR, AXIS_FSDP)),
    (r"layers/ln",           (AXIS_PIPE,)),
] + VIT_RULES.rules)


def vit_pipeline_specs(params, mesh):
    return PIPE_VIT_RULES.tree_specs(params, mesh)


def vit_pipeline_shardings(params, mesh):
    """``NamedSharding`` pytree for a ViT param tree on a pipe mesh."""
    return PIPE_VIT_RULES.tree_shardings(params, mesh)


def vit_pipeline_place(params, mesh, n_virtual: int = 1):
    """Place a ViT param tree for the (optionally interleaved) pipeline."""
    return _pipeline_place(params, mesh, vit_pipeline_specs(params, mesh),
                           n_virtual)


def vit_forward_pipelined(params, images, cfg, mesh, *,
                          n_microbatches: Optional[int] = None,
                          n_virtual: int = 1):
    """ViT forward with encoder layers pipelined over ``pipe``, composing
    with data/fsdp(ZeRO-3)/tensor exactly as the decoder families. No RoPE,
    no causal mask, no context axis (images are short sequences); the wqkv
    fused projection column-shards over tensor in blocks of 3·D/tp —
    tensor-parallel ViT stages are not wired yet, so tp must be 1.
    """
    from ..models.vit import _encoder_layer, layernorm, patchify

    live = _live_axes(mesh)
    n_stages = live.get("pipe", 1)
    if live.get("tensor", 1) > 1:
        # the fused (D, 3D) wqkv would need an interleaved q/k/v column
        # split per tensor shard; un-fused projections are round-2 work
        raise ValueError("tensor parallelism inside ViT pipeline stages is "
                         "not supported yet; use a tensor-free mesh")
    if live.get("context", 1) > 1:
        raise ValueError("a context axis does not apply to ViT (short "
                         "sequences); use a context-free mesh")
    fsdp = live.get("fsdp", 1)
    # tp forced to 1 above, so the helper's n_kv_heads/ffn_dim checks (which
    # VitConfig lacks) are short-circuited
    _validate_stage_divisibility(cfg, n_stages, 1, fsdp, n_virtual)
    M = n_microbatches or n_stages
    _validate_pipe_batch(images.shape[0], live, M)

    x = patchify(images.astype(cfg.dtype), cfg) @ params["patch_embed"]
    x = x + params["pos_embed"].astype(cfg.dtype)[None]

    layer_specs = vit_pipeline_specs(params, mesh)["layers"]
    gather_layer = _make_zero3_gather(layer_specs, fsdp)

    def stage_fn(local_layers, h):
        def body(carry, lw):
            return _encoder_layer(cfg, carry, gather_layer(lw)), None
        body = jax.checkpoint(body)
        out, _ = lax.scan(body, h, local_layers)
        return out

    act_spec = _PIPE_ACT_RULES.spec_for("x", mesh)
    run = _build_pipeline_runner(stage_fn, mesh, M, n_virtual, act_spec,
                                 layer_specs, stage_aux=False)
    x = run(params["layers"], x)
    x = layernorm(x, params["final_ln_scale"], params["final_ln_bias"],
                  cfg.norm_eps)
    pooled = jnp.mean(x, axis=1)
    return (pooled @ params["head"].astype(cfg.dtype)).astype(jnp.float32)


def vit_loss_pipelined(params, images, labels, cfg, mesh, **kw):
    from ..models.vit import classification_ce

    return classification_ce(
        vit_forward_pipelined(params, images, cfg, mesh, **kw), labels)



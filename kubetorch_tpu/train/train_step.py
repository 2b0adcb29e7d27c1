"""Sharded training step builder.

GSPMD style: the step is a pure function jit-compiled once with NamedSharding
constraints on params/opt-state/batch; XLA inserts all collectives
(reduce-scatter over fsdp, psum over data, all-to-all for expert routing).
Buffers are donated so params update in place in HBM.

**Step-time anatomy (ISSUE 12).** Three knobs decide where one step's
milliseconds and HBM go, and `kt hbm audit` is the tool that picks between
them instead of guessing:

- ``accum_steps`` — microbatched fwd+bwd inside a scan: peak activation
  memory is one microbatch's, at no extra FLOPs.
- ``overlap_grads`` — per-microbatch bucketed gradient reduction: each
  microbatch's grads are sharding-constrained to the parameter layout
  *inside* the scan (each leaf is one bucket), so GSPMD emits the fsdp
  reduce-scatter there and XLA's latency-hiding scheduler overlaps it with
  the next microbatch's compute. The fp32 accumulator holds one fsdp shard
  per device instead of a full replicated gradient. Numerics: the same
  per-element sums in a different association order — bit-comparable to the
  plain path (pinned by tests on the 8-device forced-host mesh).
- ``remat_policy`` — named ``jax.checkpoint`` policy
  (``none``/``dots``/``nothing_saveable``/callable) applied around the loss
  per microbatch, trading recompute FLOPs for activation HBM. The model's
  own layer stack takes the same names via ``LlamaConfig.remat_policy``.

The wrapper observes ``kt_train_step_seconds{phase="compute"}`` per call —
the number the perf gate's ``train_step`` stage regresses against.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import optax

from .. import telemetry
from ..models.common import resolve_remat_policy
from ..parallel.sharding import ShardingRules, batch_sharding

# metric names the step can compute; "step" always rides along
STEP_METRICS = ("loss", "grad_norm")


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup_steps: int = 100, total_steps: int = 10000):
    sched = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=lr, warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay,
                    mu_dtype=jnp.float32),
    )


def init_train_state(params: Any, optimizer=None) -> TrainState:
    optimizer = optimizer or default_optimizer()
    return TrainState(params=params, opt_state=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(loss_fn: Callable, optimizer=None, mesh=None,
                    rules: Optional[ShardingRules] = None,
                    donate: bool = True, accum_steps: int = 1,
                    overlap_grads: bool = False,
                    remat_policy: Any = None,
                    metrics: Sequence[str] = STEP_METRICS) -> Callable:
    """Build ``step(state, batch) -> (state, metrics)``, jit-sharded on ``mesh``.

    ``loss_fn(params, tokens, targets) -> scalar``. When ``mesh`` is given the
    returned step carries in/out shardings derived from ``rules`` so the first
    call lays out HBM correctly; without a mesh it is a plain jit.

    ``accum_steps > 1`` runs gradient accumulation: the batch's leading dim is
    split into that many microbatches, fwd+bwd runs per microbatch inside a
    ``lax.scan`` (peak activation memory is one microbatch's), grads are
    averaged, and ONE optimizer update applies — numerically the full-batch
    step for mean-reduced losses, at a fraction of the memory.

    ``overlap_grads=True`` (requires ``mesh``) turns the end-of-scan bulk
    reduction into per-microbatch bucketed reduce-scatters (one bucket per
    grad leaf, steered with ``with_sharding_constraint``) that overlap the
    next microbatch's fwd+bwd, and shrinks the fp32 accumulator to one fsdp
    shard per device. See the module docstring.

    ``remat_policy`` ("none"/"dots"/"nothing_saveable"/callable) wraps the
    loss in ``jax.checkpoint`` with that policy per microbatch.

    ``metrics`` selects what the step computes beyond ``step``: drop
    ``"grad_norm"`` (``metrics=("loss",)``) to remove a full-tree reduction
    from the hot path when nothing scrapes it.
    """
    optimizer = optimizer or default_optimizer()
    if mesh is not None and rules is None:
        raise ValueError("make_train_step: a mesh requires sharding `rules`")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if overlap_grads and mesh is None:
        raise ValueError("make_train_step: overlap_grads steers collectives "
                         "onto a mesh — pass mesh= and rules=")
    unknown = set(metrics) - set(STEP_METRICS)
    if unknown:
        raise ValueError(f"unknown step metrics {sorted(unknown)}; "
                         f"expected a subset of {STEP_METRICS}")
    metrics = tuple(metrics)

    policy = resolve_remat_policy(remat_policy)
    if policy is not None:
        loss_fn = jax.checkpoint(loss_fn, policy=policy)

    def _bucketed(tree):
        # each grad leaf is one bucket: constraining it to the param layout
        # HERE makes GSPMD emit that leaf's reduce-scatter at this program
        # point (inside the scan) instead of one bulk reduce after it
        return rules.constrain_tree(tree, mesh)

    def loss_and_grads(params, batch):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch["tokens"],
                                                      batch["targets"])
            if overlap_grads:
                grads = _bucketed(grads)
            return loss, grads
        b = batch["tokens"].shape[0]
        if b % accum_steps:
            raise ValueError(f"batch={b} not divisible by "
                             f"accum_steps={accum_steps}")
        micro = {k: v.reshape(accum_steps, b // accum_steps, *v.shape[1:])
                 for k, v in batch.items()}

        def body(carry, mb):
            loss_sum, grad_sum = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, mb["tokens"],
                                                      mb["targets"])
            if overlap_grads:
                grads = _bucketed(grads)
            grad_sum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), grad_sum, grads)
            if overlap_grads:
                # keep the accumulator itself pinned to one fsdp shard per
                # device — without this the carry is free to widen back to
                # a full replicated fp32 gradient
                grad_sum = _bucketed(grad_sum)
            return (loss_sum + loss, grad_sum), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if overlap_grads:
            zeros = _bucketed(zeros)
        (loss_sum, grad_sum), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / accum_steps
        return loss_sum * inv, jax.tree_util.tree_map(
            lambda g: (g * inv), grad_sum)

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        loss, grads = loss_and_grads(state.params, batch)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p + u.astype(p.dtype)), state.params, updates)
        if mesh is not None:
            # Pin the rule-defined layout: without this, GSPMD propagation is
            # free to transpose the output sharding (and with donation that
            # means a silent full reshuffle every step).
            param_sh = rules.tree_shardings(new_params, mesh)
            new_params = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_params, param_sh)
            new_opt = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_opt,
                _opt_shardings(new_opt, new_params, param_sh, mesh))
        m = {"step": state.step}
        if "loss" in metrics:
            m["loss"] = loss
        if "grad_norm" in metrics:
            # an extra full-tree reduction — opt out via metrics=("loss",)
            # when nothing reads it (docs/operations.md "Step-time anatomy")
            m["grad_norm"] = optax.global_norm(grads)
        return TrainState(new_params, new_opt, state.step + 1), m

    def in_mesh(fn):
        """Install the ambient mesh for mesh-aware ops (ring attention, the
        Pallas kernels' shard_map) INSIDE the traced function: it is read at
        trace time, and every way of tracing — a call, ``.jitted.lower``,
        ``grads_fn`` — must see it. A Mosaic kernel traced without it sits
        bare under GSPMD, which jax refuses on the chip."""
        if mesh is None:
            return fn
        from ..parallel.mesh_context import use_mesh

        @functools.wraps(fn)
        def traced(*args):
            with use_mesh(mesh):
                return fn(*args)
        return traced

    jitted = jax.jit(in_mesh(step), donate_argnums=(0,) if donate else ())
    step_hist = telemetry.train_metrics()["step_seconds"]

    def wrapper(state, batch):
        with telemetry.timed(step_hist, phase="compute"):
            return jitted(state, batch)

    if mesh is not None:
        def shard_state(state: TrainState) -> TrainState:
            """Place an (unsharded) TrainState onto the mesh per the rules."""
            from jax.sharding import NamedSharding, PartitionSpec as P

            param_sh = rules.tree_shardings(state.params, mesh)
            opt_sh = _opt_shardings(state.opt_state, state.params, param_sh, mesh)
            return TrainState(
                params=jax.tree_util.tree_map(jax.device_put, state.params, param_sh),
                opt_state=jax.tree_util.tree_map(jax.device_put, state.opt_state, opt_sh),
                step=jax.device_put(state.step, NamedSharding(mesh, P())),
            )

        wrapper.shard_state = shard_state  # type: ignore[attr-defined]
        wrapper.batch_sharding = batch_sharding(mesh)  # type: ignore[attr-defined]

    wrapper.jitted = jitted  # type: ignore[attr-defined]
    # the bare accumulation path, jitted without the optimizer: what the
    # overlap-equivalence tests and `bench.py --step-overlap` compare and
    # whose output sharding *is* the accumulator's (one fsdp shard per
    # device when overlap_grads is on)
    wrapper.grads_fn = jax.jit(in_mesh(loss_and_grads))  # type: ignore[attr-defined]
    return wrapper


def _opt_shardings(opt_state: Any, params: Any, param_shardings: Any, mesh):
    """Optimizer-state subtrees that mirror the param tree *structurally*
    (adam mu/nu) inherit the param shardings wholesale; scalar leaves (counts,
    schedule state) are replicated.

    Matching must be by tree structure, not leaf shape: distinct params can
    share a shape with different shardings (Llama wq/wo are both (L, D, D)
    with transposed specs), and a shape-keyed match would silently pin the
    wrong layout, forcing a reshard of the fp32 state every step.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    replicated = NamedSharding(mesh, P())
    param_treedef = jax.tree_util.tree_structure(params)

    def rec(node):
        if jax.tree_util.tree_structure(node) == param_treedef:
            return param_shardings
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            children = [rec(c) for c in node]
            if hasattr(node, "_fields"):  # namedtuple (optax states)
                return type(node)(*children)
            return type(node)(children)
        return replicated

    return rec(opt_state)

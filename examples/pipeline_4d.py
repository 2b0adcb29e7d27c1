"""4D-parallel Llama training: data × ZeRO-3 fsdp × pipeline × tensor on ONE
mesh — plus ring attention on a context axis for the long-sequence variant.

    kt.fn(train).to(kt.Compute(tpu="v5p-128")
                      .distribute("jax", mesh={"data": 2, "fsdp": 2,
                                               "pipe": 4, "tensor": 4}))

What each axis does (`parallel/pipeline.py`):
- ``data``/``fsdp``: batch shards; fsdp additionally stores every stage's
  layer weights ZeRO-3-sharded, all-gathering ONE layer at a time inside the
  stage body (grads reduce-scatter back through the gather's transpose).
- ``pipe``: GPipe over layer-stacked params; activations hop stage→stage
  with one ``ppermute`` per microbatch per boundary; the whole schedule is a
  single compiled ``lax.scan`` — no host round-trips between microbatches.
- ``tensor``: Megatron column/row sharding inside each stage with exactly
  two explicit psums per layer.
- pass ``n_virtual=V`` (with params placed by ``llama_pipeline_place``) for
  the interleaved schedule: V strided layer chunks per device, bubble V×
  smaller.
- ``context`` (swap for ``data`` at long seq_len): the sequence dim shards
  and the stage body runs ring attention over ICI neighbors (or ulysses
  all-to-all with ``attn_impl="ulysses"``).

The reference cannot express any of this — it launches torch processes and
leaves model parallelism to user frameworks (SURVEY §2.4). Here the mesh IS
the API. Runs locally at toy scale:

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=.. python pipeline_4d.py
"""

import kubetorch_tpu as kt


def train(num_steps: int = 20, microbatches: int = 4):
    import time

    import jax
    import jax.numpy as jnp
    import optax

    from kubetorch_tpu.models.llama import LlamaConfig, llama_init
    from kubetorch_tpu.parallel.pipeline import (PIPE_LLAMA_RULES,
                                                 llama_loss_pipelined)
    from kubetorch_tpu.train import init_train_state, make_train_step

    mesh = kt.distributed.mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    # batch divides over every batch-like axis (incl. dcn on multi-slice)
    dp = sizes.get("dcn", 1) * sizes.get("data", 1) * sizes.get("fsdp", 1)

    cfg = LlamaConfig.llama3_8b() if jax.default_backend() == "tpu" else \
        LlamaConfig.tiny(n_layers=4, attn_impl="xla", dtype=jnp.float32,
                         remat=False)
    opt = optax.adamw(3e-4)
    # PIPE_LLAMA_RULES gives make_train_step the pipeline layout: donation,
    # pinned output shardings, shard_state — no hand-rolled step needed
    step = make_train_step(
        lambda p, t, y: llama_loss_pipelined(p, t, y, cfg, mesh,
                                             n_microbatches=microbatches),
        optimizer=opt, mesh=mesh, rules=PIPE_LLAMA_RULES)
    state = step.shard_state(
        init_train_state(llama_init(jax.random.PRNGKey(0), cfg), opt))

    batch = microbatches * dp
    seq = min(cfg.max_seq_len, 4096 if jax.default_backend() == "tpu" else 32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    data = {"tokens": jax.device_put(tokens, step.batch_sharding),
            "targets": jax.device_put(jnp.roll(tokens, -1, 1),
                                      step.batch_sharding)}

    losses = []
    t0 = time.time()
    for _ in range(num_steps):
        state, metrics = step(state, data)
        losses.append(float(metrics["loss"]))
    dt = time.time() - t0
    return {"loss": losses[-1] if losses else None, "steps": num_steps,
            "tokens_per_sec": batch * seq * num_steps / dt,
            "mesh": {k: v for k, v in sizes.items() if v > 1}}


if __name__ == "__main__":
    out = (kt.fn(train)
           .to(kt.Compute(cpus=1).distribute(
               "jax", workers=1,
               mesh={"data": 1, "fsdp": 2, "pipe": 2, "tensor": 2})))()
    print(out)

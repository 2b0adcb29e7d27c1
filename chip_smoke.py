"""The quickest proof that the system still starts on the chip.

Drives the fabric's main path once, through the entry points a user calls,
at the full width of Llama-3.2-1B (depth as published, seeded random weights):

- serving: ``kt.cls(SmokeEngine).to(kt.Compute(tpu="v5e-1"))`` on the local
  backend (controller daemon → pod server → rank worker → ``GenerationEngine``),
  overlapping ``generate`` calls over HTTP, one hot reload through the
  fabric's own reload path to prove the compile cache is in use;
- training: ``kt.fn(train_smoke).to(...)`` runs ``make_train_step`` on
  ``bench.py``'s 0.5B model (flash forward and backward, chunked CE, AdamW).

This process never imports jax: a chip belongs to one process at a time, and
everything that needs it happens in the rank worker the pod server spawns.
The second phase starts only after the first phase's rank has exited.

    python3 chip_smoke.py             one chip; what the driver runs
    python3 chip_smoke.py --chips 4   the same path on a four-chip host
                                      (fsdp=4 training, tensor=4 serving)
    python3 chip_smoke.py --rehearse  CPU rehearsal of the control flow at
                                      tiny shapes; proves nothing about the
                                      chip and every line says so

Without a TPU a bare invocation exits non-zero at once and prints no result.
The last line of stdout on success is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
with the device as jax reported it inside the rank. Logs and the full report
land under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")

# Agreement with the reference, in logits. bf16 carries 8 significant bits:
# 32 units of roundoff at the unit scale of these logits. A token chosen by
# a broken position, mask or cache row scores whole units below the
# reference's best, not eighths.
LOGIT_TOL = 32 * 2.0 ** -8
NEW_TOKENS = 64


# ---------------------------------------------------------------------------
# What runs in the rank (imported there as module ``chip_smoke``)
# ---------------------------------------------------------------------------

def _device_report() -> dict:
    import jax
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {"pid": os.getpid(), "platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs),
            "bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
            "jax": jax.__version__,
            "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}


def _cache_entries() -> int:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    return len(os.listdir(d)) if os.path.isdir(d) else 0


def _compile(lowered) -> dict:
    """Compile a lowered computation: seconds, Mosaic custom calls and
    collectives in the compiled text."""
    t = time.monotonic()
    text = lowered.compile().as_text()
    return {"compile_s": round(time.monotonic() - t, 2),
            "mosaic_calls": text.count("tpu_custom_call"),
            "collectives": {op: text.count(f" {op}(") + text.count(f"{op}-start(")
                            for op in ("all-reduce", "all-gather",
                                       "reduce-scatter", "all-to-all")}}


def _model_cfg(spec: dict):
    from kubetorch_tpu.models.llama import LlamaConfig
    if spec["rehearse"]:
        import jax.numpy as jnp
        return LlamaConfig.tiny(max_seq_len=spec["max_len"], n_kv_heads=4,
                                dtype=jnp.float32)
    return LlamaConfig.llama3_1b(max_seq_len=spec["max_len"])


def _quarter_per_device(tree) -> dict:
    """Every leaf the rules shard holds 1/N of its elements per device, on N
    distinct devices."""
    import jax
    n_sharded, bad = 0, []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = len(leaf.sharding.device_set)
        shard = leaf.addressable_shards[0].data
        if leaf.sharding.is_fully_replicated:
            continue
        n_sharded += 1
        if shard.size * n != leaf.size or \
                len({s.device for s in leaf.addressable_shards}) != n:
            bad.append(jax.tree_util.keystr(path))
    return {"sharded_leaves": n_sharded, "bad": bad,
            "ok": n_sharded > 0 and not bad}


class SmokeEngine:
    """The engine service, the shape of
    ``examples/continuous_batching_service.py`` at Llama-3.2-1B width."""

    def __init__(self, spec: dict):
        import jax

        from kubetorch_tpu.models.llama import llama_init
        from kubetorch_tpu.serve import GenerationEngine

        self.spec = spec
        self.cfg = cfg = _model_cfg(spec)
        self.times = {}
        self.entries_at_start = _cache_entries()
        t = time.monotonic()
        # one jit, one compile: eager init pays ~30 small compiles
        self.params = jax.jit(llama_init, static_argnums=1)(
            jax.random.PRNGKey(0), cfg)
        jax.block_until_ready(self.params)
        self.times["init_s"] = round(time.monotonic() - t, 2)
        self.param_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.params))
        self.kw = dict(slots=spec["slots"], max_len=spec["max_len"],
                       prefill_buckets=tuple(spec["buckets"]),
                       decode_block=spec["decode_block"])
        self.mesh = None
        self.sharding = None
        if spec.get("mesh"):
            self._shard()
        else:
            self.engine = GenerationEngine(self.params, cfg, **self.kw)
        self.compiled = {}

    def _shard(self):
        """Four chips: the same params under the mesh ``.distribute`` sent,
        the engine built inside it. The unsharded copy stays as the one-chip
        reference this rank compares against."""
        import jax

        import kubetorch_tpu as kt
        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES, shard_pytree
        from kubetorch_tpu.serve import GenerationEngine

        self.mesh = kt.distributed.mesh()
        sharded = shard_pytree(self.params, LLAMA_RULES, self.mesh)
        jax.block_until_ready(sharded)
        self.sharding = _quarter_per_device(sharded)
        self.sharding["mesh"] = dict(self.mesh.shape)
        self.sharding["mesh_devices"] = len(set(self.mesh.devices.flat))
        with use_mesh(self.mesh):
            self.engine = GenerationEngine(sharded, self.cfg, **self.kw)

    def __kt_warmup__(self):
        """Compile before /ready admits traffic: each executable once
        explicitly (its seconds, its Mosaic calls), then the engine's own
        first calls, which find them in the compile cache."""
        import contextlib

        import jax
        import jax.numpy as jnp

        from kubetorch_tpu.parallel.mesh_context import use_mesh
        from kubetorch_tpu.serve import engine as E

        eng, cfg = self.engine, self.cfg
        scope = use_mesh(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()
        t0 = time.monotonic()
        key, f32 = jax.random.PRNGKey(0), jnp.float32
        with scope:
            for b in self.spec["buckets"]:
                self.compiled[f"prefill_{b}"] = _compile(E._prefill.lower(
                    eng.params, jnp.zeros((1, b), jnp.int32), jnp.int32(b),
                    key, jnp.zeros((1,), f32), cfg, top_k=None))
            n = eng.slots
            self.compiled["decode_block"] = _compile(E._decode_block.lower(
                eng.params, eng._cache, jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32), key, jnp.zeros((n,), f32), cfg,
                n_steps=eng.decode_block, top_k=None,
                skeys=jnp.zeros((n, 2), jnp.uint32)))
        self.times["compile_s"] = round(time.monotonic() - t0, 2)
        t0 = time.monotonic()
        for b in self.spec["buckets"]:
            eng.generate([1] * (b - 1), max_new_tokens=2, timeout=900)
        self.times["first_calls_s"] = round(time.monotonic() - t0, 2)
        self.times["warmup_s"] = round(
            self.times["compile_s"] + self.times["first_calls_s"], 2)
        self.entries_after_warmup = _cache_entries()

    def generate(self, prompt, max_new_tokens: int = NEW_TOKENS):
        return self.engine.generate(prompt, max_new_tokens=max_new_tokens,
                                    timeout=600)

    def report(self) -> dict:
        s = self.engine.stats()
        return {**_device_report(), "param_bytes": self.param_bytes,
                "times": self.times, "compiled": self.compiled,
                "cache_entries_at_start": self.entries_at_start,
                "cache_entries_after_warmup": self.entries_after_warmup,
                "sharding": self.sharding,
                "engine": {"tokens": s.tokens_generated,
                           "decode_steps": s.decode_steps,
                           "finished": s.finished_total}}

    def reference_check(self, prompt, tokens) -> dict:
        """The engine's tokens against the repo's references, in this rank:
        ``models.generate.generate`` on the same params, and a full-sequence
        ``llama_forward`` with XLA attention (no kernel under test in it).

        Bitwise equality with ``generate`` holds on the CPU and is reported;
        on the chip XLA's matmuls round differently at batch 1 and batch 8
        (``generate`` is not batch-invariant there either), so a near-tie
        between the reference's two best logits can go either way. What must
        hold: every engine token scores within LOGIT_TOL of the reference's
        best at its position, and where the two decodes part, the reference
        scores their two tokens within LOGIT_TOL of each other."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        from kubetorch_tpu.models.generate import generate
        from kubetorch_tpu.models.llama import llama_forward

        n = len(tokens)
        gen = np.asarray(generate(
            self.params, jnp.asarray([prompt], jnp.int32), self.cfg,
            max_new_tokens=n))[0, len(prompt):].tolist()
        ref_cfg = dataclasses.replace(self.cfg, attn_impl="xla")
        logits = np.asarray(jax.jit(
            lambda p, t: llama_forward(p, t, ref_cfg))(
                self.params, jnp.asarray([prompt + tokens], jnp.int32))
        )[0, len(prompt) - 1:-1]                      # row t predicts token t
        margin = logits.max(-1) - logits[np.arange(n), tokens]
        part = next((i for i, (a, b) in enumerate(zip(gen, tokens))
                     if a != b), None)
        tie_gap = 0.0 if part is None else float(
            abs(logits[part, gen[part]] - logits[part, tokens[part]]))
        out = {"finite": bool(np.isfinite(logits).all()),
               "max_margin": float(margin.max()),
               "tokens_off_reference_argmax": int((margin > 0).sum()),
               "equals_generate": part is None, "first_parting": part,
               "parting_gap": tie_gap, "tol": LOGIT_TOL}
        out["ok"] = (out["finite"] and out["max_margin"] <= LOGIT_TOL
                     and tie_gap <= LOGIT_TOL)
        return out

    def one_chip_tokens(self, prompts) -> list:
        """Four-chip runs only: the same prompts through an engine on the
        unsharded params (one device), for the mesh engine to be held to."""
        from kubetorch_tpu.serve import GenerationEngine
        eng = GenerationEngine(self.params, self.cfg, **self.kw)
        hs = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
        eng.start()
        try:
            return [h.result(timeout=600) for h in hs]
        finally:
            eng.stop()

    def kernel_checks(self) -> dict:
        """Each Pallas entry point, compiled as the backend compiles it (by
        Mosaic on the chip, never interpreted there), against its jax.numpy
        reference at the smoke models' shapes."""
        import jax
        import jax.numpy as jnp

        from kubetorch_tpu.models.llama import _xla_attention
        from kubetorch_tpu.models.quant import (Q4KEY, _dequant_int4,
                                                _quantize_leaf_int4)
        from kubetorch_tpu.ops.attention import flash_attention
        from kubetorch_tpu.ops.decode_attention import (
            decode_attention, decode_attention_quant)
        from kubetorch_tpu.ops.quant_matmul import q4_matmul
        from kubetorch_tpu.serve.kv_quant import quantize_rows

        f32 = jnp.float32
        on_chip = jax.default_backend() == "tpu"
        small = self.spec["rehearse"]

        def err(a, b):
            return float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32))))

        def run(fn, *args):
            jitted = jax.jit(fn)
            mosaic = "tpu_custom_call" in jitted.lower(*args).compile() \
                .as_text()
            return jitted(*args), mosaic

        out = {}

        def record(name, e, tol, mosaic):
            out[name] = {"err": round(e, 5), "tol": tol, "mosaic": mosaic,
                         "ok": e <= tol and (mosaic or not on_chip)}

        # (head_dim, heads, kv heads): the serving and the training model
        for hd, nh, nkv in ((64, 32, 8), (128, 12, 4)):
            ks = jax.random.split(jax.random.PRNGKey(hd), 3)
            b, s = (1, 128) if small else (1, 2048)
            q = jax.random.normal(ks[0], (b, s, nh, hd), jnp.bfloat16)
            k = jax.random.normal(ks[1], (b, s, nkv, hd), jnp.bfloat16)
            v = jax.random.normal(ks[2], (b, s, nkv, hd), jnp.bfloat16)
            sc = hd ** -0.5
            got, m = run(lambda q, k, v: flash_attention(q, k, v), q, k, v)
            record(f"flash_fwd_hd{hd}",
                   err(got, _xla_attention(q, k, v, sc)), 0.05, m)

            def sq(f):
                return lambda q, k, v: jnp.sum(f(q, k, v).astype(f32) ** 2)
            got, m = run(jax.grad(sq(flash_attention), (0, 1, 2)), q, k, v)
            ref = jax.grad(sq(lambda q, k, v: _xla_attention(q, k, v, sc)),
                           (0, 1, 2))(q, k, v)
            rel = max(err(g, r) / (float(jnp.max(jnp.abs(r.astype(f32))))
                                   + 1e-9) for g, r in zip(got, ref))
            record(f"flash_bwd_hd{hd}", rel, 0.05, m)

            slots, cs = (4, 256) if small else (8, 2048)
            qd = jax.random.normal(ks[0], (slots, nh, hd), jnp.bfloat16)
            ck = jax.random.normal(ks[1], (slots, cs, nkv, hd), jnp.bfloat16)
            cv = jax.random.normal(ks[2], (slots, cs, nkv, hd), jnp.bfloat16)
            pos = jnp.asarray([0, 5, 127, 128, 200, cs // 2, cs - 2, cs - 1]
                              [:slots], jnp.int32)

            def einsum_decode(qd, ck, cv, pos):
                qg = qd.reshape(slots, nkv, nh // nkv, hd)
                lg = jnp.einsum("bkgh,bskh->bkgs", qg, ck).astype(f32) * sc
                mask = jnp.arange(cs)[None, :] <= pos[:, None]
                lg = jnp.where(mask[:, None, None], lg, -1e30)
                pr = jax.nn.softmax(lg, axis=-1).astype(cv.dtype)
                return jnp.einsum("bkgs,bskh->bkgh", pr,
                                  cv).reshape(slots, nh, hd)
            want = einsum_decode(qd, ck, cv, pos)

            def grid(rows):
                # layer 1 of a two-layer head-major grid (L, B, NKV, S, ..),
                # as the engine holds it; layer 0 is zeros
                return jnp.stack([jnp.zeros_like(rows), rows]).swapaxes(2, 3)
            got, m = run(lambda *a: decode_attention(*a, 1),
                         qd, grid(ck), grid(cv), pos)
            record(f"decode_attention_hd{hd}", err(got, want), 0.05, m)
            kq, ksc = quantize_rows(ck)
            vq, vsc = quantize_rows(cv)
            got, m = run(lambda *a: decode_attention_quant(*a, 1),
                         qd, grid(kq), grid(ksc), grid(vq), grid(vsc), pos)
            record(f"decode_attention_quant_hd{hd}", err(got, want), 0.08, m)

        din, dout = (256, 512) if small else (2048, 8192)
        w = jax.random.normal(jax.random.PRNGKey(3), (din, dout), f32) \
            / din ** 0.5
        leaf = _quantize_leaf_int4(w)
        x = jax.random.normal(jax.random.PRNGKey(4), (8, din), jnp.bfloat16)
        got, m = run(lambda x, p, s: q4_matmul(x, p, s),
                     x, leaf[Q4KEY], leaf["scale"])
        record("q4_matmul", err(got, x.astype(f32) @ _dequant_int4(leaf, f32)),
               0.05, m)
        return out


def train_smoke(spec: dict) -> dict:
    """A few ``make_train_step`` steps on one repeated batch; under a mesh
    (``--chips 4``) first unsharded on one device as the reference, then
    fsdp-sharded from the same seed."""
    import jax
    import jax.numpy as jnp
    import optax

    from kubetorch_tpu.models.llama import (LlamaConfig, llama_init,
                                            llama_loss_chunked)
    from kubetorch_tpu.train import init_train_state, make_train_step

    if spec["rehearse"]:
        cfg = LlamaConfig.tiny(max_seq_len=spec["seq"], attn_impl="flash",
                               remat=False, dtype=jnp.float32)
    else:
        sys.path.insert(0, HERE)
        from bench import BENCH_MODEL
        cfg = LlamaConfig(**BENCH_MODEL, remat=False)
    batch_n, seq, steps = spec["batch"], spec["seq"], spec["steps"]
    opt = optax.adamw(1e-4)

    def loss_fn(p, t, y):
        return llama_loss_chunked(p, t, y, cfg, chunk=256)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch_n, seq), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    def new_state():
        return init_train_state(llama_init(jax.random.PRNGKey(0), cfg), opt)

    def run(step, state, batch):
        compiled = _compile(step.jitted.lower(state, batch))
        losses, norms, secs = [], [], []
        for _ in range(steps):
            t = time.monotonic()
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
            norms.append(float(m["grad_norm"]))
            secs.append(round(time.monotonic() - t, 3))
        return {"compiled": compiled, "losses": losses, "grad_norms": norms,
                "step_s": secs}

    out = {"params": cfg.param_count(), "batch": batch_n, "seq": seq,
           "cache_entries_at_start": _cache_entries()}
    out["one_chip"] = run(make_train_step(loss_fn, optimizer=opt),
                          new_state(), batch)
    if spec.get("mesh"):
        import kubetorch_tpu as kt
        from kubetorch_tpu.parallel.sharding import LLAMA_RULES
        mesh = kt.distributed.mesh()
        step = make_train_step(loss_fn, optimizer=opt, mesh=mesh,
                               rules=LLAMA_RULES)
        state = step.shard_state(new_state())
        jax.block_until_ready(state)
        sharding = _quarter_per_device(state.params)
        sharding["mesh"] = dict(mesh.shape)
        sharding["mesh_devices"] = len(set(mesh.devices.flat))
        sharding["bytes_in_use_after_shard_state"] = \
            _device_report()["bytes_in_use"]
        out["sharding"] = sharding
        out["mesh"] = run(step, state, {
            k: jax.device_put(v, step.batch_sharding)
            for k, v in batch.items()})
    out.update(_device_report())
    return out


# ---------------------------------------------------------------------------
# The parent: never imports jax
# ---------------------------------------------------------------------------

class SmokeFailure(Exception):
    pass


def check(cond, what: str, detail=None) -> None:
    if not cond:
        raise SmokeFailure(f"{what}" + (f": {detail}" if detail is not None
                                        else ""))


def probe_accelerator(tag: str) -> None:
    """Fail at once when jax finds no TPU — in a child that exits (and gives
    the chip back) before the fabric starts. The rank's own check
    (``require_accelerator``) is what the run relies on; this only saves
    starting a controller to learn the same thing."""
    code = ("import jax, json; d = jax.devices(); "
            "print(json.dumps([d[0].platform, d[0].device_kind, len(d)]))")
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=180, cwd=HERE)
        platform = json.loads(r.stdout.strip().splitlines()[-1])[0] \
            if r.returncode == 0 else None
    except (subprocess.TimeoutExpired, ValueError, IndexError):
        platform, r = None, None
    if platform != "tpu":
        tail = (r.stderr.strip().splitlines()[-3:] if r is not None else [])
        print(f"{tag}no TPU: jax came up on {platform!r} in this "
              f"environment; chip_smoke.py proves the chip and has no CPU "
              f"fallback (--rehearse is a labelled CPU rehearsal)\n"
              + "\n".join(tail), file=sys.stderr)
        sys.exit(1)


def wait_pid_gone(pid: int, what: str, timeout: float = 120.0) -> float:
    """One process per chip: the next phase starts only once this one is
    gone."""
    import psutil
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if not psutil.pid_exists(pid) or \
                psutil.Process(pid).status() == psutil.STATUS_ZOMBIE:
            return round(time.monotonic() - t0, 2)
        time.sleep(0.2)
    raise SmokeFailure(f"{what} (pid {pid}) still alive {timeout:.0f}s after "
                       "teardown: the chip is not free")


def unwrap(result, distributed: bool):
    """``.distribute`` calls answer with one result per rank; one rank
    process drives all the host's chips, so there is exactly one."""
    if not distributed:
        return result
    check(isinstance(result, list) and len(result) == 1,
          "one rank process per host", result if not isinstance(result, list)
          else len(result))
    return result[0]


def make_prompts(vocab: int, lens):
    import random
    rng = random.Random(0)
    prompts = [[rng.randrange(1, vocab) for _ in range(n)] for n in lens]
    prompts.append(list(prompts[0]))           # the repeated prompt
    return prompts


def serving_phase(kt, args, env, report, say) -> dict:
    rehearse, four = args.rehearse, args.chips == 4
    spec = {"rehearse": rehearse, "slots": 8, "decode_block": 8,
            "max_len": 256 if rehearse else 2048,
            "buckets": [16, 64] if rehearse else [128, 512],
            "mesh": {"tensor": 4} if four else None}
    vocab = 512 if rehearse else 128256
    lens = [16, 10, 25, 40, 50, 60, 33, 64] if rehearse else \
        [128, 100, 200, 300, 400, 500, 250, 450]
    prompts = make_prompts(vocab, lens)
    compute = kt.Compute(tpu=f"v5e-{args.chips}", env=env,
                         launch_timeout=900)
    if four:
        compute = compute.distribute("jax", mesh=spec["mesh"])
    svc = kt.cls(SmokeEngine, name="smoke-engine",
                 init_kwargs={"spec": spec})
    t0 = time.monotonic()
    svc.to(compute)
    try:
        cold = unwrap(svc.report(), four)
        cold["deploy_to_ready_s"] = round(time.monotonic() - t0, 2)
        report["serving_cold"] = cold
        say(f"engine service ready in {cold['deploy_to_ready_s']}s on "
            f"{cold['platform']} {cold['device_kind']} x{cold['device_count']}"
            f" (rank pid {cold['pid']}); warm-up {cold['times']}")
        check_rank(cold, args, "serving rank")
        if not rehearse:
            check(sum(cold["bytes_in_use"]) >= cold["param_bytes"],
                  "weights are on the device", cold["bytes_in_use"])
            for name, c in cold["compiled"].items():
                check(c["mosaic_calls"] > 0,
                      f"{name} contains a Mosaic custom call", c)
        if four:
            # device 0 also keeps the unsharded one-chip reference copy
            check_sharding(cold["sharding"])
            check(cold["compiled"]["decode_block"]["collectives"]
                  ["all-reduce"] > 0, "tensor-parallel decode all-reduces",
                  cold["compiled"]["decode_block"])

        # overlapping generate calls over HTTP, one thread each
        outs, errs = [None] * len(prompts), []

        def call(i):
            try:
                outs[i] = unwrap(svc.generate(prompts[i], NEW_TOKENS), four)
            except Exception as e:  # noqa: BLE001 — reported below
                errs.append(f"request {i}: {type(e).__name__}: {e}")
        t0 = time.monotonic()
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        check(not errs and not any(t.is_alive() for t in threads),
              "every generate call returned", errs)
        report["requests"] = {"n": len(prompts), "prompt_lens": lens,
                              "wall_s": round(time.monotonic() - t0, 2),
                              "tokens": [len(o) for o in outs]}
        say(f"{len(prompts)} overlapping requests answered in "
            f"{report['requests']['wall_s']}s")
        check(all(len(o) == NEW_TOKENS and all(0 <= t < vocab for t in o)
                  for o in outs), f"every call returned {NEW_TOKENS} tokens",
              report["requests"]["tokens"])
        check(outs[0] == outs[-1], "a repeated prompt returns the same tokens")

        ref = unwrap(svc.reference_check(prompts[0], outs[0]), four)
        report["reference"] = ref
        say(f"engine vs generate/llama_forward: {ref}")
        check(ref["ok"], "engine tokens agree with the reference", ref)
        if rehearse:
            check(ref["equals_generate"], "engine tokens equal generate's "
                  "(bitwise on the CPU)", ref)
        if four:
            one = unwrap(svc.one_chip_tokens(prompts[:2]), four)
            same = [a == b for a, b in zip(one, outs[:2])]
            report["four_vs_one_chip_tokens_equal"] = same
            say(f"four-chip tokens equal the one-chip engine's: {same}")
            # parted streams are judged by the same reference as above
            for p, o in zip(prompts[:2], one):
                r1 = unwrap(svc.reference_check(p, o), four)
                check(r1["ok"], "one-chip tokens agree with the reference",
                      r1)

        kern = unwrap(svc.kernel_checks(), four)
        report["kernels"] = kern
        say("kernels: " + ", ".join(
            f"{k} err={v['err']} mosaic={v['mosaic']}"
            for k, v in kern.items()))
        bad = {k: v for k, v in kern.items() if not v["ok"]}
        check(not bad, "every Pallas entry point compiled by Mosaic and "
              "matched its reference", bad)

        # hot reload through the fabric's own path: the rank is respawned
        # and its warm-up must come out of the compile cache
        t0 = time.monotonic()
        svc.to(compute)
        warm = unwrap(svc.report(), four)
        warm["deploy_to_ready_s"] = round(time.monotonic() - t0, 2)
        report["serving_reloaded"] = warm
        say(f"reloaded in {warm['deploy_to_ready_s']}s (rank pid "
            f"{warm['pid']}); warm-up {warm['times']}; cache entries "
            f"{warm['cache_entries_at_start']} -> "
            f"{warm['cache_entries_after_warmup']}")
        check(warm["pid"] != cold["pid"], "the reload respawned the rank")
        check_rank(warm, args, "reloaded rank")
        check(warm["cache_entries_at_start"] > 0
              and warm["cache_entries_after_warmup"]
              == warm["cache_entries_at_start"],
              "the reloaded warm-up added no compile-cache entries", warm)
        if not rehearse:      # tiny CPU compiles cost what a cache read does
            check(warm["times"]["compile_s"] < cold["times"]["compile_s"],
                  "the reloaded warm-up compiled faster than the cold one",
                  (cold["times"], warm["times"]))
        again = unwrap(svc.generate(prompts[0], NEW_TOKENS), four)
        check(again == outs[0], "the reloaded engine returns the same tokens")
        return warm
    finally:
        svc.teardown()


def training_phase(kt, args, env, report, say) -> dict:
    rehearse, four = args.rehearse, args.chips == 4
    spec = {"rehearse": rehearse, "steps": 3, "batch": 4,
            "seq": 128 if rehearse else 2048,
            "mesh": {"fsdp": 4} if four else None}
    compute = kt.Compute(tpu=f"v5e-{args.chips}", env=env,
                         launch_timeout=900)
    if four:
        compute = compute.distribute("jax", mesh=spec["mesh"])
    fn = kt.fn(train_smoke, name="smoke-train")
    fn.to(compute)
    try:
        t0 = time.monotonic()
        out = unwrap(fn(spec), four)
        out["call_s"] = round(time.monotonic() - t0, 2)
        report["training"] = out
        one = out["one_chip"]
        say(f"train step on {out['platform']} {out['device_kind']} "
            f"x{out['device_count']} (rank pid {out['pid']}): "
            f"{out['params']:,} params, batch {out['batch']} x {out['seq']}; "
            f"compile {one['compiled']['compile_s']}s, steps {one['step_s']}, "
            f"losses {one['losses']}, grad norms {one['grad_norms']}")
        check_rank(out, args, "training rank")
        runs = [("one chip", one)] + ([("fsdp=4", out["mesh"])] if four
                                      else [])
        for name, r in runs:
            losses = r["losses"]
            check(len(losses) >= 3 and all(x == x and abs(x) < 1e4
                                           for x in losses),
                  f"{name}: {len(losses)} steps with finite loss", losses)
            check(losses[-1] < losses[0], f"{name}: the loss falls", losses)
            check(all(g > 0 for g in r["grad_norms"]),
                  f"{name}: non-zero grad norm", r["grad_norms"])
            if not rehearse:
                # flash forward, dq and dk/dv
                check(r["compiled"]["mosaic_calls"] >= 3,
                      f"{name}: the train step contains the flash forward "
                      "and backward Mosaic calls", r["compiled"])
        if four:
            m = out["mesh"]
            say(f"fsdp=4: compile {m['compiled']['compile_s']}s, steps "
                f"{m['step_s']}, losses {m['losses']}; "
                f"sharding {out['sharding']}")
            check_sharding(out["sharding"],
                           out["sharding"]["bytes_in_use_after_shard_state"])
            c = m["compiled"]["collectives"]
            check(c["all-gather"] > 0 and
                  c["reduce-scatter"] + c["all-reduce"] > 0,
                  "the fsdp step gathers params and reduces grads", c)
            gaps = [abs(a - b) for a, b in zip(m["losses"], one["losses"])]
            report["four_vs_one_chip_loss_gaps"] = gaps
            check(max(gaps) <= 0.05, "fsdp=4 losses within 0.05 of the "
                  "one-chip run", gaps)
        return out
    finally:
        fn.teardown()


def check_rank(rep: dict, args, who: str) -> None:
    if args.rehearse:
        return
    check(rep["platform"] == "tpu", f"{who} reports platform tpu", rep)
    check(rep["device_count"] == args.chips,
          f"{who} sees {args.chips} device(s)", rep["device_count"])


def check_sharding(sh: dict, bytes_in_use=None) -> None:
    check(sh["mesh_devices"] == 4, "four devices in the mesh", sh)
    check(sh["ok"], "every sharded leaf holds a quarter per device on four "
          "distinct devices", sh)
    if bytes_in_use and all(b is not None for b in bytes_in_use):
        check(max(bytes_in_use) <= 1.2 * min(bytes_in_use),
              "per-device bytes in use within 20% (nothing parked on "
              "device 0)", bytes_in_use)


def print_logs(say) -> None:
    """Pods and the daemon write to files under the smoke's config dir; on
    failure they are the only place a rank that could not open the chip
    said so."""
    logs = [os.path.join(OUT, "kt", "local-controller.log")]
    pod_dir = os.path.join(OUT, "kt", "logs")
    if os.path.isdir(pod_dir):
        logs += sorted(os.path.join(pod_dir, f) for f in os.listdir(pod_dir))
    for path in logs:
        try:
            with open(path, errors="replace") as f:
                # XLA:CPU's cache loader writes pages per entry (rehearsals)
                tail = "".join(line for line in f
                               if "cpu_aot_loader.cc" not in line)[-6000:]
        except OSError:
            continue
        say(f"---- {os.path.relpath(path, HERE)} (tail) ----\n{tail}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny shapes; not a chip result")
    args = ap.parse_args(argv)
    tag = "[REHEARSAL on the CPU — not a chip result] " if args.rehearse \
        else ""

    def say(msg: str) -> None:
        print(f"{tag}{msg}", flush=True)

    assert "jax" not in sys.modules, "the parent must stay off jax"
    try:
        import kubetorch_tpu as kt
        from kubetorch_tpu.client import shutdown_local_controller
    except ImportError as e:
        print(f"{tag}chip_smoke.py drives the checkout it sits in, and there "
              f"is none here: {e}", file=sys.stderr)
        return 1
    if not args.rehearse:
        probe_accelerator(tag)
    # inside the checkout, fresh each run; chiprun brings it back
    import shutil
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(os.path.join(OUT, "kt"))
    os.environ.update({
        "KT_CONFIG_DIR": os.path.join(OUT, "kt"),
        "KT_CONFIG_PATH": os.path.join(OUT, "kt", "config"),
        "KT_LOCAL_MODE": "1", "KT_USERNAME": "smoke",
        "KT_STREAM_LOGS": "0", "KT_CONTROLLER_REPLACE": "always",
    })
    # every compile lands in the cache, so a second warm-up adding entries
    # can only mean a key that moved
    env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    if args.rehearse:
        # an explicit Compute(env=...) wins over the backend's device rule
        env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={args.chips}"})
    def out_of_time(*_):
        raise SmokeFailure("time limit reached")
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(3300 if args.chips == 4 else 1100)

    report = {"chips": args.chips, "rehearsal": args.rehearse}
    t_start = time.monotonic()
    ok = False
    try:
        served = serving_phase(kt, args, env, report, say)
        # the chip is free again only once that rank is gone
        report["serving_rank_exit_s"] = wait_pid_gone(
            served["pid"], "the serving rank")
        say(f"serving rank exited {report['serving_rank_exit_s']}s after "
            "teardown")
        trained = training_phase(kt, args, env, report, say)
        wait_pid_gone(trained["pid"], "the training rank")
        check("jax" not in sys.modules, "the parent never imported jax")
        ok = True
    except BaseException as e:  # noqa: BLE001 — report, clean up, exit 1
        say(f"FAILED: {type(e).__name__}: {e}")
        print_logs(say)
    finally:
        signal.alarm(0)
        try:
            shutdown_local_controller()
        except Exception as e:  # noqa: BLE001
            say(f"controller shutdown: {e}")
    report["ok"] = ok
    report["wall_s"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT, "report.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    if not ok:
        return 1
    dev = report["training"]
    say(f"all phases passed in {report['wall_s']}s; full report in "
        f"{os.path.relpath(OUT, HERE)}/report.json")
    result = {"ok": True, "device": {"platform": dev["platform"],
                                     "kind": dev["device_kind"],
                                     "count": dev["device_count"]}}
    if args.rehearse:
        result = {"ok": True, "rehearsal": True, "device": result["device"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

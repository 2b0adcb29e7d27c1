"""Test payload callables (model: reference tests/utils.py — summer,
SlowNumpyArray, memory consumers, sleep_forever)."""

import os
import time


def summer(a, b):
    return a + b


def echo_env(*names):
    return {n: os.environ.get(n) for n in names}


def whoami():
    return {"pid": os.getpid(),
            "rank": os.environ.get("RANK"),
            "world_size": os.environ.get("WORLD_SIZE"),
            "local_rank": os.environ.get("LOCAL_RANK"),
            "node_rank": os.environ.get("NODE_RANK"),
            "pod_ips": os.environ.get("POD_IPS")}


def boomer(msg="kaboom"):
    raise ValueError(msg)


def sleeper(seconds):
    time.sleep(seconds)
    return seconds


def jax_matmul(n=8):
    import jax
    import jax.numpy as jnp

    x = jnp.ones((n, n))
    return float(jnp.sum(x @ x)), jax.device_count()


class Counter:
    def __init__(self, start=0):
        self.value = start

    def increment(self, by=1):
        self.value += by
        return self.value

    def get(self):
        return self.value

    def _private(self):  # must NOT be exposed remotely
        return "hidden"


def torch_allreduce():
    """Proves the PyTorchEnv contract: torch.distributed gloo init from the
    injected MASTER_ADDR/RANK/WORLD_SIZE env, one allreduce."""
    import torch
    import torch.distributed as dist

    if not dist.is_initialized():
        dist.init_process_group("gloo")
    t = torch.tensor([float(dist.get_rank() + 1)])
    dist.all_reduce(t)
    return {"rank": dist.get_rank(), "world": dist.get_world_size(),
            "sum": float(t.item())}


class Warmable:
    """Exercises the __kt_warmup__ hook: the worker must run it at eager
    load, before the first request arrives."""

    def __init__(self):
        self.warmed = False

    def __kt_warmup__(self):
        self.warmed = True

    def was_warmed(self):
        return self.warmed


class WarmupCrasher:
    """Worker suicide during warmup — the pod must never report ready."""

    def __kt_warmup__(self):
        import os
        os._exit(41)

    def ping(self):
        return "alive"


def shouter(msg):
    print(f"SHOUT:{msg}")
    return msg.upper()


class Metered:
    """Service exposing the __kt_metrics__ scrape hook."""

    def __init__(self):
        self.calls = 0

    def ping(self):
        self.calls += 1
        return self.calls

    def __kt_metrics__(self):
        return {"calls_total": self.calls,
                "queue depth!": 1.5,      # name needs prometheus sanitizing
                "not_a_number": "nope"}   # silently dropped


class ElasticTrainer:
    """Elastic SPMD stand-in (ISSUE 6): a numpy 'training loop' whose state
    rides the commit-marker checkpoint protocol. On construction it resumes
    from the last committed checkpoint when one exists (what a respawned
    rank pool does after an elastic resume); each step bumps the params and
    rank 0 commits; a drain request (SIGTERM grace window) flushes a fresh
    commit instead of stepping."""

    def __init__(self, store_url, key, every=1):
        import numpy as np

        from kubetorch_tpu.train.checkpoint import Checkpointer

        self.rank = int(os.environ.get("RANK", "0"))
        self.ckpt = Checkpointer(key, store_url=store_url, every=every)
        restored = self.ckpt.restore()   # every rank reads; only 0 writes
        if restored is not None:
            tree, step = restored
            self.params = tree["w"]
            self.step_no = step
            self.resumed_from = step
        else:
            self.params = np.zeros(8, np.float64)
            self.step_no = 0
            self.resumed_from = None

    def _report(self, **extra):
        from kubetorch_tpu.serving import elastic
        from kubetorch_tpu.train.checkpoint import tree_fingerprint

        return {"rank": self.rank, "step": self.step_no,
                "resumed_from": self.resumed_from,
                "world": os.environ.get("WORLD_SIZE"),
                "batch_scale": elastic.batch_scale(),
                "fingerprint": tree_fingerprint({"w": self.params}),
                **extra}

    def step(self, sleep_s=0.0):
        from kubetorch_tpu.serving import elastic

        if elastic.drain_requested():
            # cooperative drain: commit NOW, inside the grace window —
            # resume must lose zero completed steps
            if self.rank == 0:
                self.ckpt.flush()
                self.ckpt.save({"w": self.params}, self.step_no)
            return self._report(drained=True)
        if sleep_s:
            time.sleep(sleep_s)
        self.params = self.params + 1.0
        self.step_no += 1
        if self.rank == 0:
            self.ckpt.maybe_save({"w": self.params}, self.step_no)
            self.ckpt.flush()        # deterministic: commit lands per step
        return self._report()


def store_fetcher(store_url, key):
    """Fetch a store key from inside the rank worker (ISSUE 5 trace e2e:
    the worker-side store.fetch/store.request spans must join the HTTP
    request's trace via the call-envelope context)."""
    from kubetorch_tpu.data_store import commands as ds
    arr = ds.get(key, store_url=store_url)
    return float(arr.sum())


class EngineService:
    """A kt.cls that serves a tiny GenerationEngine (ISSUE 26): the call's
    ``engine.request`` event lands on the rank's ``worker.execute`` span and
    rides X-KT-Timing back to the caller."""

    def __init__(self):
        import jax
        import jax.numpy as jnp

        from kubetorch_tpu.models.llama import LlamaConfig, llama_init
        from kubetorch_tpu.serve import GenerationEngine

        cfg = LlamaConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               remat=False)
        self.engine = GenerationEngine(
            llama_init(jax.random.PRNGKey(0), cfg), cfg, slots=2,
            max_len=32, prefill_buckets=(8,), decode_block=2)

    def generate(self, prompt, max_new_tokens):
        self.engine.start()
        return self.engine.submit(
            prompt, max_new_tokens=max_new_tokens).result(timeout=120)

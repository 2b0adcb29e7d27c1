"""What the benchmark deploys: the system under test in its rank.

``ServeBench`` is a ``kt.cls`` service in the shape of
``examples/continuous_batching_service.py``: the program's
``GenerationEngine`` over weights made from the seed, with ``generate`` as
the entry the window drives. Around it, and not part of the timed path, sit
what only the process that holds the chip can do: read counters and device
memory, trace the device for a few seconds, and — once the window has closed
and the engine is gone — run the plain reference.

Imported by the parent process too (to hand the class to ``kt.cls``), which
must stay off jax: jax and the program are imported inside the methods.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

# jax.monitoring events that mean "a program was compiled or fetched now"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def program_config(cfg: dict, max_seq_len: int, **over):
    """The configuration file's sizes as the program's own dataclass."""
    from kubetorch_tpu.models.llama import LlamaConfig
    from kubetorch_tpu.models.moe import MoeConfig
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              n_layers=cfg["num_hidden_layers"],
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"],
              ffn_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
              rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"])
    kw.update(over)
    if cfg.get("num_local_experts"):
        return MoeConfig(n_experts=cfg["num_local_experts"],
                         experts_per_token=cfg["num_experts_per_tok"],
                         capacity_factor=cfg["capacity_factor"], **kw)
    return LlamaConfig(**kw)


def device_report() -> dict:
    import jax
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {"pid": os.getpid(), "platform": devs[0].platform,
            "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(
                (s.get("peak_bytes_in_use") or 0) for s in stats),
            "bytes_in_use": [s.get("bytes_in_use") for s in stats]}


class CompileCounter:
    """Times at which jax compiled a program or fetched one from its cache."""

    def __init__(self):
        import jax.monitoring
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in list(self.times))


class ServeBench:
    def __init__(self, spec: dict):
        import jax
        self.spec = spec
        self.cfg = spec["config"]
        self.compiles = CompileCounter()
        dev = device_report()
        if not spec.get("rehearse") and (dev["platform"] != "tpu"
                                         or dev["count"] < spec["chips"]):
            raise RuntimeError(
                f"the cell asks for {spec['chips']} TPU chip(s); jax found "
                f"{dev['count']} x {dev['platform']}: no CPU fallback")
        self.times = {}
        self.log = []                  # one record per answered request
        self._live = {}                # handles of the requests in flight
        self._cut = threading.Event()  # the window has closed
        self._cut_by = 0.0
        self._log_lock = threading.Lock()
        self.engine = None
        self.seed = None
        self._init = jax.jit(self._make_params)
        self.build(spec["seed"])

    def _make_params(self, root):
        import bench_weights as W
        return W.init_params(root, self.cfg)

    def build(self, seed: int) -> dict:
        """Weights from the seed in one jitted call, and a fresh engine. A
        second call (the limits tool reads many seeds in one deployment)
        frees the first engine's state before making the next."""
        import jax

        import bench_weights as W
        from kubetorch_tpu.serve import GenerationEngine

        if self.engine is not None:
            self._drop_engine()
        e = self.cfg["engine"]
        t = time.monotonic()
        self.params = self._init(W.root_key(seed))
        jax.block_until_ready(self.params)
        self.times["init_s"] = time.monotonic() - t
        self.pcfg = program_config(self.cfg, e["max_len"])
        self.engine = GenerationEngine(
            self.params, self.pcfg, slots=e["slots"], max_len=e["max_len"],
            prefill_buckets=tuple(e["prefill_buckets"]),
            decode_block=e["decode_block"])
        self.seed = seed
        self._cut.clear()
        with self._log_lock:
            self.log = []
        return self.times

    def _drop_engine(self):
        self.engine.stop()
        self.engine = None
        self.params = None

    def __kt_warmup__(self):
        """Every shape the cell's traffic uses, and no other: one request
        per prefill bucket, which also runs the decode block and the cache
        splice of that bucket."""
        t = time.monotonic()
        for b in self.cfg["engine"]["prefill_buckets"]:
            self.engine.generate([1] * b, max_new_tokens=2, timeout=1100)
        self.times["warmup_s"] = time.monotonic() - t

    # -- the timed entry ----------------------------------------------------

    def generate(self, prompt, max_new_tokens: int):
        t_in = time.monotonic()
        self.engine.start()
        h = self.engine.submit(prompt, max_new_tokens=max_new_tokens)
        with self._log_lock:
            self._live[h.request_id] = h
        try:
            if self._cut.is_set():     # sent before the close, here after it
                self._cut_one(h)
            toks = h.result(timeout=600)
        finally:
            with self._log_lock:
                del self._live[h.request_id]
        t_out = time.monotonic()
        ttft = h.time_to_first_token()
        rec = {"t_in": t_in, "t_out": t_out, "engine_ttft": ttft,
               "t_first": None if ttft is None else t_in + ttft,
               "prompt_len": len(prompt), "n": len(toks),
               "cut": len(toks) < max_new_tokens}
        with self._log_lock:
            self.log.append(rec)
        return {"tokens": toks, "logprobs": h.logprobs, **rec}

    # -- the window's two ends ------------------------------------------------

    def mark(self) -> dict:
        """The engine's counters with the time of the reading, read on the
        stepping thread between two decode blocks: every token counted has
        been made by ``now`` and none after it."""
        return self.engine.at_batch_boundary(self.counters, timeout=120)

    def _cut_one(self, h) -> None:
        while (h.time_to_first_token() is None and h.request_id in self._live
               and time.monotonic() < self._cut_by):
            time.sleep(0.005)
        h.cancel()

    def cut(self, wait_s: float) -> int:
        """The window has closed. Every request still in flight is waited
        for until its first token is out (``wait_s`` at most: a late one is
        late, not wrong) and then cancelled: its reply carries the tokens
        made so far. Its time to first token counts; the reference is run
        over requests that finished."""
        self._cut_by = time.monotonic() + wait_s
        self._cut.set()
        with self._log_lock:
            live = list(self._live.values())
        for h in live:
            self._cut_one(h)
        return len(live)

    # -- counters, memory, trace: beside the timed path ---------------------

    def counters(self) -> dict:
        s = self.engine.stats()
        return {"now": time.monotonic(), "slots": s.slots,
                "tokens_generated": s.tokens_generated,
                "decode_steps": s.decode_steps,
                "admitted_total": s.admitted_total,
                "finished_total": s.finished_total}

    def report(self) -> dict:
        return {**device_report(), "times": self.times,
                "counters": self.counters(),
                "compile_events": len(self.compiles.times),
                "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}

    def compiles_between(self, t0: float, t1: float) -> int:
        return self.compiles.between(t0, t1)

    def trace(self, seconds: float, queries: list):
        """Trace the device for ``seconds`` of the running window and reduce
        the trace here: only this process can. Returns the summary and the
        engine's counters at both ends; the trace's files are deleted."""
        import jax

        import trace_reduce

        d = os.path.join(self.spec["run_dir"], "trace")
        shutil.rmtree(d, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        c0 = self.mark()
        time.sleep(seconds)
        c1 = self.mark()
        jax.profiler.stop_trace()
        t = time.monotonic()
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        out = trace_reduce.reduce_planes(trace_reduce.read_planes(path),
                                         queries)
        out["trace_bytes"] = os.path.getsize(path)
        shutil.rmtree(d, ignore_errors=True)
        out.update({"c0": c0, "c1": c1, "reduce_s": time.monotonic() - t})
        return out

    # -- after the window ---------------------------------------------------

    def finish(self, sample: list, t_pad: int, names: list, control=False,
               keep_positions=False):
        """Read the peak, free the program's state, then run the reference
        over the sampled requests (``[{prompt, tokens, logprobs}]``) and
        read the numbers ``names`` of the comparison. ``control`` and
        ``keep_positions``: only the tool that sets limits asks
        (``bench_reference.compare``)."""
        import bench_reference as R
        dev = device_report()
        counters = self.counters()
        self._drop_engine()
        t = time.monotonic()
        out = R.compare(self.seed, self.cfg,
                        [(s["prompt"], s["tokens"]) for s in sample],
                        [s["logprobs"] for s in sample], t_pad, names,
                        control=control, keep_positions=keep_positions)
        out["reference_s"] = time.monotonic() - t
        # every request's record: the traced run's readers need those that
        # were still in flight when the trace ended
        return {"device": dev, "counters": counters, "check": out,
                "log": self.log}

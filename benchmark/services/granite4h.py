"""The service of ``granite-4.0-h-micro``: ``bench_service.ServeBench`` over
the program's hybrid Mamba-2 / attention decoder
(``kubetorch_tpu.models.hybrid``), with this configuration's seeded weights,
its copy of the reference, and the recurrent state's tally beside the
engine's counters.

Loaded by the benchmark's parent process before any deploy
(``runners/serve.py:deploy``), which stays off jax: whether the program of
this checkout HAS the family is looked up here, from its files, so that a
program without it fails at once and not at the end of a launch.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

_pkg = importlib.util.find_spec("kubetorch_tpu")
if _pkg is None or not any(
        os.path.exists(os.path.join(d, "models", "hybrid.py"))
        for d in (_pkg.submodule_search_locations or ())):
    raise ImportError(
        "this checkout's program has no kubetorch_tpu/models/hybrid.py: it "
        "cannot run a hybrid Mamba-2 / attention configuration")

from bench_service import ServeBench  # noqa: E402


def program_config(cfg: dict, **over):
    """The configuration file's sizes as the program's own dataclass. What
    the program does not implement is refused by the reference's
    ``model_key`` and by the dataclass itself."""
    from kubetorch_tpu.models.hybrid import HybridConfig
    import bench_reference_hybrid as R
    R.model_key(cfg)
    kw = dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"], mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"], mamba_expand=cfg["mamba_expand"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        position_embedding_type=cfg["position_embedding_type"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"])
    kw.update(over)
    return HybridConfig(**kw)


class Granite4hServeBench(ServeBench):
    def _make_params(self, root):
        import bench_weights_hybrid as W
        return W.init_params(root, self.cfg)

    def build(self, seed: int) -> dict:
        """``ServeBench.build`` with this family's configuration class."""
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
        self.pcfg = program_config(self.cfg)
        self.engine = GenerationEngine(
            self.params, self.pcfg, slots=e["slots"], max_len=e["max_len"],
            prefill_buckets=tuple(e["prefill_buckets"]),
            decode_block=e["decode_block"])
        self.seed = seed
        self._cut.clear()
        with self._log_lock:
            self.log = []
        return self.times

    def counters(self) -> dict:
        """The engine's counters and, read at the same batch boundary, the
        live slots whose recurrent state the decode steps advanced
        (``EngineStats.ssm_state_slot_steps``)."""
        out = super().counters()
        out["ssm_state_slot_steps"] = self.engine.stats().ssm_state_slot_steps
        return out

    def finish(self, sample: list, t_pad: int, names: list, control=False,
               keep_positions=False):
        """``ServeBench.finish`` over this configuration's reference."""
        import bench_reference_hybrid as R
        from bench_service import device_report
        dev = device_report()
        counters = self.counters()
        self._drop_engine()
        t = time.monotonic()
        out = R.compare(self.seed, self.cfg,
                        [(s["prompt"], s["tokens"]) for s in sample],
                        [s["logprobs"] for s in sample], t_pad, names,
                        control=control, keep_positions=keep_positions)
        out["reference_s"] = time.monotonic() - t
        return {"device": dev, "counters": counters, "check": out,
                "log": self.log}

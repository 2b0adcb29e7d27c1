"""The service of ``kimi-vl-a3b-l9``: ``bench_service.ServeBench`` over the
program's latent-attention decoder (``kubetorch_tpu.models.mla``), with this
configuration's seeded weights, its copy of the reference, and the expert
layers' routing tally beside the engine's counters.

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
        os.path.exists(os.path.join(d, "models", "mla.py"))
        for d in (_pkg.submodule_search_locations or ())):
    raise ImportError(
        "this checkout's program has no kubetorch_tpu/models/mla.py: it "
        "cannot run a latent-attention (MLA) configuration")

from bench_service import ServeBench  # noqa: E402


def program_config(cfg: dict, max_seq_len: int, **over):
    """The configuration file's sizes as the program's own dataclass."""
    from kubetorch_tpu.models.mla import MlaMoeConfig
    kw = dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], ffn_dim=cfg["intermediate_size"],
        moe_ffn_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        first_dense_layers=cfg["first_k_dense_replace"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        scoring_func=cfg["scoring_func"], topk_method=cfg["topk_method"],
        max_seq_len=max_seq_len, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"])
    kw.update(over)
    return MlaMoeConfig(**kw)


class KimiVLServeBench(ServeBench):
    def _make_params(self, root):
        import bench_weights_mla_moe as W
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

    def counters(self) -> dict:
        """The engine's counters and, read at the same batch boundary, the
        expert layers' routing tally (``EngineStats.moe_routed_pairs`` /
        ``moe_expert_hits``, each (L_moe, E))."""
        out = super().counters()
        s = self.engine.stats()
        out["moe_routed_pairs"] = s.moe_routed_pairs.tolist()
        out["moe_expert_hits"] = s.moe_expert_hits.tolist()
        return out

    def finish(self, sample: list, t_pad: int, names: list, control=False,
               keep_positions=False):
        """``ServeBench.finish`` over this configuration's reference."""
        import bench_reference_mla_moe as R
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

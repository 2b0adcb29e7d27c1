"""The service of ``trinity-mini-l5``: ``bench_service.ServeBench`` over the
program's decoder of window and full attention layers with fine-grained
experts (``kubetorch_tpu.models.afmoe``), with this configuration's seeded
weights, its copy of the reference, and the device tallies of its expert
layers and its two kinds of attention beside the engine's counters.

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
        os.path.exists(os.path.join(d, "models", "afmoe.py"))
        for d in (_pkg.submodule_search_locations or ())):
    raise ImportError(
        "this checkout's program has no kubetorch_tpu/models/afmoe.py: it "
        "cannot run a configuration of window and full attention layers")

from bench_service import ServeBench  # noqa: E402

# what the counters carry of ``EngineStats`` beside the engine's own
TALLIES = ("moe_routed_pairs", "moe_expert_hits", "window_rows_read",
           "full_rows_read", "window_pairs_prefilled", "full_pairs_prefilled")


def program_config(cfg: dict, **over):
    """The configuration file's sizes as the program's own dataclass. What
    the program does not implement is refused by the reference's
    ``model_key`` and by the dataclass itself."""
    from kubetorch_tpu.models.afmoe import AfmoeConfig
    import bench_reference_afmoe as R
    R.model_key(cfg)
    kw = dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_dim=cfg["intermediate_size"],
        moe_ffn_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["num_shared_experts"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        sliding_window=cfg["sliding_window"],
        route_scale=float(cfg["route_scale"]), route_norm=cfg["route_norm"],
        score_func=cfg["score_func"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], mup_enabled=cfg["mup_enabled"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"])
    kw.update(over)
    return AfmoeConfig(**kw)


class TrinityServeBench(ServeBench):
    def _make_params(self, root):
        import bench_weights_afmoe as W
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
        """The engine's counters and, read at the same batch boundary, what
        its decode steps tallied on the device and its admissions counted:
        the routing tally (``moe_routed_pairs`` / ``moe_expert_hits``, each
        (L_moe, E)), the K/V rows the window layers and the full layer read,
        and the query-key pairs the prompts' two kinds of layer attended."""
        out = super().counters()
        s = self.engine.stats()
        for name in TALLIES:
            value = getattr(s, name)
            out[name] = value.tolist() if hasattr(value, "tolist") else value
        return out

    def finish(self, sample: list, t_pad: int, names: list, control=False,
               keep_positions=False):
        """``ServeBench.finish`` over this configuration's reference."""
        import bench_reference_afmoe as R
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

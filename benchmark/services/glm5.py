"""The service of ``glm-5-ep16-l6``: ``bench_service.ServeBench`` over the
program's latent-attention decoder (``kubetorch_tpu.models.mla``) with a
query rank, a sparse-attention indexer and a share of the routed experts,
this configuration's seeded weights, its copy of the reference, and the
routing tally and the selection's row counters beside the engine's counters.

Loaded by the benchmark's parent process before any deploy
(``runners/serve.py:deploy``), which stays off jax: whether the program of
this checkout KNOWS an indexer is looked up here, from its files, so that a
program without one fails at once and not at the end of a launch.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


def _program_knows_an_indexer() -> bool:
    pkg = importlib.util.find_spec("kubetorch_tpu")
    for d in (pkg.submodule_search_locations or ()) if pkg else ():
        path = os.path.join(d, "models", "mla.py")
        if os.path.exists(path):
            with open(path) as f:
                return "index_n_heads" in f.read()
    return False


if not _program_knows_an_indexer():
    raise ImportError(
        "this checkout's kubetorch_tpu/models/mla.py knows no sparse-"
        "attention indexer (index_n_heads): it cannot run a glm_moe_dsa "
        "configuration")

from bench_service import ServeBench, device_report  # noqa: E402


def program_config(cfg: dict, max_seq_len: int, **over):
    """The configuration file's sizes as the program's own dataclass: the
    router keeps its published width, the layer holds ``n_routed_experts``
    of them from ``held_first`` on."""
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
        n_experts=cfg["router_width"],
        held=(cfg["held_first"], cfg["n_routed_experts"]),
        experts_per_token=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        first_dense_layers=cfg["first_k_dense_replace"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        scoring_func=cfg["scoring_func"], topk_method=cfg["topk_method"],
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"])
    kw.update(over)
    return MlaMoeConfig(**kw)


class Glm5ServeBench(ServeBench):
    def _make_params(self, root):
        import bench_weights_dsa_moe as W
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
        """The engine's counters and, read at the same batch boundary, what
        its decode steps tallied on the device: the routing tally over the
        held experts (``moe_routed_pairs`` / ``moe_expert_hits``, each
        (L_moe, E held)) and the rows the selection scored and selected
        (``dsa_rows_scored`` / ``dsa_rows_selected``, each (L,))."""
        out = super().counters()
        s = self.engine.stats()
        for name in ("moe_routed_pairs", "moe_expert_hits",
                     "dsa_rows_scored", "dsa_rows_selected"):
            out[name] = getattr(s, name).tolist()
        return out

    def finish(self, sample: list, t_pad: int, names: list, control=False,
               keep_positions=False):
        """``ServeBench.finish`` over this configuration's reference."""
        import bench_reference_dsa_moe as R
        dev = device_report()
        counters = self.counters()
        self._drop_engine()
        t = time.monotonic()
        out = R.compare(self.seed, self.cfg,
                        [(s["prompt"], s["tokens"]) for s in sample],
                        [s["logprobs"] for s in sample], t_pad, names,
                        control=control, keep_positions=keep_positions)
        out["reference_s"] = time.monotonic() - t
        if "swapped_keys" in out:
            # the limits tool prints the numbers it knows; this reading is
            # left beside the run's logs for whoever sets the limits
            import json
            with open(os.path.join(self.spec["run_dir"],
                                   "swapped_keys.jsonl"), "a") as f:
                f.write(json.dumps({"seed": self.seed,
                                    **out["swapped_keys"]}) + "\n")
        return {"device": dev, "counters": counters, "check": out,
                "log": self.log}

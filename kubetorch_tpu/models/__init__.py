"""Model families: Llama (flagship), Mixtral-style MoE, a latent-attention
(MLA) decoder with fine-grained experts, ViT, ResNet, MLP.

The reference ships no models (it is a dispatch fabric; models live in user
code). This framework makes the headline workloads (BASELINE.md configs 1-5)
first-class so `kt.fn(train).to(kt.Compute(tpu=...))` has batteries included,
each designed mesh-first: params are plain pytrees annotated by
``parallel.ShardingRules`` and every forward is jit/GSPMD-friendly (static
shapes, scanned layers, no data-dependent Python control flow).

``models.mla`` (imported where it is used, never from here: a rank that
serves another family imports nothing of it) is the language model of
Kimi-VL-A3B-Instruct (https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/
blob/main/config.json), text only: ``MlaMoeConfig``, ``mla_moe_init``,
``mla_moe_forward``; served by ``serve.GenerationEngine`` over latent cache
rows (``serve.latent_cache``). What that cache kind does not carry yet —
int8 rows, chunked prefill, the prefix store, LoRA on the projections, an
AOT cache, a sharded mesh, speculative decoding, the scanned ``generate`` —
raises ``exceptions.UnsupportedMechanismError`` naming the mechanism, as do
``n_group``/``topk_group`` other than 1 and a low-rank query in the config.
"""

from .llama import LlamaConfig, llama_init, llama_forward, llama_loss
from .lora import LoraConfig, lora_init, lora_loss, merge_lora
from .vit import VitConfig, vit_init, vit_forward, vit_loss


def load_hf(path: str, **config_overrides):
    """HF checkpoint dir → ``(params, cfg)`` (lazy import: torch/transformers
    only load when a checkpoint is actually converted)."""
    from .convert_hf import load_hf as _load
    return _load(path, **config_overrides)


def save_hf(params, cfg, path: str) -> None:
    """Our pytree → HF ``save_pretrained`` dir (the reverse trip)."""
    from .convert_hf import save_hf as _save
    return _save(params, cfg, path)


__all__ = ["LlamaConfig", "llama_init", "llama_forward", "llama_loss",
           "LoraConfig", "lora_init", "lora_loss", "merge_lora",
           "VitConfig", "vit_init", "vit_forward", "vit_loss", "load_hf",
           "save_hf"]

"""TPU Pallas kernels for the hot ops.

Only ops where XLA's automatic fusion is insufficient get kernels: attention
(blockwise flash, ring) — the O(S²) memory/bandwidth monster. RMSNorm, RoPE,
SwiGLU are left to XLA, which fuses elementwise chains into the surrounding
matmuls better than a hand kernel would (verified against the fallback in
benchmarks before adding any kernel here).
"""

from .attention import flash_attention, flash_auto, flash_supported

__all__ = ["flash_attention", "flash_auto", "flash_supported"]

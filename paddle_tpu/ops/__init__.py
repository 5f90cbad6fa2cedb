"""paddle_tpu.ops — Pallas TPU kernels for the hot ops.

The reference ships hand-written CUDA kernels for these (phi/kernels/
fusion/gpu/, external FlashAttention-2); here each is a Pallas kernel
tiled for MXU/VMEM with a custom VJP, plus an interpret-mode path so
the same kernel code runs (and is tested) on CPU.
"""
from __future__ import annotations

from .flash_attention import flash_attention as flash_attention_fused  # noqa: F401
from .flash_attention import flash_attention_fwd  # noqa: F401

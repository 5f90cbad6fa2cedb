"""Operations and bytes of the SmallThinker family, computed from shapes
(conventions as ``shapes.py``: a matmul of [m, k] by [k, n] is 2*m*k*n
FLOPs, training is 3x the forward's matmul FLOPs, NO recomputation is
counted, the embedding lookup does no FLOPs).

Every block attends — inside a sliding window or over everything before
— and routes: a token meets the four projections of plain GQA attention
(q, k, v, o: no gate), the router, and of its ``top_k`` chosen ReGLU
experts the share that is held here (``top_k * held / published`` visits
under an even routing; there is no shared expert). Norms, RoPE, the
softmax and the ReLU gate are vector work and are not counted. The
window's pairs, the attention's FLOPs over the blocks as they are (12*d a
visible pair, head and block) and the grouped matmuls' bounds are
``shapes_afmoe``'s: the same kernels do the same work.
"""
from __future__ import annotations

from typing import Dict

from .shapes_afmoe import (  # noqa: F401 (the same kernels, the same work)
    attention_flops_per_token, expert_visits_per_token,
    flash_window_bwd_flops, flash_window_fwd_flops, gmm_bound_seconds,
    held_gmm_calls, window_pairs)


def attention_params(z: Dict) -> int:
    """The four projections of a block's attention (q, k, v, o)."""
    h, q, k = (z["hidden"], z["q_heads"] * z["head_dim"],
               z["kv_heads"] * z["head_dim"])
    return 2 * h * q + 2 * h * k


def block_matmul_params_met(z: Dict) -> float:
    """Matmul parameters a token meets in one block."""
    h = z["hidden"]
    return (attention_params(z) + h * z["published_experts"]
            + expert_visits_per_token(z) * 3 * h * z["expert_inner"])


def matmul_params_met(z: Dict) -> float:
    return (len(z["layer_kinds"]) * block_matmul_params_met(z)
            + z["hidden"] * z["vocab_rows"])


def train_flops_per_token(z: Dict, seq: int) -> float:
    return 6.0 * matmul_params_met(z) + attention_flops_per_token(z, seq)

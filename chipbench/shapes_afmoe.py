"""Operations and bytes of the AFMoE family, computed from shapes
(conventions as ``shapes.py``: a matmul of [m, k] by [k, n] is 2*m*k*n
FLOPs, training is 3x the forward's matmul FLOPs, NO recomputation is
counted, the embedding lookup does no FLOPs).

The blocks differ in kind: attention over a sliding window or over
everything before, a dense or a routed MLP. A token of a routed block
meets the shared expert, the router, and of its ``top_k`` chosen experts
the share that is held here: ``top_k * held / published`` expert visits
a token under an even routing. Norms, RoPE, the softmax and the gates
are vector work and are not counted.
"""
from __future__ import annotations

from typing import Dict, Optional

from . import shapes


def window_pairs(seq: int, window: Optional[int]) -> int:
    """(query, key) pairs inside the causal band of ``window`` keys (the
    query's own included): ``sum_t min(t + 1, window)``."""
    if window is None or window >= seq:
        return shapes.causal_pairs(seq)
    return window * (window + 1) // 2 + (seq - window) * window


def flash_window_fwd_flops(seq: int, heads: int, d: int,
                           window: Optional[int]) -> int:
    """QK^T and PV over the visible pairs: 4*d a pair and head."""
    return 4 * d * heads * window_pairs(seq, window)


def flash_window_bwd_flops(seq: int, heads: int, d: int,
                           window: Optional[int]) -> int:
    """dV, dP, dQ, dK over the visible pairs (the recomputed QK^T is not
    counted, as ``shapes.flash_bwd_flops``): 8*d a pair and head."""
    return 8 * d * heads * window_pairs(seq, window)


def attention_params(z: Dict) -> int:
    """The five projections of a block's attention (q, k, v, gate, o)."""
    h, q, k = (z["hidden"], z["q_heads"] * z["head_dim"],
               z["kv_heads"] * z["head_dim"])
    return 3 * h * q + 2 * h * k


def expert_visits_per_token(z: Dict) -> float:
    """Held-expert visits a token makes in one routed block under an even
    routing."""
    return z["top_k"] * z["experts"] / z["published_experts"]


def block_matmul_params_met(z: Dict, routed: bool) -> float:
    """Matmul parameters a token meets in one block."""
    h = z["hidden"]
    if not routed:
        return attention_params(z) + 3 * h * z["dense_inner"]
    return (attention_params(z) + h * z["published_experts"]
            + 3 * h * z["shared_inner"]
            + expert_visits_per_token(z) * 3 * h * z["expert_inner"])


def matmul_params_met(z: Dict) -> float:
    return (sum(block_matmul_params_met(z, routed)
                for _, routed in z["layer_kinds"])
            + z["hidden"] * z["vocab_rows"])


def attention_flops_per_token(z: Dict, seq: int) -> float:
    """Forward + backward attention FLOPs a token, over the blocks as
    they are: 12*d a visible pair, head and block."""
    return sum(flash_window_fwd_flops(seq, z["q_heads"], z["head_dim"], w)
               + flash_window_bwd_flops(seq, z["q_heads"], z["head_dim"], w)
               for w, _ in z["layer_kinds"]) / seq


def train_flops_per_token(z: Dict, seq: int) -> float:
    return 6.0 * matmul_params_met(z) + attention_flops_per_token(z, seq)


# -- the grouped matmuls of a block that holds a share -----------------------


def held_gmm_calls(z: Dict, rows: float):
    """The two shapes the grouped matmuls of a routed block take, on
    ``rows`` (token, choice) rows met by the held experts, as (t, k, n):
    gate-up and down. Forward, input gradient (``moe_gmm``) and weight
    gradient (``moe_tgmm``) all run on both."""
    h, f = z["hidden"], z["expert_inner"]
    return [(rows, h, 2 * f), (rows, f, h)]


def gmm_bound_seconds(t: float, k: int, n: int, groups: int, itemsize: int,
                      peaks) -> float:
    """One grouped-matmul call on t rows: the larger of its FLOPs over
    the bf16 peak and its bytes (the rows in, the rows out, every held
    group's matrix once — read by ``moe_gmm``, written by ``moe_tgmm``)
    over the HBM peak."""
    flops = 2.0 * t * k * n
    nbytes = itemsize * (t * k + groups * k * n + t * n)
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)

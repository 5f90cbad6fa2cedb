"""Operations and bytes of the Qwen3-Next family, computed from shapes
(conventions as ``shapes.py``: a matmul of [m, k] by [k, n] is 2*m*k*n
FLOPs, training is 3x the forward's matmul FLOPs, NO recomputation is
counted, the embedding lookup does no FLOPs).

A block mixes by full causal attention or by the gated delta rule, and
every MLP is routed: a token meets the router, the gated shared expert
and, of its ``top_k`` chosen experts, the share that is held here
(``top_k * held / published`` visits under an even routing). Norms, the
four-tap convolution, RoPE, the softmax and the gates are vector work
and are not counted.

The recurrence is counted from its MATHEMATICS, not from a kernel's
tiling: a token and value head cost three products with the [d_k, d_v]
state forward (``S^T k``, ``k delta^T``, ``S^T q``: 6 d_k d_v FLOPs) and
twice that backward, whatever the chunk; a pass reads q, k (key heads),
v, g, beta once and writes o once, and a backward pass moves the same
arrays and their gradients.
"""
from __future__ import annotations

from typing import Dict

from . import shapes

LINEAR = "linear_attention"


def mixer_params(z: Dict, kind: str) -> int:
    """The projections of a block's mixer that a token meets."""
    h = z["hidden"]
    if kind == LINEAR:
        key = z["key_heads"] * z["key_dim"]
        value = z["value_heads"] * z["value_dim"]
        return h * (2 * key + 2 * value) + h * 2 * z["value_heads"] + value * h
    q, k = z["q_heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    return h * 2 * q + 2 * h * k + q * h


def expert_visits_per_token(z: Dict) -> float:
    """Held-expert visits a token makes in one block under an even
    routing."""
    return z["top_k"] * z["experts"] / z["published_experts"]


def moe_params_met(z: Dict) -> float:
    h = z["hidden"]
    return (h * z["published_experts"] + 3 * h * z["shared_inner"] + h
            + expert_visits_per_token(z) * 3 * h * z["expert_inner"])


def matmul_params_met(z: Dict) -> float:
    return (sum(mixer_params(z, kind) + moe_params_met(z)
                for kind in z["layer_kinds"])
            + z["hidden"] * z["vocab_rows"])


def gdn_fwd_flops(seq: int, z: Dict) -> int:
    """One layer's recurrence over one sequence, forward."""
    return 6 * z["key_dim"] * z["value_dim"] * z["value_heads"] * seq


def gdn_bwd_flops(seq: int, z: Dict) -> int:
    return 2 * gdn_fwd_flops(seq, z)


def gdn_fwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    """q, k, v read and o written in ``itemsize``, g and beta in float32."""
    per_token = (itemsize * (2 * z["key_heads"] * z["key_dim"]
                             + 2 * z["value_heads"] * z["value_dim"])
                 + 2 * 4 * z["value_heads"])
    return per_token * seq


def gdn_bwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    return 2 * gdn_fwd_bytes(seq, z, itemsize)


def bound_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time a pass can take: the larger of its FLOPs over the
    bf16 peak and its bytes over the HBM peak."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)


def mixer_flops_per_token(z: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of what the mixers do beside their
    projections: causal attention in the full blocks (12*d a visible pair
    and head), the recurrence in the linear ones."""
    full = (shapes.flash_fwd_flops(seq, z["q_heads"], z["head_dim"])
            + shapes.flash_bwd_flops(seq, z["q_heads"], z["head_dim"]))
    linear = gdn_fwd_flops(seq, z) + gdn_bwd_flops(seq, z)
    return sum(linear if kind == LINEAR else full
               for kind in z["layer_kinds"]) / seq


def train_flops_per_token(z: Dict, seq: int) -> float:
    return 6.0 * matmul_params_met(z) + mixer_flops_per_token(z, seq)

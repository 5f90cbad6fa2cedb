"""Operations and bytes of the MiniCPM-SALA family, computed from shapes
(conventions as ``shapes.py``: a matmul of [m, k] by [k, n] is 2*m*k*n
FLOPs, training is 3x the forward's matmul FLOPs, NO recomputation is
counted, the embedding lookup does no FLOPs).

A block mixes by block-sparse softmax attention or by a decay-only linear
attention and every MLP is a dense SwiGLU. Norms, RoPE, the softmaxes,
the gates and the SELECTION of the blocks (scores against the pooled
keys: 2 d a head and kernel seen, no gradient) are not counted.

Both mixers are counted from their MATHEMATICS, whatever implements
them. The sparse layer: token ``t`` (its own block ``b_t = t div
block``) reads ``min(b_t + 1, topk)`` blocks, all but its own whole, so
``(min(b_t + 1, topk) - 1) block + t mod block + 1`` (query, key) pairs
a head; a pair costs 4 d FLOPs forward (q.k and p v) and 8 d backward,
as the flash readers count. The recurrence: a token and head cost two
products with the [d_k, d_v] state forward (``k v^T``, ``S^T q``: 4 d_k
d_v FLOPs) and twice that backward, whatever the chunk; a pass reads q,
k, v once and writes o once, and a backward pass moves the same arrays
and their gradients.
"""
from __future__ import annotations

from typing import Dict

from .shapes_qwen3next import bound_seconds  # noqa: F401 (the readers')

SPARSE = "minicpm4"


def mixer_params(z: Dict, kind: str) -> int:
    """The projections of a block's mixer (every token meets them)."""
    h = z["hidden"]
    if kind == SPARSE:
        q, k = z["q_heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
        return 3 * h * q + 2 * h * k            # W_q, W_g, W_o; W_k, W_v
    return 5 * h * z["lin_heads"] * z["lin_dim"]


def matmul_params_met(z: Dict) -> int:
    return (sum(mixer_params(z, kind) + 3 * z["hidden"] * z["inner"]
                for kind in z["layer_kinds"])
            + z["hidden"] * z["vocab_rows"])


def sparse_blocks(seq: int, rule: Dict) -> int:
    """Blocks all the tokens of one sequence read, a kv group."""
    size, topk = rule["block_size"], rule["topk"]
    return sum(size * min(b + 1, topk) for b in range(seq // size))


def sparse_blocks_per_query(seq: int, rule: Dict) -> float:
    return sparse_blocks(seq, rule) / seq


def sparse_pairs(seq: int, rule: Dict) -> int:
    """(query, key) pairs a head attends over one sequence."""
    size, topk = rule["block_size"], rule["topk"]
    own = size * (size + 1) // 2                # a token's own block
    return sum(size * size * (min(b + 1, topk) - 1) + own
               for b in range(seq // size))


def sparse_attn_fwd_flops(seq: int, z: Dict) -> int:
    """One layer's sparse attention over one sequence, forward."""
    return 4 * z["head_dim"] * z["q_heads"] * sparse_pairs(seq, z["rule"])


def sparse_attn_bwd_flops(seq: int, z: Dict) -> int:
    return 2 * sparse_attn_fwd_flops(seq, z)


def lightning_fwd_flops(seq: int, z: Dict) -> int:
    """One layer's recurrence over one sequence, forward."""
    return 4 * z["lin_dim"] ** 2 * z["lin_heads"] * seq


def lightning_bwd_flops(seq: int, z: Dict) -> int:
    return 2 * lightning_fwd_flops(seq, z)


def lightning_fwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    """q, k, v read and o written."""
    return 4 * z["lin_heads"] * z["lin_dim"] * itemsize * seq


def lightning_bwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    return 2 * lightning_fwd_bytes(seq, z, itemsize)


def mixer_flops_per_token(z: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of what the mixers do beside their
    projections."""
    sparse = sparse_attn_fwd_flops(seq, z) + sparse_attn_bwd_flops(seq, z)
    linear = lightning_fwd_flops(seq, z) + lightning_bwd_flops(seq, z)
    return sum(sparse if kind == SPARSE else linear
               for kind in z["layer_kinds"]) / seq


def train_flops_per_token(z: Dict, seq: int) -> float:
    return 6.0 * matmul_params_met(z) + mixer_flops_per_token(z, seq)

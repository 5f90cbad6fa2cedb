"""Operations and bytes of the Granite-4.0-H family, computed from shapes
(conventions as ``shapes.py``: a matmul of [m, k] by [k, n] is 2*m*k*n
FLOPs, training is 3x the forward's matmul FLOPs, NO recomputation is
counted, the embedding lookup does no FLOPs; the tied head's matrix is
met once, by the head).

A block mixes by a Mamba-2 state-space layer or by causal grouped-query
attention and every MLP is a dense SwiGLU. Norms, the softmax, SiLU,
softplus and the gate are not counted.

The recurrence and its convolution are counted from their MATHEMATICS,
whatever implements them. The recurrence: a token and head decay the [P,
N] state, add ``dt x B^T`` to it and read it with ``C``: 6 P N FLOPs
forward and twice that backward, whatever the chunk; a pass reads x, B,
C and dt once and writes y once (B and C are ONE group's: every head
reads the same), and a backward pass moves the same arrays and their
gradients. The convolution: ``taps`` products and sums a channel and
token; a forward pass reads its input and writes its output, a backward
pass reads the input and the output's gradient and writes the input's.
"""
from __future__ import annotations

from typing import Dict

from . import shapes
from .shapes_qwen3next import bound_seconds  # noqa: F401 (the readers')

MAMBA = "mamba"


def ssm_channels(z: Dict) -> int:
    """The channels the convolution passes over: x, B and C."""
    return z["ssm_heads"] * z["ssm_dim"] + 2 * z["ssm_state"]


def mixer_params(z: Dict, kind: str) -> int:
    """The projections of a block's mixer (every token meets them)."""
    h = z["hidden"]
    if kind == MAMBA:
        inner = z["ssm_heads"] * z["ssm_dim"]
        return h * (inner + ssm_channels(z) + z["ssm_heads"]) + inner * h
    q, k = z["q_heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    return 2 * h * q + 2 * h * k                # W_q, W_o; W_k, W_v


def matmul_params_met(z: Dict) -> int:
    return (sum(mixer_params(z, kind) + 3 * z["hidden"] * z["inner"]
                for kind in z["layer_kinds"])
            + z["hidden"] * z["vocab_rows"])


def ssd_fwd_flops(seq: int, z: Dict) -> int:
    """One layer's recurrence over one sequence, forward."""
    return 6 * z["ssm_dim"] * z["ssm_state"] * z["ssm_heads"] * seq


def ssd_bwd_flops(seq: int, z: Dict) -> int:
    return 2 * ssd_fwd_flops(seq, z)


def ssd_fwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    """x read and y written, B, C and dt read."""
    heads = z["ssm_heads"]
    return (2 * heads * z["ssm_dim"] + 2 * z["ssm_state"]
            + heads) * itemsize * seq


def ssd_bwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    return 2 * ssd_fwd_bytes(seq, z, itemsize)


def conv_silu_fwd_flops(seq: int, z: Dict) -> int:
    """One layer's convolution over one sequence, forward."""
    return 2 * z["taps"] * ssm_channels(z) * seq


def conv_silu_bwd_flops(seq: int, z: Dict) -> int:
    return 2 * conv_silu_fwd_flops(seq, z)


def conv_silu_fwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    return 2 * ssm_channels(z) * itemsize * seq


def conv_silu_bwd_bytes(seq: int, z: Dict, itemsize: int) -> int:
    return 3 * ssm_channels(z) * itemsize * seq


def mixer_flops_per_token(z: Dict, seq: int) -> float:
    """Forward + backward FLOPs a token of what the mixers do beside their
    projections: causal attention (12*d a visible pair and head) in the
    attention blocks, the recurrence in the others (its convolution is
    not counted: 0.1% of it)."""
    full = (shapes.flash_fwd_flops(seq, z["q_heads"], z["head_dim"])
            + shapes.flash_bwd_flops(seq, z["q_heads"], z["head_dim"]))
    scan = ssd_fwd_flops(seq, z) + ssd_bwd_flops(seq, z)
    return sum(scan if kind == MAMBA else full
               for kind in z["layer_kinds"]) / seq


def train_flops_per_token(z: Dict, seq: int) -> float:
    return 6.0 * matmul_params_met(z) + mixer_flops_per_token(z, seq)

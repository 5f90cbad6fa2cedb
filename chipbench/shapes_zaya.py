"""Operations and bytes of the ZAYA1 family, computed from shapes
(conventions as ``shapes.py``: a matmul of [m, k] by [k, n] is 2*m*k*n
FLOPs, training is 3x the forward's matmul FLOPs, no recomputation, the
embedding lookup does no FLOPs; the tied head's matmul does).

A token meets ONE expert in ``experts`` (top-k of them in general), so
the matmul parameters a token meets count ``top_k`` experts; the
depthwise convolution, the norms, RoPE and the softmax are vector work
and are not counted.
"""
from __future__ import annotations

from typing import Dict

from . import shapes


def block_matmul_params_met(z: Dict) -> int:
    """Parameters of one block that take part in a matmul for every
    token: the five projections, the grouped convolution (each channel
    meets its head's d x d taps), the router, and ``top_k`` experts."""
    h, d, r = z["hidden"], z["head_dim"], z["router_hidden"]
    q, k = z["q_heads"] * d, z["kv_heads"] * d
    attn = h * q + h * k + h * k + q * h          # wq, wk, wv1 + wv2, wo
    conv = z["taps"][1] * (q + k) * d
    router = h * r + 2 * r * r + r * z["experts"]
    expert = 3 * h * z["expert_inner"]
    return attn + conv + router + z["top_k"] * expert


def matmul_params_met(z: Dict) -> int:
    return (z["layers"] * block_matmul_params_met(z)
            + z["hidden"] * z["vocab_rows"])


def train_flops_per_token(z: Dict, seq: int) -> float:
    """Forward + backward FLOPs per trained token: 6 x the matmul
    parameters a token meets, plus causal attention over ``q_heads``
    heads of ``head_dim`` (12*d per pair, head and layer)."""
    attn = z["layers"] * (
        shapes.flash_fwd_flops(seq, z["q_heads"], z["head_dim"])
        + shapes.flash_bwd_flops(seq, z["q_heads"], z["head_dim"])) / seq
    return 6.0 * matmul_params_met(z) + attn


# -- the grouped matmul kernels (ops/grouped_matmul.py) ----------------------


def gmm_flops(t: int, k: int, n: int) -> int:
    """``moe_gmm``: t rows, each against ONE group's [k, n] matrix."""
    return 2 * t * k * n


def gmm_bytes(t: int, k: int, n: int, groups: int, itemsize: int) -> int:
    """``moe_gmm`` reads the rows and every group's matrix once and
    writes the result once (the same for the input gradient, whose rows
    are [t, n] and result [t, k]: the sum is symmetric in k and n)."""
    return itemsize * (t * k + groups * k * n + t * n)


def tgmm_flops(t: int, k: int, n: int) -> int:
    """``moe_tgmm``: x^T dy over each group's rows, t rows in all."""
    return 2 * t * k * n


def tgmm_bytes(t: int, k: int, n: int, groups: int, itemsize: int) -> int:
    """``moe_tgmm`` reads both row matrices once and writes every
    group's [k, n] gradient once."""
    return itemsize * (t * k + t * n + groups * k * n)


def block_kernel_calls(z: Dict, tokens: int):
    """The grouped-matmul calls ONE block makes in one train step on
    ``tokens`` (token, choice) rows, as (kernel, t, k, n): gate-up and
    down, each forward, input gradient (``moe_gmm`` again) and weight
    gradient (``moe_tgmm``)."""
    t = tokens * z["top_k"]
    h, f = z["hidden"], z["expert_inner"]
    pair = [(t, h, 2 * f), (t, f, h)]
    return ([("moe_gmm", *s) for s in pair] * 2
            + [("moe_tgmm", *s) for s in pair])

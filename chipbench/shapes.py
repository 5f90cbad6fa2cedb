"""Operations and bytes an algorithm needs, computed from shapes.

Conventions (stated in PERF.md):

* a matrix multiplication of [m, k] by [k, n] is 2*m*k*n FLOPs;
* training counts forward + backward = 3x the forward's matmul FLOPs and
  no recomputation;
* the ``wte`` / ``wpe`` lookup tables do no FLOPs;
* causal attention over a sequence of S tokens touches S*(S+1)/2 pairs:
  forward 2 matmuls (QK^T, PV) = 4*d_head FLOPs per pair and head;
  backward 4 matmuls (dV, dP, dQ, dK; the recomputed QK^T is not
  counted) = 8*d_head per pair and head.
"""
from __future__ import annotations


def gpt_matmul_params(hidden: int, inner: int, layers: int,
                      vocab_rows: int) -> int:
    """Parameters that take part in a matmul for every token: the blocks'
    four weight matrices and ``lm_head``. Biases, LayerNorms and the
    ``wte`` / ``wpe`` tables are not among them."""
    per_block = hidden * 3 * hidden + hidden * hidden + 2 * hidden * inner
    return layers * per_block + hidden * vocab_rows


def gpt_total_params(hidden: int, inner: int, layers: int, vocab_rows: int,
                     positions: int) -> int:
    """Every parameter of the untied GPT as the program holds it."""
    per_block = (hidden * 3 * hidden + 3 * hidden      # qkv
                 + hidden * hidden + hidden            # out
                 + hidden * inner + inner              # fc1
                 + inner * hidden + hidden             # fc2
                 + 4 * hidden)                         # two LayerNorms
    return (vocab_rows * hidden + positions * hidden + layers * per_block
            + 2 * hidden + hidden * vocab_rows)


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def flash_fwd_flops(seq: int, heads: int, d_head: int) -> int:
    """Causal attention forward for one sequence: QK^T and PV."""
    return 4 * d_head * heads * causal_pairs(seq)


def flash_bwd_flops(seq: int, heads: int, d_head: int) -> int:
    """Causal attention backward for one sequence: dV, dP, dQ, dK."""
    return 8 * d_head * heads * causal_pairs(seq)


def gpt_train_flops_per_token(hidden: int, inner: int, layers: int,
                              vocab_rows: int, heads: int, seq: int) -> float:
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``: 6 x matmul parameters, plus causal attention (12*d_head per
    pair and head and layer; averaged over the sequence that is
    6*L*hidden*(seq+1) per token)."""
    d_head = hidden // heads
    attn = layers * (flash_fwd_flops(seq, heads, d_head)
                     + flash_bwd_flops(seq, heads, d_head)) / seq
    return 6.0 * gpt_matmul_params(hidden, inner, layers, vocab_rows) + attn


def kv_bytes_per_token(layers: int, heads: int, d_head: int,
                       itemsize: int) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return 2 * layers * heads * d_head * itemsize


def paged_decode_bytes(live_tokens: int, heads: int, d_head: int,
                       itemsize: int) -> int:
    """Bytes ONE layer's decode attention must read for ``live_tokens``
    cached tokens summed over the batch: their keys and values. (Queries
    and outputs are a few KB and are left out, so the share is a lower
    bound on the bandwidth reached.)"""
    return 2 * live_tokens * heads * d_head * itemsize

"""Block-sparse attention's forward pass's share of its roofline, from
the device trace.

Time: the device durations of every event whose HLO instruction is named
after a ``sparse_attn_fwd*`` kernel of ``ops/sparse_attention.py``.
Passes: the events of ``sparse_attn_fwd``, the one kernel that writes
``o`` (one a sparse layer and forward pass; a recomputed forward is a
pass). Work, a pass: the FLOPs of the (query, key) pairs the RULE gives
(``shapes_minicpm_sala.sparse_pairs``: a token reads ``min(b_t + 1,
topk)`` blocks, its own up to itself; 4 d FLOPs a pair and head, as the
flash readers count) over the bf16 peak, for every sequence of the
batch: a FLOP bound, whatever the kernel's tiling does with them. The
selection of the blocks is no part of it. Nothing to read without the
events (a program without the kernel) or for a family without such
layers.
"""
from chipbench import shapes_minicpm_sala
from chipbench.layer_metrics.lightning_fwd_roofline import patterns, share

KERNELS, WRITER = patterns("sparse_attn_fwd")


def flop_bound(way: str):
    def bound(seq, z, peaks):
        return getattr(shapes_minicpm_sala, f"sparse_attn_{way}_flops")(
            seq, z) / peaks.bf16_flops
    return bound


def read(facts):
    return share(facts, "sparse_attn_fwd", "rule", flop_bound("fwd"))

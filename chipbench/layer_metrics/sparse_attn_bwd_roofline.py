"""Block-sparse attention's backward pass's share of its roofline, from
the device trace: ``sparse_attn_fwd_roofline``'s arithmetic on the events
named after a ``sparse_attn_bwd*`` kernel (``sparse_attn_bwd`` writes
``dq`` and adds up ``dk``, ``dv``: one a sparse layer and backward pass).
Work, a pass: 8 d FLOPs a pair and head (dV, dP, dQ, dK; the recomputed
scores are not counted), a FLOP bound. Nothing to read without the
events or for a family without such layers.
"""
from chipbench.layer_metrics.lightning_fwd_roofline import patterns, share
from chipbench.layer_metrics.sparse_attn_fwd_roofline import flop_bound

KERNELS, WRITER = patterns("sparse_attn_bwd")


def read(facts):
    return share(facts, "sparse_attn_bwd", "rule", flop_bound("bwd"))

"""The gated delta rule's backward pass's share of its roofline, from the
device trace.

Time: the device durations of every event whose HLO instruction is named
after a ``gdn_bwd*`` kernel of ``ops/gated_delta_rule.py``. Passes: the
events of ``gdn_bwd``, the one kernel that writes ``dq`` .. ``dbeta`` (one a linear
layer and backward pass; a recomputed forward is a pass). Work, a pass:
the larger of the recurrence's FLOPs over the bf16 peak and its bytes
over the HBM peak, counted from the mathematics and not from the
kernel's tiling (``shapes_qwen3next``), for every sequence of the batch.
At d_k = d_v = 128 the BYTES bind. Nothing to read without the events (a
program without the kernel) or for a family without such layers.
"""
from chipbench.layer_metrics.gdn_fwd_roofline import patterns, share

KERNELS, WRITER = patterns("bwd")


def read(facts):
    return share(facts, "bwd")

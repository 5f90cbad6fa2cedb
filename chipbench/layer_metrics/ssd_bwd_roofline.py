"""The state-space scan's backward pass's share of its roofline, from the
device trace: ``ssd_fwd_roofline``'s arithmetic on the events named after
a ``ssd_bwd*`` kernel, the one kernel that writes ``dx`` (one a Mamba
layer and backward pass). Work, a pass: twice the forward's FLOPs and
bytes (``shapes_granite``); the BYTES bind. Nothing to read without the
events or for a family without such layers.
"""
from chipbench.layer_metrics.lightning_fwd_roofline import patterns, share
from chipbench.layer_metrics.ssd_fwd_roofline import bound_of

KERNELS, WRITER = patterns("ssd_bwd")


def read(facts):
    return share(facts, "ssd_bwd", "ssm_heads", bound_of("ssd", "bwd"))

"""Lightning attention's backward pass's share of its roofline, from the
device trace: ``lightning_fwd_roofline``'s arithmetic on the events named
after a ``lightning_bwd*`` kernel, the one kernel that writes ``dq``,
``dk`` and ``dv`` (one a lightning layer and backward pass). Work, a
pass: twice the forward's FLOPs and bytes (``shapes_minicpm_sala``); the
BYTES bind. Nothing to read without the events or for a family without
such layers.
"""
from chipbench.layer_metrics.lightning_fwd_roofline import (
    lightning_bound, patterns, share)

KERNELS, WRITER = patterns("lightning_bwd")


def read(facts):
    return share(facts, "lightning_bwd", "lin_heads", lightning_bound("bwd"))

"""The Mamba layers' convolution's backward pass's share of its roofline,
from the device trace: ``conv_silu_fwd_roofline``'s arithmetic on the
events named after a ``conv_silu_bwd*`` kernel (one a Mamba layer and
backward pass). Work, a pass: the input and the output's gradient read,
the input's gradient written (``shapes_granite``: three passes over 4,352
channels); the BYTES bind. Nothing to read without the events or for a
family without such layers.
"""
from chipbench.layer_metrics.lightning_fwd_roofline import patterns, share
from chipbench.layer_metrics.ssd_fwd_roofline import bound_of

KERNELS, WRITER = patterns("conv_silu_bwd")


def read(facts):
    return share(facts, "conv_silu_bwd", "ssm_heads",
                 bound_of("conv_silu", "bwd"))

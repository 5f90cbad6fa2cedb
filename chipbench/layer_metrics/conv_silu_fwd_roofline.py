"""The Mamba layers' convolution's forward pass's share of its roofline,
from the device trace: ``ssd_fwd_roofline``'s arithmetic on the events
named after a ``conv_silu_fwd*`` kernel of ``ops/conv_silu.py`` (one a
Mamba layer and forward pass). Work, a pass: the input read and the
output written once (``shapes_granite``: 4,352 channels in bf16, 285 MB at
16,384 tokens) against 2 x taps FLOPs a channel and token: the BYTES
bind. Nothing to read without the events or for a family without such
layers.
"""
from chipbench.layer_metrics.lightning_fwd_roofline import patterns, share
from chipbench.layer_metrics.ssd_fwd_roofline import bound_of

KERNELS, WRITER = patterns("conv_silu_fwd")


def read(facts):
    return share(facts, "conv_silu_fwd", "ssm_heads",
                 bound_of("conv_silu", "fwd"))

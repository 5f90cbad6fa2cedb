"""The state-space scan's forward pass's share of its roofline, from the
device trace: ``lightning_fwd_roofline``'s arithmetic (``share``) on the
events named after a ``ssd_fwd*`` kernel of ``ops/mamba2_ssd.py``.
Passes: the events of ``ssd_fwd``, the one kernel that writes ``y`` (one a
Mamba layer and forward pass; a recomputed forward is a pass). Work, a
pass: the larger of the recurrence's FLOPs over the bf16 peak and its
bytes over the HBM peak, counted from the mathematics and not from the
kernel's chunks (``shapes_granite``: 6 P N FLOPs a token and head; x, B,
C, dt read and y written once), for every sequence of the batch. At P =
64, N = 128 the BYTES bind, by a little (0.34 ms against 0.26 ms of FLOPs
at 16,384 tokens). Nothing to read without the events (a program without
the kernel) or for a family without such layers.
"""
from chipbench import shapes_granite
from chipbench.layer_metrics.lightning_fwd_roofline import patterns, share

KERNELS, WRITER = patterns("ssd_fwd")


def bound_of(kernel: str, way: str):
    """One pass's least seconds, by ``shapes_granite.<kernel>_<way>_flops``
    and ``_bytes`` at the activations' two bytes."""
    def bound(seq, z, peaks):
        return shapes_granite.bound_seconds(
            getattr(shapes_granite, f"{kernel}_{way}_flops")(seq, z),
            getattr(shapes_granite, f"{kernel}_{way}_bytes")(seq, z, 2),
            peaks)
    return bound


def read(facts):
    return share(facts, "ssd_fwd", "ssm_heads", bound_of("ssd", "fwd"))

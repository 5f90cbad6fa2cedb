"""The gated delta rule's forward pass's share of its roofline, from the
device trace.

Time: the device durations of every event whose HLO instruction is named
after a ``gdn_fwd*`` kernel of ``ops/gated_delta_rule.py``. Passes: the
events of ``gdn_fwd``, the one kernel that writes ``o`` (one a linear
layer and forward pass; a recomputed forward is a pass). Work, a pass:
the larger of the recurrence's FLOPs over the bf16 peak and its bytes
over the HBM peak, counted from the mathematics and not from the
kernel's tiling (``shapes_qwen3next``), for every sequence of the batch.
At d_k = d_v = 128 the BYTES bind. Nothing to read without the events (a
program without the kernel) or for a family without such layers.
"""
from chipbench import shapes_qwen3next, trace as tracelib



def patterns(way: str):
    """(every kernel of one direction, the one that writes its result)."""
    return (rf"^%[\w.\-]*gdn_{way}[\w.\-]* = ",
            rf"^%[\w.\-]*gdn_{way}(?!_[a-z])[\w.\-]* = ")


KERNELS, WRITER = patterns("fwd")


def share(facts, way: str):
    """``gdn_<way>_roofline``: the passes' bound over the kernels' time."""
    trace = facts.get("trace")
    if trace is None:
        return None
    kernels, writer = patterns(way)
    seconds, _ = tracelib.kernel_seconds(trace, kernels)
    _, passes = tracelib.kernel_seconds(trace, writer)
    z = facts["family"].sizes(facts["config"])
    if not passes or "value_heads" not in z:
        return None
    seq = facts["seq"]
    bound = shapes_qwen3next.bound_seconds(
        getattr(shapes_qwen3next, f"gdn_{way}_flops")(seq, z),
        getattr(shapes_qwen3next, f"gdn_{way}_bytes")(seq, z, 2),
        facts["peaks"])
    return 100.0 * passes * facts["batch"] * bound / seconds


def read(facts):
    return share(facts, "fwd")

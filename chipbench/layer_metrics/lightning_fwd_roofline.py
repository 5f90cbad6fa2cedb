"""Lightning attention's forward pass's share of its roofline, from the
device trace.

Time: the device durations of every event whose HLO instruction is named
after a ``lightning_fwd*`` kernel of ``ops/lightning_attention.py``.
Passes: the events of ``lightning_fwd``, the one kernel that writes ``o``
(one a lightning layer and forward pass; a recomputed forward is a pass).
Work, a pass: the larger of the recurrence's FLOPs over the bf16 peak and
its bytes over the HBM peak, counted from the mathematics and not from
the kernel's chunks (``shapes_minicpm_sala``: 4 d_k d_v FLOPs a token and
head; q, k, v read and o written once), for every sequence of the batch.
At d = 128 the BYTES bind. Nothing to read without the events (a program
without the kernel) or for a family without such layers.
"""
from chipbench import shapes_minicpm_sala, trace as tracelib


def patterns(name: str):
    """(every kernel of one direction, the one that writes its result)."""
    return (rf"^%[\w.\-]*{name}[\w.\-]* = ",
            rf"^%[\w.\-]*{name}(?!_[a-z])[\w.\-]* = ")


KERNELS, WRITER = patterns("lightning_fwd")


def share(facts, name: str, needs: str, bound_of):
    """``<name>_roofline``: the passes' bound over the kernels' time;
    ``bound_of(seq, sizes, peaks)`` is one pass's least seconds."""
    trace = facts.get("trace")
    if trace is None:
        return None
    kernels, writer = patterns(name)
    seconds, _ = tracelib.kernel_seconds(trace, kernels)
    _, passes = tracelib.kernel_seconds(trace, writer)
    z = facts["family"].sizes(facts["config"])
    if not passes or needs not in z:
        return None
    bound = bound_of(facts["seq"], z, facts["peaks"])
    return 100.0 * passes * facts["batch"] * bound / seconds


def lightning_bound(way: str):
    def bound(seq, z, peaks):
        return shapes_minicpm_sala.bound_seconds(
            getattr(shapes_minicpm_sala, f"lightning_{way}_flops")(seq, z),
            getattr(shapes_minicpm_sala, f"lightning_{way}_bytes")(seq, z, 2),
            peaks)
    return bound


def read(facts):
    return share(facts, "lightning_fwd", "lin_heads", lightning_bound("fwd"))

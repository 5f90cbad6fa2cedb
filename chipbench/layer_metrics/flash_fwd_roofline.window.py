"""The sliding-window flash forward kernel's share of its roofline, from
the device trace.

A call with a ``window`` runs under a kernel name of its own
(``pl.pallas_call(name="flash_window_fwd")``: ``%flash_window_fwd.<n>`` on
the ``XLA Ops`` line), which the causal readers' patterns do not match,
as theirs do not match this one. Time: the device durations of those
events, one a window layer and forward pass (a recomputed block runs its
forward twice a step: two events, twice the work). Work:
``shapes_afmoe.flash_window_fwd_flops`` — 4*d a visible pair and head,
the pairs ``sum_t min(t + 1, window)`` — for every sequence of the
batch, per event. Divides by the FLOP bound (the bf16 peak). Nothing to
read where the family's ``sizes`` name no ``window`` or the trace holds
no such event (a program without the kernel).
"""
from chipbench import shapes_afmoe, trace as tracelib

KERNEL = r"^%[\w.\-]*flash_window_fwd[\w.\-]* = "


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    z = facts["family"].sizes(facts["config"])
    seconds, events = tracelib.kernel_seconds(trace, KERNEL)
    if not events or "window" not in z:
        return None
    flops = events * facts["batch"] * shapes_afmoe.flash_window_fwd_flops(
        facts["seq"], z["q_heads"], z["head_dim"], z["window"])
    return 100.0 * flops / (facts["peaks"].bf16_flops * seconds)

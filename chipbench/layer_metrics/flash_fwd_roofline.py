"""The flash-attention forward kernel's share of its roofline, from the
device trace.

Time: the device durations of the events whose HLO instruction is named
after the kernel (``pl.pallas_call(name="flash_fwd")`` appears on the
``XLA Ops`` line as ``%jvp_flash_fwd_.<n> = ... custom-call(...)``; the
pattern is held to the instruction's own name, not its operands'), one
event a layer and step. Work: ``shapes.flash_fwd_flops`` of the cell's
sequence for every sequence of the batch, per event. Causal attention at
d_head 128 is compute-bound: the share divides by the FLOP bound (the
bf16 peak of chipbench/peaks.py).
"""
from chipbench import shapes, trace as tracelib

KERNEL = r"^%[\w.\-]*flash_fwd[\w.\-]* = "


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    seconds, events = tracelib.kernel_seconds(trace, KERNEL)
    if not events:
        return None
    z = facts["family"].sizes(facts["config"])
    flops = events * facts["batch"] * shapes.flash_fwd_flops(
        facts["seq"], z["heads"], z["hidden"] // z["heads"])
    return 100.0 * flops / (facts["peaks"].bf16_flops * seconds)

"""The flash-attention backward kernels' share of their roofline, from
the device trace.

The backward is two kernels: ``flash_bwd_dq`` and ``flash_bwd_dkv``
(``%transpose_jvp_flash_bwd_dq__.<n>`` and ``..._dkv__.<n>`` on the ``XLA
Ops`` line), one event each a layer and step. Time: the device durations
of both. Work: ``shapes.flash_bwd_flops`` (dV, dP, dQ, dK; the
recomputed QK^T is not counted) of the cell's sequence for every
sequence of the batch, per dk/dv event. Divides by the FLOP bound.
"""
from chipbench import shapes, trace as tracelib

DQ = r"^%[\w.\-]*flash_bwd_dq[\w.\-]* = "
DKV = r"^%[\w.\-]*flash_bwd_dkv[\w.\-]* = "


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    dq_s, dq_n = tracelib.kernel_seconds(trace, DQ)
    dkv_s, dkv_n = tracelib.kernel_seconds(trace, DKV)
    if not dq_n or not dkv_n:
        return None
    z = facts["family"].sizes(facts["config"])
    flops = dkv_n * facts["batch"] * shapes.flash_bwd_flops(
        facts["seq"], z["heads"], z["hidden"] // z["heads"])
    return 100.0 * flops / (facts["peaks"].bf16_flops * (dq_s + dkv_s))

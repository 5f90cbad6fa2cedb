"""The sliding-window flash backward kernels' share of their roofline,
from the device trace: ``flash_bwd_roofline``'s arithmetic on the window
calls' own kernels, ``%flash_window_bwd_dq.<n>`` and
``%flash_window_bwd_dkv.<n>``, one event each a window layer and step.
Work: ``shapes_afmoe.flash_window_bwd_flops`` (dV, dP, dQ, dK over the
visible pairs ``sum_t min(t + 1, window)``, 8*d a pair and head; the
recomputed QK^T is not counted) per dk/dv event, over the device seconds
of both kernels. Divides by the FLOP bound."""
from chipbench import shapes_afmoe, trace as tracelib

DQ = r"^%[\w.\-]*flash_window_bwd_dq[\w.\-]* = "
DKV = r"^%[\w.\-]*flash_window_bwd_dkv[\w.\-]* = "


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    z = facts["family"].sizes(facts["config"])
    dq_s, dq_n = tracelib.kernel_seconds(trace, DQ)
    dkv_s, dkv_n = tracelib.kernel_seconds(trace, DKV)
    if not dq_n or not dkv_n or "window" not in z:
        return None
    flops = dkv_n * facts["batch"] * shapes_afmoe.flash_window_bwd_flops(
        facts["seq"], z["q_heads"], z["head_dim"], z["window"])
    return 100.0 * flops / (facts["peaks"].bf16_flops * (dq_s + dkv_s))

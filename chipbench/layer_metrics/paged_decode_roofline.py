"""The paged decode kernel's share of its roofline, from the device trace.

Time: the device durations of the events named ``%paged_attention.<n>``
(jax's Pallas paged-attention custom call; one event per layer and decode
dispatch) in the traced part of the window. Work: the keys and values of
every live cached token of every decoding lane, over all layers
(``kv_bytes_per_token`` of the family; queries and outputs left out), for
the engine steps that fell into the traced part. Decode attention is
bandwidth-bound: the share divides by the BYTES bound (HBM bytes/s of
chipbench/peaks.py).
"""
from chipbench import trace as tracelib

KERNEL = r"^%paged_attention[.\d]* = "


def read(facts):
    trace, start = facts.get("trace"), facts.get("trace_from_s")
    if trace is None or start is None:
        return None
    seconds, events = tracelib.kernel_seconds(trace, KERNEL)
    if not events:
        return None
    live = sum(kv for t, _, dec, _, kv in facts["engine_steps"]
               if t >= start and dec > 0)
    least = (live * facts["family"].kv_bytes_per_token(facts["config"])
             / facts["peaks"].hbm_bytes_per_s)
    return 100.0 * least / seconds

"""``flash_bwd_roofline`` for a family whose head size is not
``hidden // heads``: the same events (dq and dk/dv), the same arithmetic,
with the number of query heads and the head size from the family's
``sizes`` (``q_heads``, ``head_dim``): under GQA the backward runs every
query head against its expanded kv head. Divides by the FLOP bound."""
from chipbench import shapes, trace as tracelib
from chipbench.layer_metrics.flash_bwd_roofline import DKV, DQ


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    z = facts["family"].sizes(facts["config"])
    dq_s, dq_n = tracelib.kernel_seconds(trace, DQ)
    dkv_s, dkv_n = tracelib.kernel_seconds(trace, DKV)
    if not dq_n or not dkv_n or "q_heads" not in z:
        return None
    flops = dkv_n * facts["batch"] * shapes.flash_bwd_flops(
        facts["seq"], z["q_heads"], z["head_dim"])
    return 100.0 * flops / (facts["peaks"].bf16_flops * (dq_s + dkv_s))

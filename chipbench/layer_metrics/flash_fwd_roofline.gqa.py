"""``flash_fwd_roofline`` for a family whose head size is not
``hidden // heads``: the same events, the same arithmetic, with the
number of query heads and the head size from the family's ``sizes``
(``q_heads``, ``head_dim``). Divides by the FLOP bound."""
from chipbench import shapes, trace as tracelib
from chipbench.layer_metrics.flash_fwd_roofline import KERNEL


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    z = facts["family"].sizes(facts["config"])
    seconds, events = tracelib.kernel_seconds(trace, KERNEL)
    if not events or "q_heads" not in z:
        return None
    flops = events * facts["batch"] * shapes.flash_fwd_flops(
        facts["seq"], z["q_heads"], z["head_dim"])
    return 100.0 * flops / (facts["peaks"].bf16_flops * seconds)

"""The grouped-matmul kernels' share of their roofline where a block
holds a SHARE of the experts: the rows they work on are data.

Time: the device durations of the ``%moe_gmm.<n>`` and ``%moe_tgmm.<n>``
events (``moe_gmm_roofline``'s patterns). Work, per event FOUND (so a
recomputed forward, which runs ``moe_gmm`` again, reads right): the rows
the program's counters say the held experts got — the family's
``moe_counters()``: ``moe.tokens_per_expert`` over the steps
``moe.pairs_routed`` counts, the mean a routed block and step — on the
two shapes a block's grouped matmuls take (gate-up and down: every
kernel runs on both, so an event is half of each). A call's
bound is the larger of its FLOPs over the bf16 peak and its bytes over
the HBM peak (``shapes_afmoe.gmm_bound_seconds``); at an eighth of a
thousand rows an expert the BYTES bind, by a factor of two: a held
expert's matrices are fetched whole for a handful of rows. Nothing to
read without the counters (a program that records none) or the events.
"""
from chipbench import shapes_afmoe, trace as tracelib
from chipbench.layer_metrics.moe_gmm_roofline import GMM, TGMM


def read(facts):
    trace = facts.get("trace")
    counters = getattr(facts["family"], "moe_counters", None)
    got = counters and counters()
    if trace is None or got is None:
        return None
    gmm_s, gmm_n = tracelib.kernel_seconds(trace, GMM)
    tgmm_s, tgmm_n = tracelib.kernel_seconds(trace, TGMM)
    z = facts["family"].sizes(facts["config"])
    if not gmm_n or not tgmm_n or "published_experts" not in z:
        return None
    counts, pairs = got
    steps = sum(pairs) / (len(pairs) * facts["batch"] * facts["seq"]
                          * z["top_k"])
    if steps <= 0:
        return None
    rows = sum(map(sum, counts)) / (len(counts) * steps)
    per_event = sum(
        shapes_afmoe.gmm_bound_seconds(t, k, n, z["experts"], 2,
                                       facts["peaks"])
        for t, k, n in shapes_afmoe.held_gmm_calls(z, rows)) / 2
    return 100.0 * (gmm_n + tgmm_n) * per_event / (gmm_s + tgmm_s)

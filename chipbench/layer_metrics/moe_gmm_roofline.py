"""The grouped-matmul kernels' share of their roofline, from the device
trace.

Time: the device durations of the events whose HLO instruction is named
after ``ops/grouped_matmul.py``'s kernels, ``%moe_gmm.<n>`` (forward and
input gradient) and ``%moe_tgmm.<n>`` (weight gradient). Work: a block
makes four ``moe_gmm`` and two ``moe_tgmm`` calls a train step
(``shapes_zaya.block_kernel_calls``), so the events found are that many
sixths of each; every call's bound is the LARGER of its FLOPs over the
bf16 peak and its bytes over the HBM peak (at a few hundred rows a group
the two lie close together: PERF.md §3 says which binds), and the share
is the sum of the bounds over the kernels' device seconds.
"""
from chipbench import shapes_zaya, trace as tracelib

GMM = r"^%[\w.\-]*moe_gmm[\w.\-]* = "
TGMM = r"^%[\w.\-]*moe_tgmm[\w.\-]* = "


def bound_seconds(kernel, t, k, n, groups, itemsize, peaks):
    if kernel == "moe_gmm":
        flops, nbytes = (shapes_zaya.gmm_flops(t, k, n),
                         shapes_zaya.gmm_bytes(t, k, n, groups, itemsize))
    else:
        flops, nbytes = (shapes_zaya.tgmm_flops(t, k, n),
                         shapes_zaya.tgmm_bytes(t, k, n, groups, itemsize))
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)


def read(facts):
    trace = facts.get("trace")
    if trace is None:
        return None
    gmm_s, gmm_n = tracelib.kernel_seconds(trace, GMM)
    tgmm_s, tgmm_n = tracelib.kernel_seconds(trace, TGMM)
    if not gmm_n or not tgmm_n:
        return None
    z = facts["family"].sizes(facts["config"])
    if "experts" not in z:
        return None
    calls = shapes_zaya.block_kernel_calls(z, facts["batch"] * facts["seq"])
    events = {"moe_gmm": gmm_n, "moe_tgmm": tgmm_n}
    per_block = {name: sum(c[0] == name for c in calls) for name in events}
    bound = sum(
        events[name] / per_block[name]
        * bound_seconds(name, t, k, n, z["experts"], 2, facts["peaks"])
        for name, t, k, n in calls)
    return 100.0 * bound / (gmm_s + tgmm_s)

"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window, averaged over chips."""
from chipbench import trace as tracelib


def read(facts):
    trace = facts.get("trace")
    return None if trace is None else 100.0 * tracelib.idle_share(trace)

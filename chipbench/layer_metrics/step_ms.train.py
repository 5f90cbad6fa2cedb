"""Median wall time of one train step of the window (host clock around a
step that ends in ``block_until_ready``, the fresh batch included)."""
from chipbench import stats


def read(facts):
    steps = facts.get("step_s")
    if not steps or not facts["on_chip"]:
        return None
    return 1e3 * stats.median(steps)

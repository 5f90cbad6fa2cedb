"""Median wall time of the ``engine.step()`` calls that carried decode
lanes only: the engine's counters say which (decode tokens moved, prefill
tokens did not)."""
from chipbench import stats


def read(facts):
    if not facts["on_chip"]:
        return None
    walls = [w for _, w, dec, pre, _ in facts.get("engine_steps", ())
             if dec > 0 and pre == 0]
    return 1e3 * stats.median(walls) if walls else None

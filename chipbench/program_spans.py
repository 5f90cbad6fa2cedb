"""The program's own spans, as the ``program_span`` metrics read them.

The program (``paddle_tpu/jit``) records one ``to_static.call`` span for
each call of a compiled function, with its legs as children
(``to_static.revalidate``, ``.read_state``, ``.dispatch``,
``.write_state``; ``to_static.trace`` while jax traces), into the bounded
ring ``paddle_tpu.obs.ring()``. A call span's ``args`` hold ``fn`` (the
function's qualified name) and ``traces`` (how often jax traced inside
it). A call that met a graph break and went on outside the compiled path
carries ``fallback`` and is left out. Durations are seconds on the host's
clock.

A job's trainer is driven by ONE call of the program per step, so the
window's calls are the newest ``len(facts["step_s"])`` call spans of the
newest call's function, and set-up's are the ones before them. Where the
program records no such span (a commit before it did), or the ring has
dropped events (set-up's are the first to go), there is nothing to read.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

CALL = "to_static.call"
DISPATCH = "to_static.dispatch"
TRACE = "to_static.trace"


def load() -> Optional[List[Dict]]:
    """The ring's events, oldest first; ``None`` where it has dropped any."""
    from paddle_tpu import obs

    ring = obs.ring()
    if ring.n_dropped > 0:
        return None
    return ring.dump()


def split(events: List[Dict], window_calls: int
          ) -> Optional[Tuple[List[Dict], List[Dict]]]:
    """(set-up's, the window's) call spans of the trainer's function."""
    calls = [e for e in events
             if e["name"] == CALL and not e["args"].get("fallback")]
    if not calls or window_calls <= 0:
        return None
    fn = calls[-1]["args"].get("fn")
    calls = [e for e in calls if e["args"].get("fn") == fn]
    if window_calls > len(calls):
        return None
    return calls[:-window_calls], calls[-window_calls:]


def children(events: List[Dict], calls: List[Dict], name: str) -> List[Dict]:
    """The spans called ``name`` whose parent is one of ``calls``."""
    ids = {c["span_id"] for c in calls}
    return [e for e in events if e["name"] == name and e["parent_id"] in ids]


def of_a_training_run(facts: Dict):
    """(events, set-up's calls, the window's calls) of a training job's
    run on the chip, or ``None``: a time comes only from a chip run."""
    steps = facts.get("step_s")
    if not steps or not facts["on_chip"]:
        return None
    events = load()
    parts = split(events, len(steps)) if events is not None else None
    return None if parts is None else (events, *parts)

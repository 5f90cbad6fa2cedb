"""What set-up's compiling calls did, as the ``setup_step_*`` metrics,
``step_cold_compile_s`` and ``step_compiled_gib`` read it.

Beside a call's legs (``program_spans``), the program records what jax did
inside a call that compiled: one ``to_static.lower`` span for each lowering
to an MLIR module and one ``to_static.compile`` span for each backend
compile, children of the ``to_static.call`` that was open
(``paddle_tpu/obs/compile.py``: jax's own monitoring events, start and
duration as jax gives them). A compile span's ``args`` hold ``fun`` (jax's
name of the function), ``cache`` — ``"hit"``, ``"miss"`` (the request used
the persistent cache and found nothing) or ``"off"`` — and on a hit
``retrieval_s`` (the cache's read) and ``saved_s`` (what the stored
compile had cost, less the read; jax stores that cost in whole seconds).
The call span of a call that compiled carries ``memory``: the bytes the
compiler reckons its executable needs (``argument``, ``output``,
``alias``, ``temp``, ``code``).

Set-up's calls are ``program_spans.of_a_training_run``'s: the calls of the
trainer's function before the window's. Nothing to read off the chip,
where the ring dropped events, or on a program that records no such span.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from . import program_spans

LOWER = "to_static.lower"
COMPILE = "to_static.compile"


def setup_legs(facts: Dict, name: str) -> Optional[List[Dict]]:
    """The spans called ``name`` under set-up's calls of the trainer's
    function; ``None`` where there is no run to read or no such span."""
    run = program_spans.of_a_training_run(facts)
    if run is None:
        return None
    events, setup, _ = run
    return program_spans.children(events, setup, name) or None


def cold_seconds(compile_span: Dict) -> float:
    """What this compile costs at a fresh cache: its own duration where
    it compiled, what the stored compile had cost where it was loaded."""
    args = compile_span["args"]
    if args.get("cache") == "hit":
        return args.get("saved_s", 0.0) + args.get("retrieval_s", 0.0)
    return compile_span["dur"]


def compiled_bytes(facts: Dict) -> Optional[int]:
    """Bytes the executable of the newest compiling call of the trainer's
    function holds at its peak: arguments and outputs (less the outputs
    that reuse a donated argument), temporaries and code."""
    run = program_spans.of_a_training_run(facts)
    if run is None:
        return None
    _, setup, window = run
    for call in reversed(setup + window):
        memory = call["args"].get("memory")
        if memory:
            return (memory["argument"] + memory["output"] - memory["alias"]
                    + memory["temp"] + memory["code"])
    return None

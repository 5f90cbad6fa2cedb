"""Seconds of set-up spent in calls of the compiled step that made jax
trace (and so lower, compile or load from the cache): the sum of the
durations of set-up's ``to_static.call`` spans with ``traces`` >= 1."""
from chipbench import program_spans


def read(facts):
    run = program_spans.of_a_training_run(facts)
    if run is None:
        return None
    _, setup, _ = run
    return sum(c["dur"] for c in setup if c["args"].get("traces", 0) >= 1)

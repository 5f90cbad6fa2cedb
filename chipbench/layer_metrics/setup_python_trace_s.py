"""Seconds the program's own Python ran under jax's tracer (dy2static, the
tape, the optimizer's per-leaf loop), as against lowering, compiling and
cache loads: the sum of the durations of every ``to_static.trace`` span of
the trainer's function."""
from chipbench import program_spans


def read(facts):
    run = program_spans.of_a_training_run(facts)
    if run is None:
        return None
    events, setup, window = run
    legs = program_spans.children(events, setup + window, program_spans.TRACE)
    return sum(e["dur"] for e in legs)

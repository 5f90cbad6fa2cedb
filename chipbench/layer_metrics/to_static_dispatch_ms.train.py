"""jax's share of the host time of one call into the compiled step: the
median duration of the window's ``to_static.dispatch`` spans (the jitted
call alone). ``to_static_host_ms.train`` less this is the program's own."""
from chipbench import program_spans, stats


def read(facts):
    run = program_spans.of_a_training_run(facts)
    if run is None:
        return None
    events, _, window = run
    legs = program_spans.children(events, window, program_spans.DISPATCH)
    return 1e3 * stats.median([e["dur"] for e in legs]) if legs else None

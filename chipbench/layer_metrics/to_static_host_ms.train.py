"""Host time of one call into the compiled step: the median duration of
the window's ``to_static.call`` spans (the program's own span from the
entry of ``StaticFunction.__call__`` to its return; the device runs on
after it, so this is the host's path and not the step)."""
from chipbench import program_spans, stats


def read(facts):
    run = program_spans.of_a_training_run(facts)
    if run is None:
        return None
    _, _, window = run
    return 1e3 * stats.median([c["dur"] for c in window])

"""What compiling the trainer's step costs at a fresh cache, whichever way
this run came by its executables: over the ``to_static.compile`` spans
under set-up's ``to_static.call`` spans, ``saved_s + retrieval_s`` on a hit
(what jax stored as the compile's cost, in whole seconds) and the span's
duration otherwise."""
from chipbench import compile_spans


def read(facts):
    legs = compile_spans.setup_legs(facts, compile_spans.COMPILE)
    return None if legs is None else sum(map(compile_spans.cold_seconds, legs))

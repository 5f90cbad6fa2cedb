"""Seconds set-up's compiling calls spent in backend compiles: the sum of
the durations of the ``to_static.compile`` spans under set-up's
``to_static.call`` spans (jax's ``backend_compile`` events). On a warm run
that is the persistent cache's read, on a cold one XLA and Mosaic."""
from chipbench import compile_spans


def read(facts):
    legs = compile_spans.setup_legs(facts, compile_spans.COMPILE)
    return None if legs is None else sum(e["dur"] for e in legs)

"""Seconds set-up's compiling calls spent lowering jaxprs to MLIR modules:
the sum of the durations of the ``to_static.lower`` spans under set-up's
``to_static.call`` spans (jax's ``jaxpr_to_mlir_module`` events)."""
from chipbench import compile_spans


def read(facts):
    legs = compile_spans.setup_legs(facts, compile_spans.LOWER)
    return None if legs is None else sum(e["dur"] for e in legs)

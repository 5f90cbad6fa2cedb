"""How often set-up traced the trainer's function: the number of
``to_static.trace`` spans under set-up's ``to_static.call`` spans (a train
step traces twice today: once before the optimizer's state exists, once
with it)."""
from chipbench import compile_spans, program_spans


def read(facts):
    legs = compile_spans.setup_legs(facts, program_spans.TRACE)
    return None if legs is None else len(legs)

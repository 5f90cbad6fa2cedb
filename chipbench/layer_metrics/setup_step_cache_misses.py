"""Backend compiles of set-up's compiling calls that the persistent cache
did not serve: the ``to_static.compile`` spans under set-up's
``to_static.call`` spans whose ``cache`` is not ``"hit"``. 0 says the run
was warm."""
from chipbench import compile_spans


def read(facts):
    legs = compile_spans.setup_legs(facts, compile_spans.COMPILE)
    if legs is None:
        return None
    return sum(e["args"].get("cache") != "hit" for e in legs)

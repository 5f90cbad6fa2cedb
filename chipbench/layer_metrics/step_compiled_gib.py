"""GiB the compiler reckons the steady step's executable holds at its
peak: ``(argument + output - alias + temp + code) / 2**30`` from the
``memory`` of the newest compiling ``to_static.call`` of the trainer's
function (the chip has 15.75 GiB: the headroom decides what a cell must
recompute; the allocator's peak misses a step's temporaries)."""
from chipbench import compile_spans


def read(facts):
    held = compile_spans.compiled_bytes(facts)
    return None if held is None else held / 2 ** 30

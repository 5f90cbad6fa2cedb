"""XLA compilations inside the measured window (``recompile_guard``)."""


def read(facts):
    return facts.get("compiles")

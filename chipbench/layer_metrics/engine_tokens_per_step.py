"""Tokens the engine moved per iteration: (decode + prefill tokens) /
steps, from the engine's own counters over the window."""


def read(facts):
    c = facts.get("counters")
    if not c or not c["steps"]:
        return None
    return (c["decode_tokens"] + c["prefill_tokens"]) / c["steps"]

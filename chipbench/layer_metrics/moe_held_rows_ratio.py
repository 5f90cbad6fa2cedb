"""How near the held experts' load came to the deployment's even share:
the rows the held experts got / (the (token, choice) pairs routed x
held / published), from the program's counters (the family's
``moe_counters()``: ``moe.tokens_per_expert`` and ``moe.pairs_routed``,
read once after the window). 1.0 = the held experts got exactly their
share of the pairs; the counts run from the trainer's build. A count, so
it is reported off the chip too; nothing to read where the family or the
program records no such events."""


def read(facts):
    counters = getattr(facts["family"], "moe_counters", None)
    got = counters and counters()
    if got is None:
        return None
    z = facts["family"].sizes(facts["config"])
    counts, pairs = got
    even = sum(pairs) * z["experts"] / z["published_experts"]
    return sum(map(sum, counts)) / even if even else None

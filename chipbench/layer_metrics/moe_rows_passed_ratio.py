"""How many rows a share's passes ran over for each row its held experts
were given: the rows every pass of a routed block covered (the permute,
both grouped matmuls' operands, the activation, the combine) / the rows
the held experts got, a routed block and step. 1.0 = not one dead row.

From what the PROGRAM says it ran, read once after the window (the
family's ``Trainer.free()`` into ``obs.ring()``): ``moe.rows_a_window``,
the rows one pass of each block's traced call covered — the layer's own
record, a Python int it keeps when the call is traced, so a pass made
shorter or longer shows here whatever the layer's bound would say — times
the windows it ran: one a call where a window is a row for every (token,
choice) pair, else one a call and a second in the calls
``moe.calls_in_full`` counts (a call that took a third window is counted
as two: a lower bound there). The calls are ``moe.pairs_routed`` over a
call's pairs; the rows the experts got ``moe.tokens_per_expert``. The
counts run from the trainer's build. A count, so it is reported off the
chip too; nothing to read where the program records no such events."""


def read(facts):
    try:
        from paddle_tpu import obs
    except ImportError:
        return None
    last = {e["name"]: e["args"] for e in obs.ring().dump()
            if e.get("name", "").startswith("moe.")}
    try:
        rows, pairs = last["moe.rows_a_window"]["rows"], last[
            "moe.pairs_routed"]["pairs"]
        in_full = last["moe.calls_in_full"]["calls"]
        held_rows = sum(map(sum, last["moe.tokens_per_expert"]["counts"]))
    except KeyError:
        return None
    if not held_rows or None in rows:
        return None
    a_call = facts["batch"] * facts["seq"] * facts["family"].sizes(
        facts["config"])["top_k"]
    passed = sum(r * (p / a_call + (f if r < a_call else 0))
                 for r, p, f in zip(rows, pairs, in_full))
    return passed / held_rows

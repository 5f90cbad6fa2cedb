"""How many key blocks a query read, a sparse layer and kv group: the
blocks the program's tables named / the (token, kv group) rows they
named them for, from the program's counters (the family's
``sparse_counters()``: ``sparse.blocks_chosen`` and ``sparse.query_rows``,
which ``Trainer.free()`` reads once after the window). The rule gives
``shapes_minicpm_sala.sparse_blocks_per_query`` (56.125 at 16,384 tokens:
32.5 over the first 4,096, 64 after): a program that attends more or
fewer blocks than the rule shows here. The counts are the LAST step's
(int32 on the device: a sum over a run would wrap). A count, so it is
reported off the chip too; nothing to read
where the family or the program records no such events."""


def read(facts):
    counters = getattr(facts["family"], "sparse_counters", None)
    got = counters and counters()
    if got is None:
        return None
    blocks, rows = got
    return sum(blocks) / sum(rows) if sum(rows) else None

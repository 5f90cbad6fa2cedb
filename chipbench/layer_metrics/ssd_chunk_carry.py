"""The share of a head's state that survives a published chunk: the mean
over the Mamba layers of the program's counter ``ssd.chunk_carry`` (the
family's ``ssd_counters()``: per layer the mean, over heads and over the
sequence's chunks of ``mamba_chunk_size`` consecutive tokens, of ``exp(sum
of dt_t A_h over the chunk)``, of the LAST step; ``Trainer.free()`` reads
it once after the window), in percent. It says whether the cell works the
carry between chunks at all: a kernel that dropped its state would be
invisible at a carry of zero. A count, so it is reported off the chip
too; nothing to read where the family or the program records no such
event."""


def read(facts):
    counters = getattr(facts["family"], "ssd_counters", None)
    carry = counters and counters()
    if not carry:
        return None
    return 100.0 * sum(carry) / len(carry)

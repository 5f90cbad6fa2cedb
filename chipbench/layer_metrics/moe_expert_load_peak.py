"""How uneven the routing was: over the blocks, the largest of (the
busiest expert's rows / the even share), from the program's counter.

``nn.RoutedExperts`` adds every call's ``bincount`` into an int32 buffer
on the device; the family's ``Trainer.free()`` reads the buffers once,
after the window, and records them into ``paddle_tpu.obs.ring()`` as the
instant ``moe.tokens_per_expert`` (``counts`` [blocks, E]). The counts
run from the trainer's build, so set-up's three steps are among the
window's hundreds. 1.0 = every expert of every block got its share. A
count, so it is reported off the chip too; nothing to read where the
program records no such event."""


def read(facts):
    try:
        from paddle_tpu import obs
    except ImportError:
        return None
    found = [e for e in obs.ring().dump()
             if e.get("name") == "moe.tokens_per_expert"]
    if not found:
        return None
    peak = 0.0
    for block in found[-1]["args"]["counts"]:
        if sum(block) > 0:
            peak = max(peak, max(block) * len(block) / sum(block))
    return peak or None

"""Of the hidden units of the rows the held experts were given, the share
the experts' activation leaves other than zero — under ReGLU those with
``relu(m Wg) > 0`` — in percent, the mean over the routed blocks. The
family's ``Trainer.free()`` computes it ONCE after the window, off the
timed step: one forward of the last batch through the trained model's
own halves and the activation its experts declare, recorded into
``obs.ring()`` as ``moe.act_live_share``. It says how sparse the weights
make the published activation (seeded ones: about 50; SiLU would read
100): nothing in the program skips a dead unit yet, so today it is a
property of the weights, and the number a kernel that did skip them
would be sized by. A count, so it is reported off the chip too; nothing
to read where the program records no such event."""


def read(facts):
    try:
        from paddle_tpu import obs
    except ImportError:
        return None
    found = [e for e in obs.ring().dump()
             if e.get("name") == "moe.act_live_share"]
    if not found:
        return None
    shares = found[-1]["args"]["shares"]
    return 100.0 * sum(shares) / len(shares) if shares else None

"""Job kind ``train``: the compiled train step on fresh seeded batches.

Traffic file: ``batch``, ``seq`` (global batch and sequence length) and
``trace_seconds``. The configuration's ``limits.train`` holds the limits
of the comparison.

Order of a run: the plain reference follows the first two steps from the
seed and is freed; set-up builds ONE trainer (the compiled step with its
state), drives it through its first three steps by the window's own call
and feed, and hands the same object to the window. Compared: each of the
two followed steps' loss, the norm of the first gradient as the optimizer
got it (from its state after step one), the norms of both AdamW moments
after two steps, and the norm of each matrix's change after two steps
against the norm the configuration's storage should give it (rounding
noise included) — the norms by the worst leaf.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict

from .. import compare, schedule, stats

CHECK_STEPS = 2   # steps the reference follows
FIRST_STEPS = 3   # steps set-up drives (two compile, the third is steady)


def first_steps(trainer, batches, say=lambda m: None) -> Dict:
    """Drive the trainer through its first steps; what the comparison
    reads of them."""
    out = {"loss": []}
    for n, (ids, labels) in enumerate(batches):
        out["loss"].append(trainer.step(ids, labels))
        if n == 0:
            out["grad_norm"] = trainer.grad_norms()
        if n == CHECK_STEPS - 1:
            out["delta_norm"] = trainer.delta_norms()
            out["moment_norm"] = trainer.moment_norms()
        say(f"first step {n + 1}: loss={out['loss'][-1]:.6f} "
            f"t={time.perf_counter():.1f}")
    return out


def compare_steps(program: Dict, reference: Dict, limits: Dict, matrices):
    """The checks of a training cell, each beside its limit. The change of
    the parameters is compared on the ``matrices`` alone: a vector that
    starts at 1.0 (a LayerNorm scale) moves by a hundredth of a bfloat16
    step, so under stochastic rounding a few dozen of its elements jump
    a whole step and the rest stay: too few to hold to their expectation.
    The moments are compared each against its own kind (``m/``, ``v/``):
    their scales differ by orders of magnitude."""
    checks = [
        compare.check(f"loss_gap.step{n + 1}",
                      abs(program["loss"][n] - reference["loss"][n]),
                      limits["loss_gap"][n])
        for n in range(CHECK_STEPS)]
    gaps = compare.leaf_gaps
    checks.append(compare.check_worst_leaf(
        "grad_norm_gap.worst_leaf",
        gaps(program["grad_norm"], reference["grad_norm"]),
        limits["grad_norm_gap"]))
    moments = reference["moment_norm"]
    by_kind = [gaps(program["moment_norm"], moments,
                    [n for n in moments if n.startswith(tag)])
               for tag in ("m/", "v/")]
    checks.append(compare.check_worst_leaf(
        "moment_norm_gap.worst_leaf", {**by_kind[0], **by_kind[1]},
        limits["moment_norm_gap"]))
    checks.append(compare.check_worst_leaf(
        "delta_norm_gap.worst_matrix",
        gaps(program["delta_norm"], reference["delta_norm"], matrices),
        limits["delta_norm_gap"]))
    return checks


def run(ctx: Dict) -> Dict:
    from paddle_tpu.analysis import recompile_guard

    cfg, traffic, family = ctx["config"], ctx["traffic"], ctx["family"]
    seed, seconds, tracer, say = (ctx["seed"], ctx["seconds"], ctx["tracer"],
                                  ctx["say"])
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    vocab = family.sizes(cfg)["vocab"]

    def make_batch(step):
        return schedule.train_batch(seed, step, batch, seq, vocab)

    batches = [make_batch(n) for n in range(FIRST_STEPS)]
    t0 = time.perf_counter()
    reference = family.reference_training(cfg, seed, batches[:CHECK_STEPS])
    gc.collect()
    reference_s = time.perf_counter() - t0
    matrices = family.matrix_leaves(cfg)
    limits = cfg["limits"]["train"]
    control = []
    if ctx.get("control"):
        # the control (tools/control.py, never a benchmark run): the
        # reference in the precision below, put in the program's place;
        # made, like the reference, before the program's state exists
        low = family.reference_training(cfg, seed, batches[:CHECK_STEPS],
                                        ctx["control"])
        control = compare_steps(low, reference, limits, matrices)
        del low
        gc.collect()
        reference_s = time.perf_counter() - t0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in ctx["devices"])
    say(f"reference: two steps in {reference_s:.1f} s "
        f"loss={reference['loss']}; device peak so far {peak} bytes")

    t0 = time.perf_counter()
    trainer = family.Trainer(cfg, seed)
    say(f"trainer built in {time.perf_counter() - t0:.1f} s")
    program = first_steps(trainer, batches, say)
    say(f"first steps done {time.perf_counter() - t0:.1f} s after the build "
        "began")
    checks = compare_steps(program, reference, limits, matrices)
    notes = {"delta_norm_gap.all_leaves": max(compare.leaf_gaps(
        program["delta_norm"], reference["delta_norm"]).values()),
        "reference_s": reference_s}
    for c in control:
        notes["control." + c["name"]] = c["value"]

    trace_s = min(float(traffic["trace_seconds"]), seconds)
    step_s, tracing = [], False
    with recompile_guard() as guard:
        t_win = time.perf_counter()
        setup_s = t_win - ctx["t_start"] - reference_s
        n = FIRST_STEPS
        while True:
            t_a = time.perf_counter()
            if t_a - t_win >= seconds:
                break
            if tracer.on and not tracing and t_a - t_win >= seconds - trace_s:
                tracer.start()
                tracing = True
                t_a = time.perf_counter()
            with tracer.span("make_batch"):
                ids, labels = make_batch(n)
            with tracer.span("train.step"):
                loss = trainer.step(ids, labels)
            step_s.append(time.perf_counter() - t_a)
            n += 1
        elapsed = time.perf_counter() - t_win
    if tracing:
        tracer.stop()
    steps = len(step_s)
    failed = steps if math.isnan(loss) else 0  # NaN: nothing trained
    tokens_per_s = steps * batch * seq / elapsed
    say(f"window: {steps} steps in {elapsed:.3f} s, last loss {loss:.4f}, "
        f"median step {1e3 * stats.median(step_s):.2f} ms, "
        f"compiles {guard.count()}")
    checks.append(compare.check("compiles_in_window", guard.count(), 0))
    trainer.free()
    return {
        "checks": checks, "notes": notes,
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "facts": {"step_s": step_s, "compiles": guard.count(),
                  "tokens_per_s": tokens_per_s, "seq": seq, "batch": batch},
    }

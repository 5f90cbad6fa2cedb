"""Job kind ``train_routed``: ``train`` for a family whose blocks route
their tokens to experts.

Top-1 routing is discrete. Where the program's bfloat16 activations put a
token's two best experts in the other order than the float32 reference
has them, the token meets another expert, and every number downstream is
another number: with a free-running reference the norms ``train``
compares read alike for a sound program and for one a precision lower
(PERF.md section 2). So here the reference FOLLOWS the program: the
compiled step returns, beside its loss, the expert every token met in
every block; the reference is told those experts for the two steps it
follows and computes everything else itself, the gate values included,
and the checks of ``train`` (``compare_steps``, under ``limits.train``)
then compare arithmetic with arithmetic. Beside them
``route_flip_share.mean``: the share of (token, block) pairs of step one
where the expert the program's step chose is not the one the reference
would have chosen at that point, on the same rows of the stream.

Since the reference needs what the program did, the order of a run is
the other way round than ``train``'s: set-up builds ONE trainer and
drives it through its first three steps, the window gets the same object
and frees it, and only then the reference follows the first two steps
(it holds up to 9.6 GB on the device: it cannot sit beside the program's
state) and the comparison is made. ``first_steps`` and ``compare_steps``
are ``train``'s own.

Needs of the family beyond ``train``'s: ``Trainer.routing()`` ([blocks,
B, S] of the last step, as the compiled step returned it) and
``reference_training(..., follow=[routing of step one, of step two])``
whose result holds ``routing``, [blocks] of (the reference's own choice
[B, S], top probability).
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict

import numpy as np

from .. import compare, schedule, stats
from .train import CHECK_STEPS, FIRST_STEPS, compare_steps, first_steps


def flip_share(a, b) -> float:
    """Share of (block, token) pairs two routings [blocks, ...] send to
    different experts."""
    a, b = np.asarray(a), np.asarray(b)
    return float(np.mean(a.reshape(a.shape[0], -1)
                         != b.reshape(b.shape[0], -1)))


class Recorded:
    """A trainer whose every step's routing is kept, as the compiled step
    returned it."""

    def __init__(self, trainer):
        self.trainer, self.routing = trainer, []

    def step(self, ids, labels) -> float:
        loss = self.trainer.step(ids, labels)
        self.routing.append(self.trainer.routing())
        return loss

    def __getattr__(self, name):
        return getattr(self.trainer, name)


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
    trainer = family.Trainer(cfg, seed)
    say(f"trainer built in {time.perf_counter() - t0:.1f} s")
    first = Recorded(trainer)
    program = first_steps(first, batches, say)
    say(f"first steps done {time.perf_counter() - t0:.1f} s after the build "
        "began")

    # the window: jobs/train.py's, on the same object
    trace_s = min(float(traffic["trace_seconds"]), seconds)
    step_s, tracing = [], False
    with recompile_guard() as guard:
        t_win = time.perf_counter()
        setup_s = t_win - ctx["t_start"]
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
    quarter = max(1, steps // 4)
    say(f"window: {steps} steps in {elapsed:.3f} s, last loss {loss:.4f}, "
        f"median step {1e3 * stats.median(step_s):.2f} ms (first quarter "
        f"{1e3 * stats.median(step_s[:quarter]):.2f}, last "
        f"{1e3 * stats.median(step_s[-quarter:]):.2f}: the routing's drift), "
        f"compiles {guard.count()}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in ctx["devices"])
    trainer.free()
    first.trainer = trainer = None
    gc.collect()

    # the comparison, after the program's state has left the device
    t0 = time.perf_counter()
    follow = first.routing[:CHECK_STEPS]
    reference = family.reference_training(cfg, seed, batches[:CHECK_STEPS],
                                          follow=follow)
    say(f"reference: two steps in {time.perf_counter() - t0:.1f} s "
        f"loss={reference['loss']}; device peak before it {peak} bytes")
    own = np.stack([e for e, _ in reference["routing"]])
    matrices = family.matrix_leaves(cfg)
    limits = cfg["limits"]["train"]
    checks = compare_steps(program, reference, limits, matrices)
    checks.append(compare.check(
        "route_flip_share.mean", flip_share(follow[0], own),
        limits["route_flip_share"]))
    checks.append(compare.check("compiles_in_window", guard.count(), 0))
    notes = {"delta_norm_gap.all_leaves": max(compare.leaf_gaps(
        program["delta_norm"], reference["delta_norm"]).values()),
        "reference_s": time.perf_counter() - t0,
        "program_memory_peak_bytes": peak}
    if ctx.get("control"):
        # the control (tools/control.py, never a benchmark run): the
        # reference in the precision below, following the same routing,
        # put in the program's place
        low = family.reference_training(cfg, seed, batches[:CHECK_STEPS],
                                        ctx["control"], follow=follow)
        for c in compare_steps(low, reference, limits, matrices):
            notes["control." + c["name"]] = c["value"]
        notes["control.route_flip_share.mean"] = flip_share(
            np.stack([e for e, _ in low["routing"]]), own)
    return {
        "checks": checks, "notes": notes,
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "facts": {"step_s": step_s, "compiles": guard.count(),
                  "tokens_per_s": tokens_per_s, "seq": seq, "batch": batch},
    }

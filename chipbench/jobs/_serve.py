"""The serving loop the ``serve_open`` and ``serve_closed`` jobs share:
one process, one thread; submit what is due, step the engine, stamp each
new token on the benchmark's own clock when the step returns.

A *source* decides what is due: ``due(now)`` yields the items to submit
at ``now`` (seconds from the window's start), ``finished(item, now)`` is
told of each completion, ``next_due()`` is the next instant something
will be due (``None`` when that depends on a completion), and
``attempted(requests, seconds)`` picks, once the window has closed, the
requests that count as attempted; of those, the ones that did not return
``ok`` with every token asked for are failed.

After the window a seeded sample of the finished requests, the longest
among them, is run once through the family's plain reference (the engine
is freed first); compared is the widest gap by which a served token's
logit lies below the reference's best. Greedy decoding only.
"""
from __future__ import annotations

import random
import time
from typing import Dict, List

import numpy as np

from .. import compare, schedule, stats

WARM = ((40, 3), (700, 2))  # (prompt, new tokens): one chunk, several


class Req:
    __slots__ = ("item", "handle", "seen", "times", "t_due", "t_submit",
                 "t_done", "prompt")

    def __init__(self, item, prompt, t_due):
        self.item, self.prompt, self.t_due = item, prompt, t_due
        self.handle, self.seen, self.times = None, 0, []
        self.t_submit = self.t_done = None

    def ok(self) -> bool:
        h = self.handle
        return (self.t_done is not None and h.status == "ok"
                and len(h.out) == self.item["max_new_tokens"])


def _warm(server, vocab: int, max_len: int) -> None:
    rng = np.random.default_rng(0)
    for n, (p, new) in enumerate(WARM):
        p = min(p, max_len - new - 1)
        server.submit(f"warm{n}", rng.integers(0, vocab, p, dtype=np.int32),
                      new)
    while not server.idle():
        server.step()


def pad_length(traffic: Dict, positions: int) -> int:
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    return min(positions, -(-longest // 128) * 128)


def run(ctx: Dict, make_source) -> Dict:
    from paddle_tpu.analysis import recompile_guard

    cfg, traffic, family = ctx["config"], ctx["traffic"], ctx["family"]
    seed, seconds, tracer, say = (ctx["seed"], ctx["seconds"], ctx["tracer"],
                                  ctx["say"])
    z = family.sizes(cfg)
    items = schedule.generate(traffic, seed, seconds)
    prompts = [schedule.prompt_tokens(seed, it, z["vocab"]) for it in items]
    source = make_source(traffic, items)
    t_b = time.perf_counter()
    server = family.Server(cfg, seed)
    say(f"server built in {time.perf_counter() - t_b:.1f} s")
    _warm(server, z["vocab"], cfg["engine"]["max_len"])
    say(f"warmed up {time.perf_counter() - t_b:.1f} s after the build began")
    c0 = server.counters()

    trace_s = min(float(traffic["trace_seconds"]), seconds)
    inflight: Dict[int, Req] = {}
    done: List[Req] = []
    all_reqs: List[Req] = []
    steps = []   # (start_s, wall_s, d_decode, d_prefill, live_kv) per step
    trace_from = None
    free_min = server.free_blocks()
    last = dict(c0)
    tracing = False
    with recompile_guard() as guard:
        t0 = time.perf_counter()
        setup_s = t0 - ctx["t_start"]
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            if tracer.on and not tracing and now >= seconds - trace_s:
                tracer.start()
                tracing = True
                now = trace_from = time.perf_counter() - t0
            with tracer.span("submit"):
                for item, t_due in source.due(now):
                    r = Req(item, prompts[item["i"]], t_due)
                    r.t_submit = time.perf_counter() - t0
                    r.handle = server.submit(
                        item["i"], r.prompt, item["max_new_tokens"])
                    inflight[item["i"]] = r
                    all_reqs.append(r)
            if server.idle():
                nxt = source.next_due()
                wake = seconds if nxt is None else min(nxt, seconds)
                with tracer.span("wait"):
                    time.sleep(max(0.0, min(wake - now, 0.05)))
                continue
            t_a = time.perf_counter()
            with tracer.span("engine.step"):
                finished = server.step()
            t_b = time.perf_counter()
            with tracer.span("harvest"):
                stamp, live = t_b - t0, 0
                for r in inflight.values():
                    n = len(r.handle.out)
                    if n > r.seen:
                        if r.seen:  # a decode lane: it read its whole cache
                            live += len(r.prompt) + r.seen
                        r.times.extend([stamp] * (n - r.seen))
                        r.seen = n
                for h in finished:
                    r = inflight.pop(h.req_id, None)
                    if r is not None:
                        r.t_done = stamp
                        done.append(r)
                        source.finished(r.item, stamp)
                c = server.counters()
                steps.append((t_a - t0, t_b - t_a,
                              c["decode_tokens"] - last["decode_tokens"],
                              c["prefill_tokens"] - last["prefill_tokens"],
                              live))
                last = c
                free_min = min(free_min, server.free_blocks())
        elapsed = time.perf_counter() - t0
    if tracing:
        tracer.stop()
    c1 = server.counters()
    num_blocks = server.num_blocks
    server.free()

    attempted = source.attempted(all_reqs, seconds)
    failed = [r for r in attempted if not r.ok()]
    served = [r for r in done if r.ok()]
    tokens = sum(len(r.prompt) + len(r.handle.out) for r in served)
    e2e = {"serve_tokens_per_s": tokens / elapsed, "setup_s": setup_s}
    first = [stats.ttft(r.t_due, r.times) for r in attempted if r.times]
    itl = stats.all_gaps(r.times for r in attempted)
    if first:
        e2e["ttft_p50_ms"] = 1e3 * stats.median(first)
        e2e["ttft_p95_ms"] = 1e3 * stats.percentile(first, 95)
        say(f"ttft_p50_ms={e2e['ttft_p50_ms']:.3f} "
            f"ttft_p95_ms={e2e['ttft_p95_ms']:.3f} n={len(first)}")
    if itl:
        for q in (90, 95, 99):
            e2e[f"itl_p{q}_ms"] = 1e3 * stats.percentile(itl, q)
        say(f"itl_p50_ms={1e3 * stats.median(itl):.3f} "
            f"itl_p90_ms={e2e['itl_p90_ms']:.3f} "
            f"itl_p95_ms={e2e['itl_p95_ms']:.3f} "
            f"itl_p99_ms={e2e['itl_p99_ms']:.3f} n={len(itl)}")
    say(f"window: {elapsed:.3f} s, submitted {len(all_reqs)}, attempted "
        f"{len(attempted)}, finished {len(served)}, failed {len(failed)}, "
        f"steps {len(steps)}, tokens {tokens}, compiles {guard.count()}")
    if served:
        lat = [r.t_done - r.t_submit for r in served]
        say(f"request service time: median {stats.median(lat):.3f} s, "
            f"longest {max(lat):.3f} s (drain_s is {traffic.get('drain_s')})")

    # -- correct: the served tokens against the plain reference ----------
    checks = [compare.check("compiles_in_window", guard.count(), 0)]
    t_ref = time.perf_counter()
    worst, n_tok = float("inf"), 0
    if served:
        rng = random.Random(seed)
        longest = max(served, key=lambda r: len(r.prompt) + len(r.handle.out))
        others = [r for r in served if r is not longest]
        k = min(int(traffic["check_requests"]) - 1, len(others))
        sample = [longest] + rng.sample(others, k)
        ref = family.reference(cfg, seed)
        pad = pad_length(traffic, z["positions"])
        worst = 0.0
        # the control (tools/control.py, never a benchmark run): at the
        # same positions, the token the precision below puts first
        low = (family.reference(cfg, seed, ctx["control"])
               if ctx.get("control") else None)
        low_worst = total = low_total = 0.0
        for r in sample:
            out = np.asarray(r.handle.out, np.int32)
            gap = family.served_gaps(ref, r.prompt, out, pad)
            worst = max(worst, float(gap.max()))
            total += float(gap.sum())
            n_tok += len(out)
            if low is not None:
                gap = family.control_gaps(ref, low, r.prompt, out, pad)
                low_worst = max(low_worst, float(gap.max()))
                low_total += float(gap.sum())
    limits = traffic.get("limits", cfg["limits"]["serve"])
    mean = total / n_tok if n_tok else float("inf")
    # the widest gap catches one wrong token; the mean gap is steady from
    # seed to seed and catches a loss of precision everywhere
    checks.append(compare.check("served_token_gap.widest", worst,
                                limits["token_gap_widest"]))
    checks.append(compare.check("served_token_gap.mean", mean,
                                limits["token_gap_mean"]))
    notes = {"reference_s": time.perf_counter() - t_ref,
             "checked_tokens": n_tok}
    if ctx.get("control"):
        notes["control.served_token_gap.widest"] = low_worst
        notes["control.served_token_gap.mean"] = low_total / max(n_tok, 1)

    lag = [r.t_submit - r.t_due for r in all_reqs]
    return {
        "checks": checks, "notes": notes,
        "attempted": len(attempted), "failed": len(failed),
        "end_to_end": e2e,
        "facts": {"engine_steps": steps, "lag_s": lag,
                  "trace_from_s": trace_from,
                  "counters": {k: c1[k] - c0[k] for k in c1},
                  "free_blocks_min": free_min, "num_blocks": num_blocks,
                  "compiles": guard.count(), "elapsed_s": elapsed},
    }

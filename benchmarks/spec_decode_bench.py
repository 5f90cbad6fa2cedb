"""Speculative-decoding serving row: acceptance rate x
decode tokens/s at draft depth k in {2, 4, 8} vs the k=None baseline,
same engine, same session.

Methodology (RTT-free by subtraction, decode_bench.py style): each
row times TWO full engine drains of the same warm engine config —
max_new_tokens = NEW_BIG and NEW_SMALL — and reports
(t_big - t_small) / (tokens_big - tokens_small): prefill, admission
and any residual compile cancel, leaving pure steady-state decode.
Speculation's win is TOKENS PER DISPATCH: a verify round emits
1 + accepted tokens per slot where plain decode emits exactly 1, so
at host-RTT-bound serving sizes tok/s scales with the acceptance
rate. The workload is REPETITIVE prompts (shared n-gram structure,
the prompt-lookup proposer's habitat — retrieval/code/boilerplate
traffic in production terms).

Runs under the ``BENCH_TOTAL_BUDGET`` supervisor deadline (default
600 s; rows emit incrementally so a timeout still lands partial
JSON). CPU smoke mode engages automatically off-TPU (tiny model,
small budgets) — it validates the harness and the acceptance-rate
plumbing, not absolute throughput.

    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/spec_decode_bench.py

ref: Leviathan et al. 2023 (speculative sampling), Saxena 2023
(prompt lookup decoding), vLLM ngram speculative config.
"""
import argparse
import json
import os
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils.retries import Deadline


def build_engine(model, on_tpu, spec_k, max_len):
    if on_tpu:
        B, BS, PAD = 8, 64, 2048
    else:
        B, BS, PAD = 4, 8, 64
    return ContinuousBatchingEngine(
        model, max_batch=B, max_len=max_len, block_size=BS,
        num_blocks=B * (-(-max_len // BS)) + 2, prompt_pad=PAD,
        spec_decode_k=spec_k)


def timed_drain(eng, prompts, new_tokens, tag):
    """One full drain on an ALREADY-WARM engine (the engine's compiled
    phases persist across drains, so the big-minus-small subtraction
    cancels prefill + host scheduling, leaving steady-state decode)."""
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        eng.add_request(f"{tag}{i}", p, max_new_tokens=new_tokens)
    done = eng.run()
    wall = time.perf_counter() - t0
    toks = sum(len(done[f"{tag}{i}"].out) for i in range(len(prompts)))
    return wall, toks


def spec_row(model, on_tpu, spec_k, prompts, big, small, max_len):
    eng = build_engine(model, on_tpu, spec_k, max_len)
    # warm every phase outside the measured window (incl. the spec
    # verify program: a repetitive warm prompt guarantees a draft)
    warm = np.tile(np.arange(4, dtype=np.int32), 6)
    eng.add_request("warm", warm, max_new_tokens=8)
    eng.run()
    st0, rounds0 = eng.spec_stats(), eng.spec_slot_rounds
    w_big, t_big = timed_drain(eng, prompts, big, "b")
    st1, rounds1 = eng.spec_stats(), eng.spec_slot_rounds
    w_small, t_small = timed_drain(eng, prompts, small, "s")
    tps = (t_big - t_small) / max(w_big - w_small, 1e-9)
    # every quality stat is a BIG-WINDOW delta, matching the tok/s
    # methodology (the warm request's rounds must not contaminate)
    proposed = st1["proposed"] - st0["proposed"]
    accepted = st1["accepted"] - st0["accepted"]
    emitted = st1["emitted"] - st0["emitted"]
    rounds = rounds1 - rounds0
    return tps, {
        "acceptance_rate": (accepted / proposed) if proposed else 0.0,
        "tokens_per_slot_round": (emitted / rounds) if rounds else 0.0,
        "proposed_big_window": proposed,
        "emitted_big_window": emitted,
    }


def main():
    argparse.ArgumentParser().parse_args()
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.9)

    if on_tpu:
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048)
        P, NEW_BIG, NEW_SMALL, MAX_LEN, NPROMPT = 512, 256, 16, 1024, 8
    else:
        config = LlamaConfig.tiny()
        P, NEW_BIG, NEW_SMALL, MAX_LEN, NPROMPT = 16, 24, 6, 64, 4

    paddle.seed(0)
    model = LlamaForCausalLM(config)
    if on_tpu:
        model.bfloat16()

    rng = np.random.RandomState(0)
    # repetitive prompts: a short base phrase tiled to length P
    prompts = []
    for i in range(NPROMPT):
        base = rng.randint(0, config.vocab_size, (P // 4,))
        prompts.append(np.tile(base, 5)[:P].astype(np.int32))

    rows = {}
    baseline_tps = None
    for k in (None, 2, 4, 8):
        if dl.expired():
            from paddle_tpu.obs.regress import bench_record
            bench_record("spec_decode", "spec_decode_best_speedup",
                         None, "", error="budget exhausted",
                         partial=rows)
            return
        tps, st = spec_row(model, on_tpu, k, prompts, NEW_BIG,
                           NEW_SMALL, MAX_LEN)
        label = "off" if k is None else f"k{k}"
        rows[label] = {
            "tok_s": round(tps, 1),
            "acceptance_rate": round(st["acceptance_rate"], 4),
            "tokens_per_slot_round": round(st["tokens_per_slot_round"], 3),
        }
        if k is None:
            baseline_tps = tps
        else:
            rows[label]["speedup"] = round(tps / baseline_tps, 3)
        print(f"[spec] {label}: {tps:.0f} tok/s  "
              f"accept={st['acceptance_rate']:.3f}  "
              f"tok/slot-round={st['tokens_per_slot_round']:.2f}",
              flush=True)

    best = max((r["speedup"] for r in rows.values() if "speedup" in r),
               default=None)
    from paddle_tpu.obs.regress import bench_record
    bench_record(
        "spec_decode", "spec_decode_best_speedup", best,
        "x decode tok/s vs spec-off (best k)",
        extra={
            "rows": rows,
            "prompt_len": P,
            "new_tokens_big_small": [NEW_BIG, NEW_SMALL],
            "device": getattr(dev, "device_kind", str(dev)),
            "cpu_smoke": not on_tpu,
        })


if __name__ == "__main__":
    main()

"""Continuous-batching serving benchmarks: sustained throughput at
fixed HBM, and the mixed-prompt-length latency comparison chunked
prefill exists for.

Part 1 (sustained): requests with mixed prompt lengths arrive
continuously, finish at different times, and the engine recycles their
blocks into new admissions — report sustained decode tokens/s and slot
occupancy (the workload paged KV exists for; serving_capacity.py
shows the memory win, this measures the LOOP).

Part 2 (mixed 128–4096): the same engine serves a workload whose
prompt lengths span 128–4096 under BOTH prefill policies —
whole-prompt (one padded prefill stalls every in-flight decode for the
full prompt) and chunked (``prefill_chunk`` tokens per step under
``max_num_batched_tokens``, decode-priority). Reports time-to-first-
token and p50/p99 inter-token latency per mode; the acceptance claim
is chunked p99 ITL strictly better than whole-prompt.

Part 4 (``--router``, ISSUE 6): a 2-replica ClusterRouter serving a
shared-prefix mixed-priority workload twice — engine prefix cache ON
vs OFF — with prefix-affinity placement. Reports the measured cluster
prefix-hit-rate, TTFT p50/p99 per mode (chunked prefill inside each
replica, so cached tokens are chunks never scheduled), and per-replica
routed/shed/expired counters. The acceptance claim: hit-rate > 0 and
cache-on TTFT p50 strictly better than cache-off.

Part 5 (``--disagg``, ISSUE 8): decode p99 inter-token latency under
concurrent 4096-token prefills — disaggregated prefill/decode (one
prefill + one decode worker PROCESS over a TCPKVStore with crash-safe
KV-block handoff) vs the unified chunked engine — plus a measured
graceful-degradation phase (prefill worker killed; new prompts must
complete via colocated fallback with zero shed). NB the CPU row
measures MECHANISM (zero loss, fallback, ITL distribution): at tiny-
model scale the base64/TCP transport dominates and a 256-token chunk
costs single-digit ms, so unified chunked wins on CPU; the latency-
independence claim is the TPU column, where a real model's chunk
stalls decode for tens of ms and transfers ride ICI/DMA.

Part 6 (``--overlap``, ISSUE 10): the async host/device pipelining
A/B — the SAME decode-heavy chunked workload served by the sync engine
(blocking D2H fetch + full table/cache_len re-upload every step) and
the ``overlap=True`` engine (device-resident step state, lag-1 copy
ring, dirty-slot uploads). Reports per mode: decode tokens/s, the
decode-phase host-blocked fraction (blocked-in-fetch seconds / step
seconds, steady-state delta), and H2D upload bytes per decode token —
the two quantities the pipeline exists to shrink — plus a BITWISE
output-stream equality check (the token-exactness acceptance gate).
On CPU the dispatch itself is cheap, so the blocked-fraction drop is
the mechanism proof; the tok/s column on the chip is not measured.

Part 7 (``--obs``, ISSUE 12): the observability-overhead A/B — the
SAME sustained decode workload with trace recording ON vs OFF
(``obs.set_enabled``; the metrics registry stays live in both modes —
it backs the engine's own counters). Whole-run A/B cannot resolve a
sub-2% effect (run-to-run drift is ±5-8%), so recording is toggled
per STEP inside one engine run: adjacent steady decode steps sample
identical machine conditions, paired (on − off) diffs are
trimmed-mean'd against the off-step time, reporting tok/s for both
columns and asserting the obs-on overhead stays under 2% — the budget
that lets tracing default to on in production.

Part 3 (``--overload``, ISSUE 4): offered load ≈ 2x measured capacity,
mixed interactive/batch priorities with per-class deadlines, admission
control ON. The overload-control claim: every rejection happens at
admission (``status="shed"``, zero accepted-then-expired), batch
traffic absorbs the shedding, and admitted interactive p99 TTFT stays
inside the interactive deadline. The whole scenario runs under a
``Deadline`` carved from ``BENCH_TOTAL_BUDGET`` (default 600 s) and
always emits its JSON line inside that window.

    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/serving_throughput.py
    # --sustained-only / --mixed-only to run one part; --overload for
    # the overload-control scenario alone

ref: python/paddle/incubate/nn/functional/block_multihead_attention.py
(the reference's serving kernel; no published numbers in-tree),
Yu et al. OSDI'22 (Orca), Agrawal et al. OSDI'24 (Sarathi-Serve),
Zhou et al. SOSP'19 (DAGOR overload control).
"""
import argparse
import dataclasses
import json
import os
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference.admission import AdmissionConfig
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils.retries import Deadline


def _emit(doc: dict) -> None:
    """One metric line through the shared obs ledger writer (ISSUE 15):
    same stdout contract as the old hand-rolled ``print(json.dumps(...))``
    lines, plus the schema'd append to ``BENCH_LEDGER`` when set."""
    from paddle_tpu.obs.regress import bench_record

    bench_record("serving_throughput", doc["metric"], doc.get("value"),
                 doc.get("unit", ""), extra=doc.get("extra"))


def _pct(xs, p):
    return round(float(np.percentile(xs, p)) * 1000, 2) if xs else None


def sustained(model, config, on_tpu, dev):
    if on_tpu:
        B, MAX_LEN, BS, PAD = 64, 2048, 64, 512
        NUM_BLOCKS = B * (640 // BS) + 16  # ~640 live tokens/seq budget
        N_REQ, GEN = 192, 128
        prompt_lens = (256, 384, 512)
    else:  # mechanics check
        B, MAX_LEN, BS, PAD = 4, 64, 8, 16
        NUM_BLOCKS = 4 * 4 + 2
        N_REQ, GEN = 12, 8
        prompt_lens = (5, 9, 14)

    rng = np.random.RandomState(0)
    eng = ContinuousBatchingEngine(
        model, max_batch=B, max_len=MAX_LEN, block_size=BS,
        num_blocks=NUM_BLOCKS, prompt_pad=PAD,
        decode_chunk=16 if on_tpu else 4)
    for i in range(N_REQ):
        plen = int(prompt_lens[i % len(prompt_lens)])
        eng.add_request(i, rng.randint(0, config.vocab_size, (plen,)),
                        max_new_tokens=GEN)

    # warm both compiled phases outside the timed region; throughput
    # counts only tokens produced inside the timed window
    eng.step()
    warm_toks = eng.decode_tokens
    t0 = time.perf_counter()
    occupancy = []
    while eng._queue or eng.num_active:
        eng.step()
        occupancy.append(eng.num_active)
    dt = time.perf_counter() - t0
    done = eng._completed
    assert len(done) == N_REQ, (len(done), N_REQ)
    toks = eng.decode_tokens - warm_toks
    _emit({
        "metric": "serving_decode_tokens_per_sec",
        "value": round(toks / dt, 1),
        "unit": "tokens/s",
        "extra": {
            "requests": N_REQ, "gen_per_req": GEN, "max_batch": B,
            "num_blocks": NUM_BLOCKS, "block_size": BS,
            "decode_chunk": eng.decode_chunk,
            "mean_occupancy": round(float(np.mean(occupancy)), 2),
            "steps": eng.steps, "wall_s": round(dt, 2),
            "device": getattr(dev, "device_kind", str(dev)),
        },
    })


def _run_mixed_mode(model, config, *, chunked, B, MAX_LEN, BS, PAD, CHUNK,
                    N_REQ, GEN, prompt_lens):
    kw = dict(max_batch=B, max_len=MAX_LEN, block_size=BS,
              num_blocks=B * (-(-MAX_LEN // BS)) + 4, decode_chunk=1)
    if chunked:
        kw.update(prefill_chunk=CHUNK)  # budget defaults to B + CHUNK
    else:
        kw.update(prompt_pad=PAD)
    eng = ContinuousBatchingEngine(model, **kw)
    # compile both phases outside the measured workload
    eng.add_request("warm", np.ones(1, np.int32), max_new_tokens=2)
    eng.run()

    rng = np.random.RandomState(1)
    t0 = time.perf_counter()
    for i in range(N_REQ):
        plen = int(prompt_lens[i % len(prompt_lens)])
        eng.add_request(i, rng.randint(0, config.vocab_size, (plen,)),
                        max_new_tokens=GEN)
    done = eng.run()
    wall = time.perf_counter() - t0
    reqs = [done[i] for i in range(N_REQ)]
    assert all(r.status == "ok" for r in reqs)
    ttfts = [r.ttft() for r in reqs]
    itls = [d for r in reqs for d in r.inter_token_latencies()]
    toks = sum(len(r.out) for r in reqs)
    return {
        "mode": "chunked" if chunked else "whole_prompt",
        "ttft_ms_p50": _pct(ttfts, 50), "ttft_ms_p99": _pct(ttfts, 99),
        "itl_ms_p50": _pct(itls, 50), "itl_ms_p99": _pct(itls, 99),
        "tokens_per_sec": round(toks / wall, 1),
        "wall_s": round(wall, 2), "steps": eng.steps,
        "max_step_tokens": eng.max_step_tokens,
        "prefill_chunk": CHUNK if chunked else None,
        "max_num_batched_tokens": eng.max_num_batched_tokens,
        "prompt_pad": None if chunked else PAD,
    }


def mixed(model, config, on_tpu, dev):
    """Mixed 128–4096 prompt lengths, whole-prompt vs chunked."""
    if on_tpu:
        B, MAX_LEN, BS, PAD, CHUNK = 16, 4352, 64, 4096, 512
        N_REQ, GEN = 48, 64
    else:
        B, MAX_LEN, BS, PAD, CHUNK = 2, 4160, 64, 4096, 256
        N_REQ, GEN = 6, 12
    prompt_lens = (128, 4096, 512, 2048)

    rows = []
    for chunked in (False, True):
        row = _run_mixed_mode(
            model, config, chunked=chunked, B=B, MAX_LEN=MAX_LEN, BS=BS,
            PAD=PAD, CHUNK=CHUNK, N_REQ=N_REQ, GEN=GEN,
            prompt_lens=prompt_lens)
        rows.append(row)
        _emit({
            "metric": "serving_mixed_prefill_latency",
            "value": row["itl_ms_p99"], "unit": "ms (p99 ITL)",
            "extra": {**row, "requests": N_REQ, "gen_per_req": GEN,
                      "max_batch": B, "prompt_lens": list(prompt_lens),
                      "device": getattr(dev, "device_kind", str(dev))},
        })
    whole, chunk = rows
    _emit({
        "metric": "serving_mixed_itl_p99_speedup",
        "value": round(whole["itl_ms_p99"] / chunk["itl_ms_p99"], 2),
        "unit": "x (whole-prompt p99 ITL / chunked p99 ITL)",
        "extra": {
            "chunked_p99_better":
                chunk["itl_ms_p99"] < whole["itl_ms_p99"],
            "ttft_ms_p50_whole": whole["ttft_ms_p50"],
            "ttft_ms_p50_chunked": chunk["ttft_ms_p50"],
        },
    })


def overload(model, config, on_tpu, dev):
    """~2x offered load with admission control: shed at the front door,
    keep interactive latency flat, never accept-then-expire."""
    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.85)  # reserve tail for the JSON emit
    if on_tpu:
        B, MAX_LEN, BS, PAD, GEN = 16, 1024, 64, 512, 48
        prompt_lens, n_req = (128, 256, 384), 192
    else:
        B, MAX_LEN, BS, PAD, GEN = 2, 64, 8, 16, 6
        prompt_lens, n_req = (5, 9, 14), 48

    def make_engine(admission=None):
        return ContinuousBatchingEngine(
            model, max_batch=B, max_len=MAX_LEN, block_size=BS,
            num_blocks=B * (-(-MAX_LEN // BS)) + 2, prompt_pad=PAD,
            admission=admission)

    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, config.vocab_size,
                           (int(prompt_lens[i % len(prompt_lens)]),))
               for i in range(n_req)]

    # calibration: closed-loop saturation measures the service capacity
    # (real tokens/s) and a per-request latency scale; both compiled
    # phases are warmed first so compile time cannot deflate capacity
    calib = make_engine()
    calib.add_request("warm", np.ones(1, np.int32), max_new_tokens=2)
    calib.run()
    n_cal = min(3 * B, n_req)
    t0 = time.perf_counter()
    for i in range(n_cal):
        calib.add_request(i, prompts[i], max_new_tokens=GEN)
    calib.run()
    cal_wall = time.perf_counter() - t0
    capacity_tps = (calib.prefill_tokens + calib.decode_tokens) / cal_wall
    lat_scale = cal_wall / max(n_cal / B, 1)  # ~ one admission wave

    interactive_ddl = max(8 * lat_scale, 1.0)
    batch_ddl = max(24 * lat_scale, 3.0)
    per_req_tokens = float(np.mean([p.size for p in prompts])) + GEN
    arrival_dt = per_req_tokens / (2.0 * capacity_tps)  # 2x offered load

    eng = make_engine(AdmissionConfig(
        max_queue=B, high_watermark=0.75,
        target_delay_s=interactive_ddl / 2))
    # each engine instance compiles its own phase programs: warm them
    # outside the measured window so compile latency cannot expire the
    # first admitted arrivals
    eng.add_request("warm", np.ones(1, np.int32), max_new_tokens=2)
    eng.run()
    del eng._completed["warm"]
    # the warm steps carried compile latency — drop them from the
    # service-rate EWMAs so feasibility reasons from steady-state speed
    eng.ewma_step_s = eng.ewma_step_tokens = None
    submitted = 0
    t0 = time.perf_counter()
    while ((submitted < n_req or eng._queue or eng.num_active)
           and not dl.expired()):
        now = time.perf_counter() - t0
        while submitted < n_req and now >= submitted * arrival_dt:
            i = submitted
            pri = "interactive" if i % 3 == 0 else "batch"
            eng.add_request(
                i, prompts[i], max_new_tokens=GEN, priority=pri,
                deadline=interactive_ddl if pri == "interactive"
                else batch_ddl)
            submitted += 1
        eng.step()
    wall = time.perf_counter() - t0

    done = eng._completed
    ok = [r for r in done.values() if r.status == "ok"]
    ok_inter = [r for r in ok if r.priority == "interactive"]
    ttfts = [r.ttft() for r in ok_inter if r.ttft() is not None]
    goodput = sum(len(r.out) for r in ok) / wall
    shed_total = eng.n_shed["interactive"] + eng.n_shed["batch"]
    _emit({
        "metric": "serving_overload_goodput",
        "value": round(goodput, 1),
        "unit": "ok tokens/s at ~2x offered load",
        "extra": {
            "submitted": submitted, "completed_ok": len(ok),
            "capacity_tokens_per_sec": round(capacity_tps, 1),
            "offered_x": 2.0,
            "shed_rate": round(shed_total / max(submitted, 1), 3),
            "shed_interactive": eng.n_shed["interactive"],
            "shed_batch": eng.n_shed["batch"],
            "accepted_then_expired": eng.n_expired,
            "ttft_ms_p99_interactive": _pct(ttfts, 99),
            "interactive_deadline_ms": round(interactive_ddl * 1000, 1),
            "batch_deadline_ms": round(batch_ddl * 1000, 1),
            "admission_level": eng.admission.level,
            "max_queue": B, "max_batch": B, "gen_per_req": GEN,
            "wall_s": round(wall, 2),
            "budget_s": budget_s,
            "stopped_early": dl.expired(),
            "device": getattr(dev, "device_kind", str(dev)),
        },
    })


def router(model, config, on_tpu, dev):
    """2-replica cluster, shared-prefix traffic, prefix cache on/off."""
    from paddle_tpu.inference.cluster import ClusterRouter, InProcessReplica
    from paddle_tpu.inference.serving import ContinuousBatchingEngine as CBE

    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.85)  # reserve tail for the JSON emit
    if on_tpu:
        B, MAX_LEN, BS, CHUNK, GEN = 8, 1024, 64, 256, 32
        n_req, plen_prefix, tail_lens = 64, 512, (64, 128)
        n_families = 4
    else:
        B, MAX_LEN, BS, CHUNK, GEN = 2, 128, 8, 16, 6
        n_req, plen_prefix, tail_lens = 24, 32, (5, 9)
        n_families = 2

    rng = np.random.RandomState(3)
    families = [rng.randint(0, config.vocab_size, (plen_prefix,))
                for _ in range(n_families)]
    workload = []
    for i in range(n_req):
        tail = rng.randint(0, config.vocab_size,
                           (int(tail_lens[i % len(tail_lens)]),))
        pri = "interactive" if i % 3 == 0 else "batch"
        workload.append(
            (i, np.concatenate([families[i % n_families], tail]), pri))

    def run_mode(prefix_cache):
        def factory():
            return CBE(model, max_batch=B, max_len=MAX_LEN, block_size=BS,
                       num_blocks=B * (-(-MAX_LEN // BS)) + 8,
                       prefill_chunk=CHUNK, prefix_cache=prefix_cache)

        reps = [InProcessReplica(f"r{i}", factory) for i in range(2)]
        # warm both replicas' compiled phases outside the timed window
        for rep in reps:
            rep.supervisor.submit(f"warm-{rep.replica_id}",
                                  np.ones(1, np.int32), max_new_tokens=2)
            while rep.supervisor.pending:
                rep.supervisor.step()
        rt = ClusterRouter(reps, block_size=BS)
        t0 = time.perf_counter()
        for rid, prompt, pri in workload:
            rt.submit(rid, prompt, max_new_tokens=GEN, priority=pri)
        res = rt.run(deadline=dl.sub(fraction=0.45))
        wall = time.perf_counter() - t0
        assert all(res[rid]["status"] == "ok"
                   for rid, _, _ in workload), "router workload lost work"
        reqs = [r for rep in reps
                for rid, r in rep.supervisor.results.items()
                if not str(rid).startswith("warm")]
        ttfts = [r.ttft() for r in reqs if r.ttft() is not None]
        toks = sum(len(r.out) for r in reqs)
        per_replica = []
        for i, rep in enumerate(reps):
            load = rep.load()
            per_replica.append({
                "replica": rep.replica_id,
                "routed": rt.n_routed[i],
                "shed": load["n_shed_interactive"] + load["n_shed_batch"],
                "expired": load["n_expired"],
                "prefix_hit_tokens": load["prefix"]["hit_tokens"],
            })
        return {
            "prefix_cache": prefix_cache,
            "prefix_hit_rate": round(rt.prefix_hit_rate(), 3),
            "ttft_ms_p50": _pct(ttfts, 50), "ttft_ms_p99": _pct(ttfts, 99),
            "tokens_per_sec": round(toks / wall, 1),
            "wall_s": round(wall, 2),
            "per_replica": per_replica,
        }

    off = run_mode(False)
    on = run_mode(True)
    _emit({
        "metric": "cluster_router_prefix_hit_rate",
        "value": on["prefix_hit_rate"],
        "unit": "cached/prompt tokens over 2 replicas",
        "extra": {
            "cache_on": on, "cache_off": off,
            "ttft_p50_speedup": round(
                off["ttft_ms_p50"] / on["ttft_ms_p50"], 2)
            if on["ttft_ms_p50"] else None,
            "ttft_p50_improved":
                (on["ttft_ms_p50"] or 0) < (off["ttft_ms_p50"] or 0),
            "requests": n_req, "replicas": 2,
            "prefix_len": plen_prefix, "families": n_families,
            "prefill_chunk": CHUNK, "gen_per_req": GEN,
            "budget_s": budget_s,
            "device": getattr(dev, "device_kind", str(dev)),
        },
    })


def disagg(model, config, on_tpu, dev):
    """Part 5 (``--disagg``, ISSUE 8): decode p99 ITL under concurrent
    4096-token prefills — disaggregated prefill/decode (one prefill +
    one decode worker PROCESS over a TCPKVStore, KV-block handoff) vs
    the unified chunked-prefill engine. The ROADMAP item-3 claim:
    disaggregation makes decode inter-token latency independent of
    concurrent long prefills, because the prefill pool runs them in a
    different process/chip entirely. Ends with a measured graceful-
    degradation phase: the prefill worker is KILLED and new prompts
    must complete via the decode worker's colocated fallback (no shed
    storm).

    CPU only. One process for each chip: this parent has built the
    model through JAX and so holds the chip; the two worker processes
    it spawns would each need that same chip and fail or hang. On a
    chip the scenario is refused rather than run."""
    import subprocess
    import sys

    if on_tpu:
        raise SystemExit(
            "serving_throughput --disagg: refused on a chip — one "
            "process for each chip: this parent holds it, and the "
            "prefill/decode worker processes it spawns would each need "
            "it. Run with JAX_PLATFORMS=cpu for the handoff mechanics.")

    from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer
    from paddle_tpu.inference.cluster import ProcessReplica
    from paddle_tpu.inference.disagg import DisaggRouter

    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.85)  # reserve tail for the JSON emit
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    B, MAX_LEN, CHUNK, LONG, SHORT = 2, 4160, 256, 4096, 128
    N_SHORT, N_LONG, GEN_S, GEN_L = 4, 2, 24, 8
    BS = 8  # _disagg_worker.py's engine block size
    blocks = B * (-(-MAX_LEN // BS)) + 8

    rng = np.random.RandomState(4)
    shorts = [(f"s{i}", rng.randint(0, config.vocab_size, (SHORT,)))
              for i in range(N_SHORT)]
    longs = [(f"l{i}", rng.randint(0, config.vocab_size, (LONG,)))
             for i in range(N_LONG)]

    def itls_of(times_by_rid):
        return [b - a for ts in times_by_rid for a, b in zip(ts, ts[1:])]

    # -- unified chunked baseline (one engine time-slices both) --------
    eng = ContinuousBatchingEngine(
        model, max_batch=B, max_len=MAX_LEN, block_size=BS,
        num_blocks=blocks, prefill_chunk=CHUNK)
    eng.add_request("warm", np.ones(1, np.int32), max_new_tokens=2)
    eng.run()
    for rid, p in shorts:
        eng.add_request(rid, p, max_new_tokens=GEN_S)
    for rid, p in longs:
        eng.add_request(rid, p, max_new_tokens=GEN_L)
    t0 = time.perf_counter()
    done = eng.run()
    uni_wall = time.perf_counter() - t0
    assert all(done[rid].status == "ok" for rid, _ in shorts + longs)
    uni_itls = itls_of([done[rid].times for rid, _ in shorts])
    unified = {
        "mode": "unified_chunked",
        "decode_itl_ms_p50": _pct(uni_itls, 50),
        "decode_itl_ms_p99": _pct(uni_itls, 99),
        "wall_s": round(uni_wall, 2),
    }

    # -- disaggregated: 1 prefill + 1 decode worker process ------------
    server = TCPStoreServer("127.0.0.1", 0)
    procs = []
    try:
        reps = []
        for rid, role in (("pf0", "prefill"), ("dx0", "decode")):
            jdir = os.path.join(
                "/tmp", f"disagg_bench_{os.getpid()}", rid)
            env = dict(os.environ)
            env.pop("PADDLE_CHAOS", None)
            env.pop("XLA_FLAGS", None)
            env.update({
                "DISAGG_ROLE": role,
                "DISAGG_STORE_PORT": str(server.port),
                "DISAGG_WORKER_ID": rid,
                "DISAGG_JOURNAL_DIR": jdir,
                "DISAGG_DECODE_IDS": "dx0",
                "DISAGG_BUDGET": str(max(dl.remaining() - 5, 30)),
                "DISAGG_CHUNK": str(CHUNK),
                "DISAGG_MAX_LEN": str(MAX_LEN),
                "DISAGG_BLOCKS": str(blocks),
                "DISAGG_BATCH": str(B),
                "DISAGG_STEPS_PER_PUMP": "8",
                # the workers must run the SAME model/platform as the
                # unified baseline or the comparison is meaningless
                "DISAGG_MODEL_JSON": json.dumps(dataclasses.asdict(config)),
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": repo + os.pathsep
                + os.environ.get("PYTHONPATH", ""),
            })
            p = subprocess.Popen(
                [sys.executable,
                 os.path.join(repo, "tests", "_disagg_worker.py")],
                env=env, cwd=repo)
            procs.append(p)
            store = TCPKVStore("127.0.0.1", server.port)
            # journal_dir: a mid-run death recovers via journal-replay
            # ∪ routing table, not the routing table alone
            reps.append(ProcessReplica(store, rid, journal_dir=jdir,
                                       proc=p))
        router = DisaggRouter([reps[0]], [reps[1]])
        store = TCPKVStore("127.0.0.1", server.port)
        while not dl.expired():
            if all(store.get(f"cluster/{r}/hb")
                   for r in ("pf0", "dx0")):
                break
            time.sleep(0.25)
        # warm both workers' compiled phases outside the timed window
        router.submit("warm", np.ones(1, np.int32), max_new_tokens=2)
        router.run(deadline=dl.sub(fraction=0.3))

        for rid, p in shorts:
            router.submit(rid, p, max_new_tokens=GEN_S)
        for rid, p in longs:
            router.submit(rid, p, max_new_tokens=GEN_L)
        t0 = time.perf_counter()
        res = router.run(deadline=dl.sub(fraction=0.8))
        dis_wall = time.perf_counter() - t0
        assert all(res[rid]["status"] == "ok"
                   for rid, _ in shorts + longs), "disagg lost work"
        dis_itls = itls_of([res[rid].get("times", [])
                            for rid, _ in shorts])
        disagg_row = {
            "mode": "disagg_1pf_1dx",
            "decode_itl_ms_p50": _pct(dis_itls, 50),
            "decode_itl_ms_p99": _pct(dis_itls, 99),
            "wall_s": round(dis_wall, 2),
            "fallback": router.n_fallback,
            "handoff_failed": router.n_handoff_failed,
        }

        # -- graceful degradation: kill the prefill pool, keep serving
        procs[0].kill()
        fb_ids = []
        for i in range(3):
            rid = f"fb{i}"
            fb_ids.append(rid)
            router.submit(
                rid, rng.randint(0, config.vocab_size, (SHORT,)),
                max_new_tokens=8)
        fb_res = router.run(deadline=dl.sub(fraction=0.9))
        fb_ok = sum(fb_res.get(r, {}).get("status") == "ok"
                    for r in fb_ids)
        dx_load = reps[1].load() or {}
        degradation = {
            "prefill_killed": True,
            "fallback_submitted": len(fb_ids),
            "fallback_ok": fb_ok,
            "shed": (dx_load.get("n_shed_interactive", 0)
                     + dx_load.get("n_shed_batch", 0)),
            "router_fallback_total": router.n_fallback,
        }
        router.stop(deadline=10.0)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()

    _emit({
        "metric": "serving_disagg_decode_itl_p99",
        "value": disagg_row["decode_itl_ms_p99"],
        "unit": "ms (decode p99 ITL under concurrent 4096-tok prefills)",
        "extra": {
            "disagg": disagg_row, "unified": unified,
            "itl_p99_speedup": round(
                unified["decode_itl_ms_p99"]
                / disagg_row["decode_itl_ms_p99"], 2)
            if disagg_row["decode_itl_ms_p99"] else None,
            "degradation": degradation,
            "short_requests": N_SHORT, "long_requests": N_LONG,
            "short_len": SHORT, "long_len": LONG,
            "gen_short": GEN_S, "gen_long": GEN_L,
            "prefill_chunk": CHUNK, "max_batch": B,
            "budget_s": budget_s,
            "device": getattr(dev, "device_kind", str(dev)),
        },
    })


def overlap_ab(model, config, on_tpu, dev):
    """Part 6 (``--overlap``, ISSUE 10): sync vs async-pipelined engine
    over one decode-heavy workload — host-blocked fraction, H2D bytes
    per decode token, tok/s, and the bitwise stream-equality gate."""
    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.85)  # reserve tail for the JSON emit
    if on_tpu:
        B, MAX_LEN, BS, CHUNK, GEN = 16, 1024, 64, 256, 128
        n_req, plens = 48, (128, 256)
    else:
        B, MAX_LEN, BS, CHUNK, GEN = 4, 128, 8, 16, 24
        n_req, plens = 12, (5, 9, 14)

    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, config.vocab_size,
                           (int(plens[i % len(plens)]),))
               for i in range(n_req)]

    def run_mode(overlap):
        eng = ContinuousBatchingEngine(
            model, max_batch=B, max_len=MAX_LEN, block_size=BS,
            num_blocks=B * (-(-MAX_LEN // BS)) + 4, prefill_chunk=CHUNK,
            overlap=overlap)
        # warm every compiled phase (prefill, decode, update_slot)
        # outside the measured window, then DELTA the transfer/blocked
        # counters so the row is steady-state, not warmup
        eng.add_request("warm", np.ones(1, np.int32), max_new_tokens=4)
        eng.run()
        base = eng.overlap_stats()
        dec0, steps0 = eng.decode_tokens, eng.steps
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            eng.add_request(i, p, max_new_tokens=GEN)
        done = eng.run()
        wall = time.perf_counter() - t0
        st = eng.overlap_stats()
        streams = {i: list(done[i].out) for i in range(n_req) if i in done}
        assert all(done[i].status == "ok" for i in range(n_req))
        dec = eng.decode_tokens - dec0
        busy = st["busy_s"] - base["busy_s"]
        blocked = st["host_blocked_s"] - base["host_blocked_s"]
        row = {
            "mode": "overlap" if overlap else "sync",
            "decode_tokens_per_sec": round(dec / wall, 1),
            "host_blocked_frac": round(blocked / busy, 4) if busy else None,
            "host_blocked_s": round(blocked, 4),
            "h2d_decode_bytes_per_token": round(
                (st["h2d_decode_bytes"] - base["h2d_decode_bytes"])
                / max(dec, 1), 1),
            "dispatches": st["dispatches"] - base["dispatches"],
            "tokens_per_dispatch": round(
                dec / max(st["dispatches"] - base["dispatches"], 1), 2),
            "wall_s": round(wall, 2), "steps": eng.steps - steps0,
        }
        return streams, row

    sync_streams, sync_row = run_mode(False)
    # honor the budget between modes: a blown-out sync half (slow
    # compile) still emits its JSON row inside the
    # window instead of dying mid-A/B with no output at all
    ovl_streams, ovl_row = (None, None)
    if not dl.expired():
        ovl_streams, ovl_row = run_mode(True)
    identical = ovl_streams is not None and sync_streams == ovl_streams
    _emit({
        "metric": "serving_overlap_host_blocked_frac",
        "value": ovl_row["host_blocked_frac"] if ovl_row else None,
        "unit": "blocked/busy (overlap mode; sync row beside)",
        "extra": {
            "overlap": ovl_row, "sync": sync_row,
            "identical_streams": identical,
            "stopped_early": ovl_row is None,
            "blocked_frac_drop_x": round(
                sync_row["host_blocked_frac"]
                / ovl_row["host_blocked_frac"], 2)
            if ovl_row and ovl_row["host_blocked_frac"] else None,
            "h2d_bytes_drop_x": round(
                sync_row["h2d_decode_bytes_per_token"]
                / ovl_row["h2d_decode_bytes_per_token"], 2)
            if ovl_row and ovl_row["h2d_decode_bytes_per_token"]
            else None,
            "requests": n_req, "gen_per_req": GEN, "max_batch": B,
            "prefill_chunk": CHUNK, "budget_s": budget_s,
            "device": getattr(dev, "device_kind", str(dev)),
        },
    })
    assert ovl_row is None or identical, \
        "overlap output streams diverged from sync"


def obs_ab(model, config, on_tpu, dev):
    """Trace-recording overhead A/B: ONE sustained decode workload with
    recording toggled every step, comparing median steady-state decode
    step times. Whole-run A/B pairs are useless here: run-to-run noise
    on a shared box is ±5-8% while the effect under test is <2%, but
    adjacent steps of the same run sample identical conditions, so
    per-step alternation pairs the modes tightly. The CPU row uses a
    mid-size model on purpose: the recording cost is a fixed ~10-20us
    per step, so the ratio is only meaningful against a serving-
    representative (millisecond-plus) step, not a toy-model one."""
    from paddle_tpu import obs

    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.85)
    if on_tpu:
        B, MAX_LEN, BS, PAD = 16, 1024, 64, 256
        N_REQ, GEN = 64, 64
        prompt_lens = (128, 192, 256)
    else:
        B, MAX_LEN, BS, PAD = 4, 64, 8, 16
        N_REQ, GEN = 64, 40
        prompt_lens = (5, 9, 14)
        config = LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=688,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, max_position_embeddings=256)
        paddle.seed(0)
        model = LlamaForCausalLM(config)
    rng = np.random.RandomState(3)

    eng = ContinuousBatchingEngine(
        model, max_batch=B, max_len=MAX_LEN, block_size=BS,
        num_blocks=B * (-(-MAX_LEN // BS)) + 2, prompt_pad=PAD,
        # the sustained row's decode_chunk: spans are per DISPATCH, so
        # the A/B must amortize them over a dispatch's worth of tokens
        # exactly like the serving configuration does
        decode_chunk=16 if on_tpu else 4)
    # compile both phases outside the timed loop
    eng.add_request("warm", np.ones(5, np.int32), max_new_tokens=2)
    eng.run()
    for i in range(N_REQ):
        plen = int(prompt_lens[i % len(prompt_lens)])
        eng.add_request(i, rng.randint(0, config.vocab_size, (plen,)),
                        max_new_tokens=GEN)

    # paired estimator: adjacent steps alternate modes and sample the
    # same machine conditions, so the per-pair (on - off) difference
    # cancels drift/noise that swamps unpaired medians at this scale
    diffs, offs = [], []
    last = None  # (step index, mode, seconds) of the last steady step
    prev, i = obs.enabled(), 0
    try:
        while (eng._queue or eng.num_active) and not dl.expired():
            on = i % 2 == 0
            obs.set_enabled(on)
            # pair only pure steady-state decode steps: full batch,
            # nothing mid-prefill, no admission possible, and a full
            # decode_chunk emitted per row — a homogeneous population
            # (prefill/admission steps land in both modes anyway)
            steady = (eng.num_active == B
                      and eng.num_prefilling == 0)
            d0 = eng.decode_tokens
            t0 = time.perf_counter()
            eng.step()
            dt = time.perf_counter() - t0
            if steady and eng.decode_tokens - d0 == B * eng.decode_chunk:
                if last is not None and last[0] == i - 1:
                    li, lon, ldt = last
                    diffs.append(dt - ldt if on else ldt - dt)
                    offs.append(ldt if on else dt)
                last = (i, on, dt)
            i += 1
    finally:
        obs.set_enabled(prev)
    assert not eng._queue and not eng.num_active, "budget too small"
    assert len(diffs) >= 40, len(diffs)

    def _trimmed(xs, frac=0.2):  # robust + lower-variance than median
        xs = np.sort(np.asarray(xs))
        k = int(len(xs) * frac)
        return float(np.mean(xs[k:len(xs) - k]))

    off_med = _trimmed(offs)
    on_med = off_med + _trimmed(diffs)
    overhead = _trimmed(diffs) / off_med
    _emit({
        "metric": "serving_obs_overhead_pct",
        "value": round(100 * overhead, 2),
        "unit": "% steady-state decode step time added by recording",
        "extra": {
            "tokens_per_sec_obs_off": round(
                B * eng.decode_chunk / off_med, 1),
            "tokens_per_sec_obs_on": round(
                B * eng.decode_chunk / on_med, 1),
            "decode_chunk": eng.decode_chunk,
            "step_ms_obs_off": round(off_med * 1000, 3),
            "step_ms_obs_on": round(on_med * 1000, 3),
            "paired_steps": len(diffs),
            "requests": N_REQ, "gen_per_req": GEN, "max_batch": B,
            "ring_len": len(obs.ring()),
            "device": getattr(dev, "device_kind", str(dev)),
        },
    })
    assert overhead < 0.02, \
        f"obs-on overhead {100 * overhead:.2f}% exceeds the 2% budget"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sustained-only", action="store_true")
    ap.add_argument("--mixed-only", action="store_true")
    ap.add_argument("--overload", action="store_true",
                    help="run only the 2x-offered-load admission-control "
                         "scenario (under BENCH_TOTAL_BUDGET)")
    ap.add_argument("--router", action="store_true",
                    help="run only the 2-replica cluster-router shared-"
                         "prefix scenario, prefix cache on vs off "
                         "(under BENCH_TOTAL_BUDGET)")
    ap.add_argument("--disagg", action="store_true",
                    help="run only the disaggregated prefill/decode "
                         "scenario: decode p99 ITL under concurrent "
                         "4096-token prefills, 2-process KV handoff vs "
                         "unified chunked, plus the kill-the-prefill-"
                         "pool fallback phase (under BENCH_TOTAL_BUDGET)")
    ap.add_argument("--overlap", action="store_true",
                    help="run only the async host/device pipelining "
                         "A/B: sync vs overlap=True engine over the "
                         "same decode-heavy workload — host-blocked "
                         "fraction, H2D bytes/token, tok/s, bitwise "
                         "stream equality (under BENCH_TOTAL_BUDGET)")
    ap.add_argument("--obs", action="store_true",
                    help="run only the observability-overhead A/B: one "
                         "sustained decode run with trace recording "
                         "toggled per step, paired adjacent-step "
                         "diffs; asserts obs-on costs < 2%% per step")
    args = ap.parse_args()

    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=4608)
    else:
        config = LlamaConfig.tiny(max_position_embeddings=4608)

    paddle.seed(0)
    model = LlamaForCausalLM(config)
    if on_tpu:
        model.bfloat16()

    if args.overload:
        overload(model, config, on_tpu, dev)
        return
    if args.router:
        router(model, config, on_tpu, dev)
        return
    if args.disagg:
        disagg(model, config, on_tpu, dev)
        return
    if args.overlap:
        overlap_ab(model, config, on_tpu, dev)
        return
    if args.obs:
        obs_ab(model, config, on_tpu, dev)
        return
    if not args.mixed_only:
        sustained(model, config, on_tpu, dev)
    if not args.sustained_only:
        mixed(model, config, on_tpu, dev)


if __name__ == "__main__":
    main()

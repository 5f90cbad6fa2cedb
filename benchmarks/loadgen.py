"""Open-loop trace-driven load harness (ISSUE 14).

Closed-loop drivers (submit, wait, submit) let a slow server throttle
its own workload — the measured "latency" is then a function of the
harness, not the scheduler (the coordinated-omission trap the serving
papers this stack follows call out; Sarathi-Serve, DistServe). This
harness is OPEN-LOOP: a seeded schedule fixes every arrival instant
up front, and the driver submits at those instants regardless of what
has completed. Queues build when the server falls behind — that
build-up IS the signal the SLO report grades.

The schedule generator composes four effects, all from one
``random.Random(seed)`` stream (pure python — byte-reproducible
across platforms, unlike numpy's generators across versions):

- **Poisson arrivals** via exponential gaps at the envelope's peak
  rate, thinned against the instantaneous rate (Lewis-Shedler): a
  candidate at ``t`` survives with probability ``rate(t)/rate_max``.
- **Burst episodes** — seeded windows covering ``burst_frac`` of the
  horizon multiply the rate by ``burst_factor`` (the flash-crowd
  shape single-rate Poisson can't produce).
- **Diurnal ramp** — one sinusoid period compressed into the horizon
  (amplitude ``diurnal_amp``), so a short run still sweeps through
  trough and peak load.
- **Heavy-tailed lengths** — lognormal prompt/output token counts
  (clamped), the observed production shape: most requests short, a
  fat tail of long ones.
- **Zipf tenant mix** — tenant ``k`` drawn with weight
  ``1/(k+1)^zipf_s``: one dominant tenant, a long tail of small ones,
  the shape per-tenant attainment accounting exists for.

``generate_schedule`` is pure and deterministic: same spec -> the
same ``schedule_json`` bytes (the acceptance gate). The driver layer
(:class:`EngineFront` / :class:`RouterFront`) adapts any front door —
``ContinuousBatchingEngine``, ``ClusterRouter``, ``DisaggRouter`` —
behind submit/pump/harvest, and the report is
``paddle_tpu.obs.slo.attainment_report`` over the harvested
per-token timestamps, plus a stitched Chrome trace of the run.

    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/loadgen.py --smoke

``--smoke`` runs the CPU mechanics check: a seeded schedule over a
2-replica in-process ClusterRouter (tiny Llama, 3 zipf tenants) under
``BENCH_TOTAL_BUDGET``, bench.py's preflight device probe included,
and emits one JSON metric line with the per-tenant attainment table.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import random
import sys
import time
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # direct `python benchmarks/loadgen.py` runs
    sys.path.insert(0, _REPO)


# ---------------------------------------------------------------------------
# schedule generation (pure, deterministic — no framework imports)
# ---------------------------------------------------------------------------

@dataclass
class TraceSpec:
    """The seeded workload shape. ``n_requests`` arrivals over roughly
    ``duration_s`` schedule-seconds (the thinned process runs past the
    horizon if the tail needs it; the driver can compress real time
    with ``time_scale``)."""

    seed: int = 0
    n_requests: int = 48
    duration_s: float = 8.0
    burst_factor: float = 3.0     # rate multiplier inside burst windows
    burst_frac: float = 0.15      # fraction of horizon under bursts
    diurnal_amp: float = 0.5      # sinusoid amplitude, 0 <= amp < 1
    tenants: int = 3
    zipf_s: float = 1.2           # tenant-mix skew
    batch_frac: float = 0.25      # P(priority == "batch")
    prompt_len_median: float = 10.0
    prompt_len_sigma: float = 0.5
    prompt_len_max: int = 24
    output_len_median: float = 6.0
    output_len_sigma: float = 0.5
    output_len_max: int = 12

    def to_dict(self) -> dict:
        return asdict(self)


def _zipf_cdf(n: int, s: float) -> List[float]:
    w = [1.0 / (k + 1) ** s for k in range(n)]
    tot = sum(w)
    acc, out = 0.0, []
    for x in w:
        acc += x / tot
        out.append(acc)
    return out


def _burst_windows(rng: random.Random,
                   spec: TraceSpec) -> List[Tuple[float, float]]:
    """Seeded burst episodes covering ~burst_frac of the horizon."""
    windows: List[Tuple[float, float]] = []
    covered, target = 0.0, spec.burst_frac * spec.duration_s
    while covered < target:
        width = rng.uniform(0.03, 0.10) * spec.duration_s
        start = rng.uniform(0.0, spec.duration_s - width)
        windows.append((start, start + width))
        covered += width
    return windows


def generate_schedule(spec: TraceSpec) -> List[dict]:
    """The open-loop arrival trace: ``n_requests`` entries sorted by
    arrival time ``t`` (seconds from run start), each with tenant,
    priority, lengths, and a per-request prompt seed. Deterministic in
    ``spec`` alone."""
    if not 0.0 <= spec.diurnal_amp < 1.0:
        raise ValueError("diurnal_amp must be in [0, 1)")
    rng = random.Random(spec.seed)
    bursts = _burst_windows(rng, spec)
    cdf = _zipf_cdf(spec.tenants, spec.zipf_s)
    base_rate = spec.n_requests / spec.duration_s
    rate_max = base_rate * (1.0 + spec.diurnal_amp) * spec.burst_factor

    def rate(t: float) -> float:
        r = base_rate * (1.0 + spec.diurnal_amp
                         * math.sin(2.0 * math.pi * t / spec.duration_s))
        if any(a <= (t % spec.duration_s) < b for a, b in bursts):
            r *= spec.burst_factor
        return r

    def _length(median: float, sigma: float, cap: int) -> int:
        v = rng.lognormvariate(math.log(median), sigma)
        return max(1, min(int(cap), int(round(v))))

    out: List[dict] = []
    t = 0.0
    while len(out) < spec.n_requests:
        # Lewis-Shedler thinning: candidates at the envelope's peak
        # rate, kept with probability rate(t)/rate_max
        t += rng.expovariate(rate_max)
        if rng.random() * rate_max > rate(t):
            continue
        u = rng.random()
        tenant = next(k for k, c in enumerate(cdf) if u <= c)
        out.append({
            "i": len(out),
            "req_id": f"lg-{spec.seed}-{len(out):04d}",
            "t": round(t, 6),
            "tenant": f"tenant{tenant}",
            "priority": ("batch" if rng.random() < spec.batch_frac
                         else "interactive"),
            "prompt_len": _length(spec.prompt_len_median,
                                  spec.prompt_len_sigma,
                                  spec.prompt_len_max),
            "max_new_tokens": _length(spec.output_len_median,
                                      spec.output_len_sigma,
                                      spec.output_len_max),
            "prompt_seed": rng.getrandbits(32),
        })
    return out


def schedule_json(spec: TraceSpec, schedule: List[dict]) -> str:
    """Canonical bytes for the schedule — the reproducibility gate:
    equal specs must serialize byte-identically."""
    return json.dumps({"schema": "paddle_tpu.loadgen/1",
                       "spec": spec.to_dict(), "schedule": schedule},
                      sort_keys=True, indent=2)


def feedforward_from_spec(spec: TraceSpec):
    """The trace's rate envelope as an autoscaler feed-forward hint:
    ``f(t_schedule_seconds) -> expected-rate-multiple`` (1.0 = the base
    rate). Re-derives the seeded burst windows exactly as
    :func:`generate_schedule` does (they are the FIRST draw from
    ``random.Random(seed)``), so the hint and the trace agree on when
    the flash crowds land — the feed-forward raises the replica floor
    BEFORE a predictable peak instead of paying one SLO breach per
    ramp. Pure: no clocks; the caller maps wall time onto schedule
    time (``(now - t0) / time_scale``)."""
    rng = random.Random(spec.seed)
    bursts = _burst_windows(rng, spec)

    def multiple(t: float) -> float:
        m = 1.0 + spec.diurnal_amp * math.sin(
            2.0 * math.pi * t / spec.duration_s)
        if any(a <= (t % spec.duration_s) < b for a, b in bursts):
            m *= spec.burst_factor
        return m

    return multiple


# ---------------------------------------------------------------------------
# front-door adapters
# ---------------------------------------------------------------------------

class EngineFront:
    """Drive a bare ``ContinuousBatchingEngine``."""

    def __init__(self, engine):
        self.engine = engine

    def submit(self, item: dict, prompt) -> None:
        self.engine.add_request(
            item["req_id"], prompt, item["max_new_tokens"],
            priority=item["priority"], tenant=item["tenant"])

    def pump(self) -> None:
        self.engine.step()

    def unfinished(self, ids) -> int:
        return sum(1 for r in ids if r not in self.engine._completed)

    def harvest(self, ids) -> List[object]:
        return [self.engine._completed.get(r) for r in ids]


class RouterFront:
    """Drive a ``ClusterRouter`` or ``DisaggRouter`` (both expose
    ``submit(req_id, prompt, n, *, priority, tenant)`` and
    ``step() -> [result dicts]``). Per-token timestamps are harvested
    from the worker supervisors' GenRequests; a request only the
    router-level result dict knows about (e.g. finished on a replica
    that later died) degrades to status-only accounting."""

    def __init__(self, router):
        self.router = router
        self.results: Dict[object, dict] = {}

    def submit(self, item: dict, prompt) -> None:
        self.router.submit(
            item["req_id"], prompt, item["max_new_tokens"],
            priority=item["priority"], tenant=item["tenant"])

    def pump(self) -> None:
        for d in self.router.step():
            self.results[d["req_id"]] = d

    def unfinished(self, ids) -> int:
        return sum(1 for r in ids if r not in self.results)

    def _workers(self):
        for attr in ("replicas", "prefill", "decode"):
            for w in getattr(self.router, attr, ()):
                yield w

    def harvest(self, ids) -> List[object]:
        by_id: Dict[object, object] = {}
        for w in self._workers():
            sup = getattr(w, "supervisor", None)
            if sup is not None:
                by_id.update(sup.results)
        out: List[object] = []
        for rid in ids:
            if rid in by_id:
                out.append(by_id[rid])
            elif rid in self.results:
                d = dict(self.results[rid])
                d.setdefault("times", [])
                out.append(d)
            else:
                out.append(None)
        return out


# ---------------------------------------------------------------------------
# the open-loop driver
# ---------------------------------------------------------------------------

def run_schedule(front, schedule: List[dict], *, vocab_size: int,
                 time_scale: float = 1.0, deadline=None,
                 drain_s: float = 60.0,
                 on_tick=None) -> Tuple[List[object], float]:
    """Submit every schedule entry at its arrival instant (scaled by
    ``time_scale``), pumping the front door between arrivals but NEVER
    gating a submission on completions; then drain. ``on_tick`` (a
    zero-arg callable) runs alongside every pump — the seam a control
    loop (the fleet autoscaler) rides to observe and act while the
    open-loop trace plays. Returns ``(per-request records, wall_s)`` —
    records are GenRequest-shaped (or ``None`` for requests the
    deadline abandoned)."""
    import numpy as np

    ids = [item["req_id"] for item in schedule]
    prompts = {
        item["req_id"]: np.random.RandomState(
            item["prompt_seed"] % (2 ** 32)).randint(
                0, vocab_size, (item["prompt_len"],)).astype(np.int32)
        for item in schedule
    }
    t0 = time.perf_counter()
    for item in schedule:
        due = t0 + item["t"] * time_scale
        while time.perf_counter() < due:
            front.pump()
            if on_tick is not None:
                on_tick()
        front.submit(item, prompts[item["req_id"]])
    t_drain = time.perf_counter()
    while front.unfinished(ids):
        if time.perf_counter() - t_drain > drain_s:
            break
        if deadline is not None and deadline.remaining() <= 0:
            break
        front.pump()
        if on_tick is not None:
            on_tick()
    wall = time.perf_counter() - t0
    return front.harvest(ids), wall


def _lost(rid: str, item: dict) -> dict:
    return {"req_id": rid, "tenant": item["tenant"],
            "priority": item["priority"], "status": "lost",
            "t_submit": 0.0, "times": [], "out": []}


def run_report(front, spec: TraceSpec, slo_spec, *, vocab_size: int,
               time_scale: float = 1.0, deadline=None,
               drain_s: float = 60.0, on_tick=None) -> dict:
    """generate + drive + grade: the one-call harness."""
    from paddle_tpu.obs import slo as _slo

    schedule = generate_schedule(spec)
    recs, wall = run_schedule(front, schedule, vocab_size=vocab_size,
                              time_scale=time_scale, deadline=deadline,
                              drain_s=drain_s, on_tick=on_tick)
    recs = [r if r is not None else _lost(item["req_id"], item)
            for r, item in zip(recs, schedule)]
    return _slo.attainment_report(
        recs, slo_spec, wall,
        extra={"trace_spec": spec.to_dict(), "time_scale": time_scale})


# ---------------------------------------------------------------------------
# the --smoke scenario (CPU mechanics check; the TPU row reuses it)
# ---------------------------------------------------------------------------

def burn_columns(table: dict, objective: float = 0.99) -> dict:
    """Burn-rate / remaining-error-budget columns for one attainment
    table row (overall or per-tenant) — computed by the ALERT ENGINE's
    own arithmetic (:func:`paddle_tpu.obs.alerts.burn_rate` /
    :func:`~paddle_tpu.obs.alerts.budget_remaining_frac`), so the
    open-loop harness and the alert rules grade from the same math; a
    parity test pins the two surfaces against each other."""
    from paddle_tpu.obs import alerts as _alerts

    n = int(table["requests"])
    att = table["attainment"]["all"]
    # the table stores met/n rounded to 6 digits; the round-trip back
    # to the integer met count is exact for any realistic n
    bad = 0 if att is None else n - int(round(att * n))
    return {
        "slo_objective": objective,
        "burn_rate": round(_alerts.burn_rate(bad, n, objective), 6),
        "budget_remaining_frac": round(
            _alerts.budget_remaining_frac(bad, n, objective), 6),
    }


def smoke(args) -> dict:
    from paddle_tpu.utils.retries import Deadline

    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.85)  # reserve tail for the JSON emit

    import paddle_tpu as paddle
    from paddle_tpu import obs as _obs
    from paddle_tpu.inference.cluster import ClusterRouter, InProcessReplica
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.obs.slo import SLOClass, SLOSpec

    paddle.seed(0)
    config = LlamaConfig.tiny()
    model = LlamaForCausalLM(config)

    def factory():
        return ContinuousBatchingEngine(
            model, max_batch=4, max_len=48, block_size=8, num_blocks=28,
            prompt_pad=24)

    replicas = [InProcessReplica(f"rep{i}", factory) for i in range(2)]
    router = ClusterRouter(replicas, block_size=8)
    front = RouterFront(router)

    spec = TraceSpec(seed=args.seed, n_requests=args.requests,
                     duration_s=args.duration, tenants=args.tenants)
    # CPU targets: generous enough that a healthy tiny-model run meets
    # most of them, tight enough that the attainment fractions are not
    # trivially 1.0 for the dominant tenant under its own bursts
    slo_spec = SLOSpec(
        default=SLOClass(ttft_s=8.0, itl_p95_s=2.0, e2e_s=20.0),
        per_priority={"batch": SLOClass(ttft_s=15.0, e2e_s=30.0)},
        per_tenant={"tenant0": SLOClass(ttft_s=6.0)})

    report = run_report(front, spec, slo_spec,
                        vocab_size=config.vocab_size,
                        time_scale=args.time_scale, deadline=dl,
                        drain_s=min(60.0, max(5.0, dl.remaining())))
    if args.report_out:
        from paddle_tpu.obs.slo import report_json
        with open(args.report_out, "w", encoding="utf-8") as fh:
            fh.write(report_json(report))
    if args.trace_out:
        from paddle_tpu.obs.trace import export_chrome_trace, ring, \
            stitch_traces
        export_chrome_trace(stitch_traces([ring().dump()]),
                            path=args.trace_out)
    ov = report["overall"]
    return {
        "metric": "loadgen_goodput_under_slo",
        "value": ov["goodput_tokens_per_s"],
        "unit": "tok/s",
        "extra": {
            "requests": ov["requests"],
            "attainment_all": ov["attainment"]["all"],
            "ttft_p99_s": ov["ttft"]["p99"],
            "itl_p95_p99_s": ov["itl_p95"]["p99"],
            # burn-rate / error-budget columns (ISSUE 15): same
            # arithmetic as the alert engine's burn-rate rules
            **burn_columns(ov),
            "tenants": {
                t: {"requests": row["requests"],
                    "attainment_all": row["attainment"]["all"],
                    "ttft_p50_s": row["ttft"]["p50"],
                    "ttft_p99_s": row["ttft"]["p99"],
                    "goodput_tokens_per_s": row["goodput_tokens_per_s"],
                    **burn_columns(row)}
                for t, row in report["tenants"].items()},
            "fleet_snapshot_series": len(
                _obs.registry().snapshot().get("metrics", {})),
        },
    }


# ---------------------------------------------------------------------------
# the --autoscale scenario (ISSUE 19: closed-loop fleet control)
# ---------------------------------------------------------------------------

def _rec_status(rec) -> str:
    if rec is None:
        return "lost"
    if isinstance(rec, dict):
        return str(rec.get("status", "lost"))
    return str(getattr(rec, "status", "lost"))


def autoscale_smoke(args) -> dict:
    """Closed-loop fleet control under the bursty trace (CPU):

    a 1-replica ClusterRouter grows/shrinks under a FleetAutoscaler
    driven by a short-window TTFT burn-rate rule (internal target
    DELIBERATELY tighter than the graded SLO — the SRE-workbook move:
    page before the user-facing objective is gone) plus the trace's own
    diurnal/burst envelope as feed-forward. Chaos SIGKILLs the first
    drain victim MID-DRAIN; journal-∪-table recovery must lose zero
    accepted requests. Side runs grade WFQ fairness under a hot-tenant
    flood and the host-RAM cache tier with a working set bigger than
    HBM. Emits one bench row per claim, each with explicit polarity."""
    from paddle_tpu.utils.retries import Deadline

    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET", "600"))
    dl = Deadline(budget_s * 0.85)

    import shutil
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.admission import AdmissionConfig, TenantPolicy
    from paddle_tpu.inference.autoscale import (AutoscalerConfig,
                                                FleetAutoscaler)
    from paddle_tpu.inference.cache_tier import HostTier
    from paddle_tpu.inference.cluster import ClusterRouter, InProcessReplica
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.obs import slo as _slo
    from paddle_tpu.obs.alerts import AlertManager, BurnRateRule
    from paddle_tpu.obs.slo import SLOClass, SLOSpec
    from paddle_tpu.testing import chaos

    ts = max(float(args.time_scale), 1e-9)
    paddle.seed(0)
    config = LlamaConfig.tiny()
    model = LlamaForCausalLM(config)

    def make_engine(**over):
        kw = dict(max_batch=4, max_len=48, block_size=8, num_blocks=28,
                  prompt_pad=24)
        kw.update(over)
        return ContinuousBatchingEngine(model, **kw)

    # Every engine jits its own phase closures, so a replica spawned
    # mid-burst would pay a cold XLA compile on its first prefill.
    # Warm the persistent compilation cache once (every entry kept,
    # however quick its compile): spawned replicas then deserialize
    # instead of compiling.
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    warm = make_engine()
    warm.add_request("warmup", np.arange(9, dtype=np.int32), 2)
    for _ in range(64):
        warm.step()
        if "warmup" in warm._completed:
            break
    del warm

    # --- the autoscaled fleet -------------------------------------------
    journals = tempfile.mkdtemp(prefix="ascale-journal-")

    def replica_factory(rid):
        return InProcessReplica(
            rid, make_engine,
            journal_dir=os.path.join(journals, str(rid)))

    router = ClusterRouter([replica_factory("seed0")], block_size=8)
    front = RouterFront(router)

    # graded SLO (the user-facing objective) vs the controller's rule:
    # an exact-bucket-bound 2.0 s TTFT target — tighter than the graded
    # 8 s so the controller pages BEFORE users hurt, but above a lone
    # CPU prefill's latency so a healthy fleet can actually recover its
    # budget (the scale-down gate). 50% objective, one short window —
    # fires within ~2 s of a backlog forming.
    slo_spec = SLOSpec(
        default=SLOClass(ttft_s=8.0, itl_p95_s=2.0, e2e_s=20.0),
        per_priority={"batch": SLOClass(ttft_s=15.0, e2e_s=30.0)})
    alerts = AlertManager([BurnRateRule(
        "ttft_burn_fast", "serving_ttft_seconds",
        objective=0.5, threshold_s=2.0,
        windows=((2.0 * ts, 1.0),), resolve_for_s=0.25 * ts)],
        emit_trace=False)

    spec = TraceSpec(seed=args.seed, n_requests=args.requests,
                     duration_s=args.duration, tenants=args.tenants,
                     burst_factor=4.0, burst_frac=0.2)
    envelope = feedforward_from_spec(spec)
    t0_cell: List[Optional[float]] = [None]

    def feedforward(now: float) -> float:
        if t0_cell[0] is None:
            return 1.0
        t = (now - t0_cell[0]) / ts
        if t >= spec.duration_s:  # past the horizon: no forecast — the
            return 1.0            # periodic envelope must not re-fire
        return envelope(t)

    cfg = AutoscalerConfig(
        min_replicas=1, max_replicas=3,
        scale_up_cooldown_s=0.75 * ts, scale_down_cooldown_s=1.0 * ts,
        recover_budget_frac=0.2, recover_hold_s=0.75 * ts,
        spawn_backoff_s=0.25, drain_timeout_s=8.0 * ts,
        # headroom 0.3: the 4x burst envelope pre-warms the floor to 2,
        # leaving the third replica to the burn signal — feed-forward
        # alone must not pin the fleet at peak (that IS static peak)
        feedforward_headroom=0.3, evaluate_interval_s=0.2 * ts)
    scaler = FleetAutoscaler(router, replica_factory, config=cfg,
                             alerts=alerts, feedforward=feedforward,
                             clock=time.perf_counter)

    # chaos: the FIRST drain victim is SIGKILLed mid-drain — the
    # zero-lost acceptance row covers the crash-only recovery path
    chaos.install(chaos.ChaosSchedule(seed=args.seed)
                  .at("scale.drain", 1, "drop"))

    peak = [1]
    last_tick = [0.0]

    def on_tick():
        now = time.perf_counter()
        if now - last_tick[0] < 0.05:
            return
        last_tick[0] = now
        rec = scaler.step(now)
        peak[0] = max(peak[0], int(rec["live"]))

    schedule = generate_schedule(spec)
    try:
        t_start = t0_cell[0] = time.perf_counter()
        recs, wall = run_schedule(
            front, schedule, vocab_size=config.vocab_size,
            time_scale=ts, deadline=dl,
            drain_s=min(60.0, max(5.0, dl.remaining())),
            on_tick=on_tick)
        t0_cell[0] = None  # trace over: feed-forward floor back to min
        # let in-progress drains finish so replica-seconds reflects the
        # controller's real footprint, not a snapshot mid-scale-down
        t_cool = time.perf_counter()
        while time.perf_counter() - t_cool < 6.0 and dl.remaining() > 0:
            router.step()
            rec = scaler.step()
            if not rec["draining"] and rec["live"] <= rec["floor"]:
                break
            time.sleep(0.01)
    finally:
        chaos.uninstall()

    wall_total = time.perf_counter() - t_start
    replica_seconds = scaler.replica_seconds
    static_rs = cfg.max_replicas * wall_total
    saving = 1.0 - replica_seconds / static_rs if static_rs > 0 else 0.0

    statuses: Dict[str, int] = {}
    for r in recs:
        st = _rec_status(r)
        statuses[st] = statuses.get(st, 0) + 1
    lost = sum(n for st, n in statuses.items() if st != "ok")
    actions: Dict[str, int] = {}
    for d in scaler.decisions:
        actions[d["action"]] = actions.get(d["action"], 0) + 1

    graded = [r if r is not None else _lost(item["req_id"], item)
              for r, item in zip(recs, schedule)]
    report = _slo.attainment_report(
        graded, slo_spec, wall,
        extra={"trace_spec": spec.to_dict(), "time_scale": ts})
    ov = report["overall"]

    try:
        router.stop()
    except Exception:  # noqa: BLE001 — teardown must not fail the bench
        pass

    # --- WFQ fairness under a hot-tenant flood --------------------------
    adm = AdmissionConfig(max_queue=512, wfq=True,
                          tenants={"*": TenantPolicy(weight=1.0)})
    feng = make_engine(admission=adm)
    fspec = TraceSpec(seed=args.seed + 1, n_requests=32, duration_s=3.0,
                      tenants=3, zipf_s=3.0, burst_factor=1.0,
                      burst_frac=0.0)
    freport = run_report(
        EngineFront(feng), fspec, slo_spec,
        vocab_size=config.vocab_size, time_scale=ts, deadline=dl,
        drain_s=min(60.0, max(5.0, dl.remaining())))
    fair = {t: row["attainment"]["all"]
            for t, row in freport["tenants"].items()
            if row["attainment"]["all"] is not None}
    fair_min = min(fair.values()) if fair else 0.0
    fair_max = max(fair.values()) if fair else 0.0
    fair_band = (fair_min / fair_max) if fair_max else 0.0
    wfq_snap = feng.admission.snapshot() if feng.admission else {}

    # --- host-RAM cache tier: working set > HBM budget ------------------
    def _cache_pass(eng, prompts, tag):
        for j, p in enumerate(prompts):
            rid = f"{tag}-{j}"
            eng.add_request(rid, p, 4)
            for _ in range(512):  # bounded: a stuck request must not
                if rid in eng._completed:  # burn the whole bench budget
                    break
                eng.step()

    rngp = np.random.RandomState(args.seed + 7)
    # 16 prompts x 2 full blocks = 32 cacheable blocks against a
    # 24-block HBM pool: HBM alone cannot hold the working set
    prompts = [rngp.randint(0, config.vocab_size, (17,)).astype(np.int32)
               for _ in range(16)]

    def _replay_hit_rate(tier):
        eng = make_engine(num_blocks=24, prefix_cache=True,
                          cache_tier=tier)
        _cache_pass(eng, prompts, "warm")
        s0 = eng.prefix_stats()
        _cache_pass(eng, prompts, "replay")
        s1 = eng.prefix_stats()
        hits = s1["hit_tokens"] - s0["hit_tokens"]
        pres = s1["prefill_tokens"] - s0["prefill_tokens"]
        rate = hits / (hits + pres) if hits + pres else 0.0
        return rate, s1

    tier = HostTier()
    tier_rate, tier_stats = _replay_hit_rate(tier)
    hbm_rate, _ = _replay_hit_rate(None)

    shutil.rmtree(journals, ignore_errors=True)

    rows = [
        {"metric": "autoscale_saving_frac_vs_static_peak",
         "value": round(saving, 6), "unit": "frac", "polarity": "up",
         "extra": {"replica_seconds": round(replica_seconds, 3),
                   "static_replica_seconds": round(static_rs, 3),
                   "wall_s": round(wall_total, 3),
                   "max_replicas": cfg.max_replicas,
                   "peak_live": peak[0],
                   "target_min_saving": 0.30}},
        {"metric": "autoscale_replica_seconds",
         "value": round(replica_seconds, 3), "unit": "replica*s",
         "polarity": "down",
         "extra": {"wall_s": round(wall_total, 3)}},
        {"metric": "autoscale_ttft_p99_s",
         "value": ov["ttft"]["p99"], "unit": "s", "polarity": "down",
         "extra": {"slo_ttft_s": 8.0,
                   "attainment_all": ov["attainment"]["all"],
                   "requests": ov["requests"],
                   **burn_columns(ov)}},
        {"metric": "autoscale_lost_requests",
         "value": lost, "unit": "requests", "polarity": "down",
         "extra": {"statuses": statuses,
                   "chaos_drain_kills": actions.get("drain-died", 0),
                   "router_recoveries": router.n_recoveries,
                   "poisoned": len(router.poisoned_ids)}},
        {"metric": "autoscale_decisions",
         "value": sum(actions.values()), "unit": "decisions",
         "polarity": "down",
         "extra": {"actions": actions,
                   "decisions": scaler.decisions[-64:]}},
        {"metric": "autoscale_tenant_attainment_min",
         "value": round(fair_min, 6), "unit": "frac", "polarity": "up",
         "extra": {"tenants": fair,
                   "fairness_band_min_over_max": round(fair_band, 6),
                   "wfq_vtime": wfq_snap.get("vtime"),
                   "quota_shed": wfq_snap.get("n_quota_shed")}},
        {"metric": "autoscale_cache_tier_hit_rate",
         "value": round(tier_rate, 6), "unit": "frac", "polarity": "up",
         "extra": {"hbm_only_hit_rate": round(hbm_rate, 6),
                   "working_set_blocks": 32, "hbm_blocks": 24,
                   "tier": tier_stats.get("tier")}},
    ]
    return {"rows": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop trace-driven load harness")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU mechanics run: 2-replica in-process "
                         "router, 3 zipf tenants, under "
                         "BENCH_TOTAL_BUDGET")
    ap.add_argument("--autoscale", action="store_true",
                    help="closed-loop fleet-control run: burn-rate-"
                         "driven autoscaler over a 1..3-replica "
                         "router, chaos SIGKILL mid-drain, WFQ "
                         "fairness + host-RAM cache-tier side runs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=None,
                    help="arrivals (default 24; 60 with --autoscale)")
    ap.add_argument("--duration", type=float, default=None,
                    help="schedule horizon in seconds (default 4; "
                         "10 with --autoscale)")
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="multiply schedule times (e.g. 0.5 = 2x "
                         "faster offered load)")
    ap.add_argument("--schedule-only", action="store_true",
                    help="print the canonical schedule JSON and exit "
                         "(no model, no framework import)")
    ap.add_argument("--report-out", default=None,
                    help="write the full attainment report JSON here")
    ap.add_argument("--trace-out", default=None,
                    help="write the stitched Chrome trace here")
    args = ap.parse_args(argv)
    if args.requests is None:
        args.requests = 60 if args.autoscale else 24
    if args.duration is None:
        args.duration = 10.0 if args.autoscale else 4.0

    if args.schedule_only:
        spec = TraceSpec(seed=args.seed, n_requests=args.requests,
                         duration_s=args.duration, tenants=args.tenants)
        print(schedule_json(spec, generate_schedule(spec)))
        return 0
    if not (args.smoke or args.autoscale):
        ap.error("pick a scenario: --smoke, --autoscale or "
                 "--schedule-only")
    from paddle_tpu.obs.regress import bench_record

    if args.autoscale:
        doc = autoscale_smoke(args)
        for row in doc.get("rows", ()):
            bench_record("loadgen_autoscale", row["metric"],
                         row["value"], row.get("unit", ""),
                         extra=row.get("extra"),
                         polarity=row.get("polarity"))
        return 0

    doc = smoke(args)
    bench_record(
        "loadgen", doc.get("metric", "loadgen_goodput_under_slo"),
        doc.get("value"), doc.get("unit", ""), extra=doc.get("extra"),
        **{k: v for k, v in doc.items()
           if k not in ("metric", "value", "unit", "extra")})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

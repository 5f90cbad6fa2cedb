"""SLO alerting + perf-regression sentinel (ISSUE 15): burn-rate math
against hand-computed windows, the pending→firing→resolved lifecycle
under seeded flapping, absence detection of a silenced publisher, the
bench-ledger regression verdicts (true regression flagged, noise
quiet), CLI exit codes, and the loadgen-vs-alert-engine parity pin.

The capstone is the e2e proof: chaos-injected SLO violations in a
2-replica in-process fleet drive a burn-rate alert through its full
lifecycle deterministically (explicit evaluation clock), visible in
``health()``, in the merged fleet snapshot, and as ``alert_firing`` /
``alert_resolved`` instants in the exported Chrome trace.

Everything here is quick-lane (``pytest -m alerts``).
"""
import json
import os
import random
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.obs import agg
from paddle_tpu.obs import alerts as al
from paddle_tpu.obs import regress as rg
from paddle_tpu.obs.metrics import Histogram, MetricsRegistry

pytestmark = pytest.mark.alerts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mgr(rules=(), **kw):
    kw.setdefault("emit_trace", False)
    kw.setdefault("emit_metrics", False)
    return al.AlertManager(rules, **kw)


def _cli(args, **kw):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.obs", *args],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=180, **kw)


# ---------------------------------------------------------------------------
# error-budget arithmetic


class TestBudgetMath:
    def test_burn_rate_hand_computed(self):
        # 5% errors against a 99% objective: 5x the budget
        assert al.burn_rate(5, 100, 0.99) == pytest.approx(5.0)
        assert al.burn_rate(0, 100, 0.99) == 0.0
        assert al.burn_rate(3, 0, 0.99) == 0.0  # no traffic, no burn
        # a 100% objective has zero budget: any error is infinite burn
        assert al.burn_rate(1, 10, 1.0) == float("inf")

    def test_budget_remaining_hand_computed(self):
        assert al.budget_remaining_frac(0, 100, 0.99) == 1.0
        assert al.budget_remaining_frac(1, 100, 0.99) == \
            pytest.approx(0.0)
        assert al.budget_remaining_frac(2, 100, 0.99) == \
            pytest.approx(-1.0)
        assert al.budget_remaining_frac(0, 0, 0.99) == 1.0

    def test_count_over_exact_at_bucket_bounds(self):
        # 0.5 / 1.0 / 2.0 / 4.0 are exact 2**(k/4) bucket bounds, so
        # count_over is exact there (an observation AT the threshold
        # is not "over" it)
        h = Histogram()
        for v in (0.5, 0.5, 1.0, 2.0, 4.0):
            h.observe(v)
        assert h.count_over(0.5) == 3
        assert h.count_over(1.0) == 2
        assert h.count_over(4.0) == 0
        assert h.count_over(-1.0) == 5  # everything, zeros included

    def test_windowed_burn_hand_computed(self):
        # target 1.0 s, objective 0.9 (10% budget). Baseline tick sees
        # 10 obs / 2 bad but its window delta is ZERO (first sample is
        # its own reference). The next tick adds 10 obs / 5 bad:
        # burn = (5/10) / 0.1 = 5.0 over the trailing window.
        reg = MetricsRegistry()
        h = reg.histogram("serving_ttft_seconds", {"tenant": "t0"})
        for _ in range(8):
            h.observe(0.25)
        for _ in range(2):
            h.observe(4.0)
        rule = al.BurnRateRule(
            "burn", "serving_ttft_seconds", objective=0.9,
            threshold_s=1.0, windows=((5.0, 1.0),))
        m = _mgr([rule])
        m.evaluate(registry=reg, now=0.0)
        st = m.statuses()[0]
        assert st["state"] == "inactive"
        assert st["annotations"]["burn"] == {"5s": 0.0}
        for _ in range(5):
            h.observe(0.25)
        for _ in range(5):
            h.observe(4.0)
        m.evaluate(registry=reg, now=10.0)
        st = m.statuses()[0]
        assert st["annotations"]["burn"] == {"5s": pytest.approx(5.0)}
        assert st["value"] == pytest.approx(5.0)  # ratio vs factor 1.0
        assert st["state"] == "firing"  # for_s=0: breach fires at once
        # cumulative budget over everything observed: 7 bad / 20 total
        assert st["annotations"]["bad_total"] == 7
        assert st["annotations"]["observed_total"] == 20
        assert st["annotations"]["budget_remaining_frac"] == \
            pytest.approx(1.0 - (7 / 20) / 0.1, abs=1e-6)

    def test_multi_window_needs_every_window_hot(self):
        # long window still remembers the burst, short window has gone
        # quiet: the rule must NOT breach (fast reset)
        reg = MetricsRegistry()
        h = reg.histogram("serving_ttft_seconds", {"tenant": "t0"})
        rule = al.BurnRateRule(
            "burn", "serving_ttft_seconds", objective=0.9,
            threshold_s=1.0, windows=((30.0, 1.0), (5.0, 1.0)))
        m = _mgr([rule])
        m.evaluate(registry=reg, now=0.0)
        for _ in range(10):
            h.observe(4.0)  # burst: 10/10 bad
        m.evaluate(registry=reg, now=10.0)
        st = m.statuses()[0]
        assert st["state"] == "firing"
        # no new traffic: at t=20 the 5 s window's reference is the
        # t=10 sample (delta zero) while the 30 s window still spans
        # the burst — min ratio goes to 0 and the alert starts clearing
        m.evaluate(registry=reg, now=20.0)
        st = m.statuses()[0]
        assert st["annotations"]["burn"]["30s"] > 1.0
        assert st["annotations"]["burn"]["5s"] == 0.0
        assert st["value"] == 0.0

    def test_per_tenant_targets_resolve_from_slo_spec(self):
        from paddle_tpu.obs.slo import SLOClass, SLOSpec

        spec = SLOSpec(default=SLOClass(ttft_s=2.0),
                       per_tenant={"gold": SLOClass(ttft_s=0.5)})
        rules = al.burn_rules_from_slo(spec, objective=0.9,
                                       windows=((5.0, 1.0),))
        rule = {r.metric: r for r in rules}["serving_ttft_seconds"]
        assert rule.target_for("gold") == 0.5
        assert rule.target_for("anyone_else") == 2.0
        # 1.0 s observations AFTER the baseline tick: bad for gold
        # only — the rule fans out per tenant and only gold's budget
        # burns over the window
        reg = MetricsRegistry()
        hists = {t: reg.histogram("serving_ttft_seconds",
                                  {"tenant": t})
                 for t in ("gold", "bronze")}
        m = _mgr([rule])
        m.evaluate(registry=reg, now=0.0)
        for h in hists.values():
            for _ in range(10):
                h.observe(1.0)
        m.evaluate(registry=reg, now=10.0)
        by_tenant = {s["labels"]["tenant"]: s for s in m.statuses()}
        assert by_tenant["gold"]["state"] == "firing"
        assert by_tenant["bronze"]["state"] == "inactive"


# ---------------------------------------------------------------------------
# lifecycle determinism


class TestLifecycle:
    def _gauge_reg(self):
        reg = MetricsRegistry()
        g = reg.gauge("serving_queue_frac", {"engine": "e0"})
        return reg, g

    def _rule(self, threshold=0.95, **kw):
        kw.setdefault("stat", "value")
        return al.ThresholdRule("queue_saturated",
                                "serving_queue_frac", threshold, **kw)

    def test_pending_firing_resolved_explicit_clock(self):
        reg, g = self._gauge_reg()
        m = _mgr([self._rule(for_s=2.0, resolve_for_s=2.0)])
        g.set(0.99)
        m.evaluate(registry=reg, now=0.0)
        assert m.statuses()[0]["state"] == "pending"
        assert m.events == []  # entering pending is not an event
        m.evaluate(registry=reg, now=3.0)
        st = m.statuses()[0]
        assert st["state"] == "firing" and st["fired_at"] == 3.0
        g.set(0.5)
        m.evaluate(registry=reg, now=4.0)
        assert m.statuses()[0]["state"] == "firing"  # hysteresis hold
        m.evaluate(registry=reg, now=6.5)
        st = m.statuses()[0]
        assert st["state"] == "resolved" and st["resolved_at"] == 6.5
        assert [e["event"] for e in m.events] == ["firing", "resolved"]

    def test_pending_flap_returns_to_inactive_without_event(self):
        reg, g = self._gauge_reg()
        m = _mgr([self._rule(for_s=5.0)])
        g.set(0.99)
        m.evaluate(registry=reg, now=0.0)
        assert m.statuses()[0]["state"] == "pending"
        g.set(0.1)
        m.evaluate(registry=reg, now=1.0)
        assert m.statuses()[0]["state"] == "inactive"
        assert m.events == []

    def test_resolve_threshold_widens_the_clear_band(self):
        reg, g = self._gauge_reg()
        m = _mgr([self._rule(resolve_threshold=0.8,
                             resolve_for_s=1.0)])
        g.set(0.99)
        m.evaluate(registry=reg, now=0.0)
        assert m.statuses()[0]["state"] == "firing"
        # below the fire threshold but above the resolve threshold:
        # still held, never starts clearing
        g.set(0.9)
        m.evaluate(registry=reg, now=5.0)
        m.evaluate(registry=reg, now=10.0)
        assert m.statuses()[0]["state"] == "firing"
        g.set(0.5)
        m.evaluate(registry=reg, now=11.0)
        m.evaluate(registry=reg, now=12.5)
        assert m.statuses()[0]["state"] == "resolved"

    def test_refire_after_resolve(self):
        reg, g = self._gauge_reg()
        m = _mgr([self._rule()])
        for now, v in ((0.0, 0.99), (1.0, 0.1), (2.0, 0.99)):
            g.set(v)
            m.evaluate(registry=reg, now=now)
        assert [e["event"] for e in m.events] == \
            ["firing", "resolved", "firing"]

    def test_seeded_flapping_is_deterministic(self, tmp_path):
        # same seeded signal, two fresh managers: byte-identical
        # journals and identical event logs
        rnd = random.Random(0)
        values = [rnd.random() for _ in range(60)]

        def run(journal):
            reg, g = self._gauge_reg()
            m = al.AlertManager(
                [self._rule(threshold=0.5, for_s=2.0,
                            resolve_for_s=2.0)],
                journal_path=str(journal), emit_trace=False,
                emit_metrics=False)
            for i, v in enumerate(values):
                g.set(v)
                m.evaluate(registry=reg, now=float(i))
            return m

        m1 = run(tmp_path / "j1.jsonl")
        m2 = run(tmp_path / "j2.jsonl")
        assert m1.events == m2.events
        assert len(m1.events) > 0  # the seed does flap across 0.5
        assert (tmp_path / "j1.jsonl").read_bytes() == \
            (tmp_path / "j2.jsonl").read_bytes()
        for line in (tmp_path / "j1.jsonl").read_text().splitlines():
            assert json.loads(line)["schema"] == al.ALERT_SCHEMA

    def test_clock_never_runs_backwards(self):
        reg, g = self._gauge_reg()
        m = _mgr([self._rule(for_s=2.0)])
        g.set(0.99)
        m.evaluate(registry=reg, now=10.0)
        # a stale clock (wall tick racing a test clock) is clamped to
        # the newest now ever seen — the hold window can't reopen
        m.evaluate(registry=reg, now=5.0)
        assert m.statuses()[0]["state"] == "pending"
        m.evaluate(registry=reg, now=12.0)
        assert m.statuses()[0]["state"] == "firing"


# ---------------------------------------------------------------------------
# absence: a silent publisher is an alert


class TestAbsence:
    def test_stale_source_fires_and_fresh_source_does_not(self):
        m = _mgr([al.AbsenceRule("replica_silent", max_age_s=5.0)])
        m.evaluate(registry=MetricsRegistry(), now=0.0,
                   ages={"rep-0": 0.2, "rep-1": 9.0})
        by_src = {s["labels"]["source"]: s for s in m.statuses()}
        assert by_src["rep-0"]["state"] == "inactive"
        assert by_src["rep-1"]["state"] == "firing"

    def test_vanished_source_keeps_alerting(self):
        # the manager remembers every source it has ever seen: a
        # source deleted from the store entirely grades as age=inf
        m = _mgr([al.AbsenceRule("replica_silent", max_age_s=5.0)])
        m.evaluate(registry=MetricsRegistry(), now=0.0,
                   ages={"rep-0": 0.1, "rep-1": 0.1})
        m.evaluate(registry=MetricsRegistry(), now=10.0,
                   ages={"rep-0": 0.1})
        by_src = {s["labels"]["source"]: s for s in m.statuses()}
        assert by_src["rep-1"]["state"] == "firing"
        assert by_src["rep-1"]["annotations"] == {"vanished": True}

    def test_without_ages_absence_is_skipped_not_cleared(self):
        m = _mgr([al.AbsenceRule("replica_silent", max_age_s=5.0)])
        m.evaluate(registry=MetricsRegistry(), now=0.0,
                   ages={"rep-0": 9.0})
        assert m.statuses()[0]["state"] == "firing"
        # a registry-only tick (no fleet store in sight) must not
        # resolve an absence alert it cannot re-grade
        m.evaluate(registry=MetricsRegistry(), now=20.0)
        assert m.statuses()[0]["state"] == "firing"

    def test_fleet_path_grades_published_unix(self):
        from paddle_tpu.distributed.store import MemKVStore

        store = MemKVStore()
        reg = MetricsRegistry()
        agg.publish(store, "rep-0", registry=reg)
        # rep-1 published long ago: craft the blob with an old stamp
        state = reg.dump_state()
        state["source"] = "rep-1"
        state["published_unix"] = time.time() - 60.0
        store.put_bytes("obs/rep-1/metrics",
                        json.dumps(state, sort_keys=True).encode())
        m = _mgr([al.AbsenceRule("replica_silent", max_age_s=5.0)])
        m.evaluate_fleet(store)
        by_src = {s["labels"]["source"]: s for s in m.statuses()}
        assert by_src["rep-0"]["state"] == "inactive"
        assert by_src["rep-1"]["state"] == "firing"
        assert by_src["rep-1"]["value"] >= 55.0


# ---------------------------------------------------------------------------
# regression sentinel


def _ledger(tmp_path, name, values, metric="bench_tokens_per_sec",
            **fields):
    path = tmp_path / name
    for i, v in enumerate(values):
        rg.bench_record("synthetic", metric, v, "tok/s",
                        ledger_path=str(path), emit=False, **fields)
    return str(path)


def _round_files(tmp_path):
    """Driver round files (``{n, cmd, rc, tail, parsed}``): two rounds
    with a parsed metric line, then a failed and a timed-out one."""
    rounds = [(0, {"metric": "synthetic_tokens_per_sec", "value": 1000.0,
                   "unit": "tokens/s"}),
              (0, {"metric": "synthetic_tokens_per_sec", "value": 1010.0,
                   "unit": "tokens/s"}),
              (1, None), (124, None)]
    paths = []
    for n, (rc, parsed) in enumerate(rounds, 1):
        path = tmp_path / f"round_{n:02d}.json"
        path.write_text(json.dumps({"n": n, "cmd": "python bench.py",
                                    "rc": rc, "tail": "", "parsed": parsed}))
        paths.append(str(path))
    return paths


class TestRegress:
    def test_true_regression_flagged(self, tmp_path):
        rnd = random.Random(7)
        base = [1000.0 + rnd.uniform(-15, 15) for _ in range(10)]
        path = _ledger(tmp_path, "led.jsonl", base + [700.0])
        verdicts = rg.detect_regressions(rg.load_ledger([path]))
        assert [v["verdict"] for v in verdicts] == ["regression"]
        v = verdicts[0]
        assert v["polarity"] == "up" and v["delta"] < -v["threshold"]

    def test_run_to_run_noise_stays_quiet(self, tmp_path):
        rnd = random.Random(7)
        base = [1000.0 + rnd.uniform(-15, 15) for _ in range(10)]
        path = _ledger(tmp_path, "led.jsonl", base + [base[0] * 0.99])
        verdicts = rg.detect_regressions(rg.load_ledger([path]))
        assert [v["verdict"] for v in verdicts] == ["ok"]

    def test_down_polarity_metric_flags_latency_growth(self, tmp_path):
        path = _ledger(tmp_path, "led.jsonl",
                       [0.100, 0.101, 0.099, 0.100, 0.300],
                       metric="recovery_ram_tier_s")
        verdicts = rg.detect_regressions(rg.load_ledger([path]))
        assert [v["verdict"] for v in verdicts] == ["regression"]
        assert verdicts[0]["polarity"] == "down"
        # and shrinking latency is an improvement, not a regression
        path2 = _ledger(tmp_path, "led2.jsonl",
                        [0.100, 0.101, 0.099, 0.100, 0.030],
                        metric="recovery_ram_tier_s")
        verdicts = rg.detect_regressions(rg.load_ledger([path2]))
        assert [v["verdict"] for v in verdicts] == ["improvement"]

    def test_insufficient_history_stays_quiet(self, tmp_path):
        path = _ledger(tmp_path, "led.jsonl", [1000.0, 400.0])
        verdicts = rg.detect_regressions(rg.load_ledger([path]))
        assert [v["verdict"] for v in verdicts] == \
            ["insufficient_data"]

    def test_config_change_starts_a_fresh_baseline(self, tmp_path):
        # same metric, different config signature: separate groups
        path = str(tmp_path / "led.jsonl")
        for v in (1000.0, 1001.0, 999.0, 1000.0):
            rg.bench_record("b", "tps", v, "", ledger_path=path,
                            emit=False, config={"batch": 8})
        rg.bench_record("b", "tps", 500.0, "", ledger_path=path,
                        emit=False, config={"batch": 32})
        verdicts = rg.detect_regressions(rg.load_ledger([path]))
        assert sorted(v["verdict"] for v in verdicts) == \
            ["insufficient_data", "ok"]

    def test_polarity_resolution_order(self):
        assert rg.polarity_of("llama_train_tokens_per_sec_per_chip") \
            == "up"
        assert rg.polarity_of("trainfault_recovery_ram_tier_s") == \
            "down"
        # an up-token wins over a down-suffix in the same name
        assert rg.polarity_of("tokens_per_sec_window_s") == "up"
        # an explicit per-record override beats every heuristic
        assert rg.polarity_of("tokens_per_sec",
                              {"polarity": "down"}) == "down"

    def test_bench_record_stdout_and_ledger_contract(self, tmp_path,
                                                     capsys):
        path = str(tmp_path / "led.jsonl")
        rec = rg.bench_record("b", "m", 1.5, "s", ledger_path=path,
                              extra={"rows": 3})
        out = capsys.readouterr().out.strip()
        doc = json.loads(out)  # the driver's _last_metric_line parse
        assert doc["metric"] == "m" and doc["value"] == 1.5
        assert doc["schema"] == rg.BENCH_SCHEMA
        assert rec["extra"] == {"rows": 3}
        rg.bench_record("b", "m", 2.5, "s", ledger_path=path,
                        emit=False, line_prefix="BENCH_ROW ")
        loaded = rg.load_ledger([path])
        assert [r["value"] for r in loaded] == [1.5, 2.5]
        assert all(r["schema"] == rg.BENCH_SCHEMA for r in loaded)

    def test_loader_accepts_driver_round_files(self, tmp_path):
        # driver round files load: parsed payloads become records,
        # null-parsed (failed / timed-out) rounds are skipped
        records = rg.load_ledger(_round_files(tmp_path))
        assert [r["value"] for r in records] == [1000.0, 1010.0]
        assert all("metric" in r and "bench" in r for r in records)


# ---------------------------------------------------------------------------
# CLI exit codes


class TestCLI:
    def test_regress_flags_synthetic_regression(self, tmp_path):
        rnd = random.Random(7)
        base = [1000.0 + rnd.uniform(-15, 15) for _ in range(10)]
        path = _ledger(tmp_path, "led.jsonl", base + [700.0])
        r = _cli(["regress", "--ledger", path])
        assert r.returncode == 1, r.stdout + r.stderr
        assert "REGRESSION" in r.stdout
        assert "regression(s) detected" in r.stderr

    def test_regress_quiet_on_stable_ledger(self, tmp_path):
        rnd = random.Random(7)
        base = [1000.0 + rnd.uniform(-15, 15) for _ in range(10)]
        path = _ledger(tmp_path, "led.jsonl", base + [base[-1]])
        r = _cli(["regress", "--ledger", path])
        assert r.returncode == 0, r.stdout + r.stderr

    def test_regress_quiet_on_round_file_history(self, tmp_path):
        r = _cli(["regress", "--ledger", *_round_files(tmp_path)])
        assert r.returncode == 0, r.stdout + r.stderr

    def test_alerts_rc0_on_healthy_fleet_rc1_on_silent(self, tmp_path):
        from paddle_tpu.distributed.store import FileKVStore

        root = str(tmp_path / "fleet")
        store = FileKVStore(root)
        reg = MetricsRegistry()
        agg.publish(store, "rep-0", registry=reg)
        # the rule's default age (5 s) is shorter than a subprocess can
        # take to import jax beside five busy workers: 30 s keeps the
        # healthy source healthy, and the silent one is 60 s old
        r = _cli(["alerts", root, "--absence-age", "30"])
        assert r.returncode == 0, r.stdout + r.stderr
        # now a source whose last publication is a minute old
        state = reg.dump_state()
        state["source"] = "rep-1"
        state["published_unix"] = time.time() - 60.0
        store.put_bytes("obs/rep-1/metrics",
                        json.dumps(state, sort_keys=True).encode())
        r = _cli(["alerts", root, "--absence-age", "30"])
        assert r.returncode == 1, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        firing = [d for d in doc if d["state"] == "firing"]
        assert firing and firing[0]["rule"] == "replica_silent"

    def test_alerts_rules_lists_the_rule_set(self):
        r = _cli(["alerts", "--rules", "--ttft-slo", "2.0", "unused"])
        assert r.returncode == 0, r.stdout + r.stderr
        rules = json.loads(r.stdout)
        kinds = sorted(d["kind"] for d in rules)
        # only the TTFT histogram is constrained by --ttft-slo, so
        # exactly one burn rule joins the stock absence + queue rules
        assert kinds == ["absence", "burn_rate", "threshold"]
        burn = [d for d in rules if d["kind"] == "burn_rate"][0]
        assert burn["metric"] == "serving_ttft_seconds"

    def test_top_once_renders_a_frame(self, tmp_path):
        from paddle_tpu.distributed.store import FileKVStore

        root = str(tmp_path / "fleet")
        agg.publish(FileKVStore(root), "rep-0",
                    registry=MetricsRegistry())
        r = _cli(["top", root, "--once"])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "paddle_tpu.obs top" in r.stdout
        assert "rep-0" in r.stdout and "ALERTS" in r.stdout


# ---------------------------------------------------------------------------
# health surfaces + loadgen parity


class TestHealthSurfaces:
    def test_envelope_reports_empty_summary_without_manager(self):
        old = al.set_default_manager(None)
        try:
            h = obs.health_envelope("kindx", {})
            assert h["alerts"] == {"rules": 0, "pending": 0,
                                   "firing": 0, "resolved": 0,
                                   "active": []}
        finally:
            al.set_default_manager(old)

    def test_envelope_carries_the_default_managers_firing(self):
        reg = MetricsRegistry()
        g = reg.gauge("serving_queue_frac", {"engine": "e0"})
        g.set(0.99)
        m = _mgr([al.ThresholdRule("queue_saturated",
                                   "serving_queue_frac", 0.95,
                                   stat="value")])
        m.evaluate(registry=reg, now=time.time())
        old = al.set_default_manager(m)
        try:
            h = obs.health_envelope("kindx", {"legacy": 1})
            assert h["legacy"] == 1
            assert h["alerts"]["firing"] == 1
            assert h["alerts"]["active"][0]["rule"] == \
                "queue_saturated"
        finally:
            al.set_default_manager(old)


class TestLoadgenParity:
    def _loadgen(self):
        import importlib.util

        name = "_alerts_loadgen"
        if name in sys.modules:
            return sys.modules[name]
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, "benchmarks", "loadgen.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod  # dataclasses resolve via sys.modules
        spec.loader.exec_module(mod)
        return mod

    def test_burn_columns_match_the_alert_engines_arithmetic(self):
        lg = self._loadgen()
        # 37 of 40 met → 3 bad; the report stores attainment rounded,
        # burn_columns round-trips the integer back out
        table = {"requests": 40,
                 "attainment": {"all": round(37 / 40, 6)}}
        cols = lg.burn_columns(table, objective=0.99)
        assert cols["burn_rate"] == pytest.approx(
            al.burn_rate(3, 40, 0.99), abs=1e-6)
        assert cols["budget_remaining_frac"] == pytest.approx(
            al.budget_remaining_frac(3, 40, 0.99), abs=1e-6)
        assert cols["slo_objective"] == 0.99
        # no graded requests: burn 0, budget untouched — matches the
        # engine's no-traffic convention
        cols = lg.burn_columns({"requests": 0,
                                "attainment": {"all": None}})
        assert cols["burn_rate"] == 0.0
        assert cols["budget_remaining_frac"] == 1.0


# ---------------------------------------------------------------------------
# the e2e proof: chaos-driven SLO burn through the full lifecycle


class TestE2EFleet:
    @pytest.mark.slow   # 130-340 s of tier-1, on no cell's path (ROADMAP D14 a)
    def test_burn_alert_full_lifecycle_over_chaos_fleet(self, tmp_path):
        from paddle_tpu.distributed.store import MemKVStore
        from paddle_tpu.inference.cluster import (ClusterRouter,
                                                  InProcessReplica)
        from paddle_tpu.inference.serving import \
            ContinuousBatchingEngine
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.obs import trace as _trace
        from paddle_tpu.testing import chaos
        from paddle_tpu.testing.chaos import ChaosSchedule

        obs.registry().reset()
        _trace.ring().clear()
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())

        def factory():
            return ContinuousBatchingEngine(
                model, max_batch=4, max_len=48, block_size=8,
                num_blocks=28, prompt_pad=24)

        router = ClusterRouter(
            [InProcessReplica(f"rep{i}", factory) for i in range(2)],
            block_size=8)
        rng = np.random.RandomState(3)

        def drive(n, tag):
            for i in range(n):
                router.submit(f"{tag}{i}",
                              rng.randint(0, 50, (8,)).astype(np.int32),
                              max_new_tokens=3, tenant="t0")
            router.run(deadline=120.0)

        # clean phase: establish the healthy TTFT so the chaos phase's
        # threshold adapts to whatever this host's baseline is
        drive(4, "clean")
        hist = Histogram()
        for _, h in obs.registry()._metrics[
                "serving_ttft_seconds"].series.items():
            hist.merge(h)
        clean_p99 = hist.percentile(99.0)
        thr = max(0.1, clean_p99 * 3.0)
        slow_s = max(0.25, clean_p99 * 6.0)

        journal = tmp_path / "alerts.jsonl"
        mgr = al.AlertManager(
            [al.BurnRateRule(
                "slo_burn_serving_ttft_seconds",
                "serving_ttft_seconds", objective=0.9,
                threshold_s=thr, windows=((30.0, 1.0), (5.0, 1.0)),
                for_s=5.0, resolve_for_s=5.0)],
            journal_path=str(journal))
        old = al.set_default_manager(mgr)
        base = time.time()
        try:
            mgr.evaluate(now=base)  # baseline sample: zero delta
            assert mgr.active() == []

            # chaos: every engine step stalls long past the TTFT
            # target — every request in these batches burns budget
            with chaos.active(ChaosSchedule().every(
                    "serving.step", 1, "slow", slow_s)):
                drive(3, "burn_a")
                mgr.evaluate(now=base + 10.0)
                st = mgr.active()
                assert [s["state"] for s in st] == ["pending"]
                drive(3, "burn_b")
                mgr.evaluate(now=base + 20.0)
            st = mgr.firing()
            assert len(st) == 1 and st[0]["labels"]["tenant"] == "t0"
            assert st[0]["annotations"]["target_s"] == \
                pytest.approx(thr)

            # firing is visible from every surface: the router's
            # health() envelope ...
            h = router.health()
            assert h["alerts"]["firing"] == 1
            assert h["alerts"]["active"][0]["rule"] == \
                "slo_burn_serving_ttft_seconds"
            # ... the merged fleet snapshot (the firing counter rides
            # the local registry into publication) ...
            store = MemKVStore()
            agg.publish(store, "rep-0")
            snap = agg.fleet_snapshot(store)
            assert "obs_alerts_fired_total" in snap["metrics"]

            # quiet traffic clears both windows; hysteresis holds for
            # resolve_for_s before the resolved event lands
            mgr.evaluate(now=base + 40.0)
            assert mgr.firing(), "still inside the clear hold"
            mgr.evaluate(now=base + 50.0)
            assert mgr.firing() == []
            assert [s["state"] for s in mgr.active()] == ["resolved"]
            assert [e["event"] for e in mgr.events] == \
                ["firing", "resolved"]

            # ... and the stitched Chrome trace carries both instants
            events = _trace.export_chrome_trace(
                _trace.stitch_traces([_trace.ring().dump()]),
                path=str(tmp_path / "trace.json"))
            names = [e.get("name") for e in events]
            assert "alert_firing" in names
            assert "alert_resolved" in names
            exported = json.loads(
                (tmp_path / "trace.json").read_text())
            assert any(e.get("name") == "alert_firing"
                       for e in exported["traceEvents"])
            journal_events = [json.loads(s) for s in
                              journal.read_text().splitlines()]
            assert [e["event"] for e in journal_events] == \
                ["firing", "resolved"]
        finally:
            al.set_default_manager(old)
            chaos.uninstall()
            router.stop(deadline=30.0)

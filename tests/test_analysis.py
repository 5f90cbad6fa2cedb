"""graft-lint: rule fixtures, suppression/baseline mechanics, the
self-lint gate, and the runtime recompile sanitizer (ISSUE 3).

Every rule is proven BOTH ways: fixtures seed >= 2 true violations it
must catch AND >= 2 near-misses it must NOT flag (the near-misses are
the historical false-positive shapes: scheduler.step(), rank-
conditional logging, dict .get(), x = f(x) rebinding, ...).

Run standalone via ``pytest -m analysis`` (< 60 s).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.analysis import (
    analyze_paths,
    analyze_source,
    apply_baseline,
    baseline_entries,
    default_baseline_path,
    load_baseline,
)

pytestmark = pytest.mark.analysis

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO_ROOT, "paddle_tpu")


def findings_for(src, rule, path="fixture.py"):
    return analyze_source(textwrap.dedent(src), path, select=[rule])


def lines_of(findings):
    return [f.line for f in findings]


# ---------------------------------------------------------------------------
# TRACE001 — host side effects in traced regions


class TestTrace001:
    def test_catches_host_effects_under_jit_and_to_static(self):
        src = """
        import time
        import numpy as np
        import jax

        @jax.jit
        def step(x):
            print("step", x)        # line 8: runs at trace time only
            t = time.time()         # line 9
            return x * 2

        def loss(x):
            n = np.random.randn(3)  # line 13
            return x + n
        loss_s = to_static(loss)
        """
        got = findings_for(src, "TRACE001")
        assert lines_of(got) == [8, 9, 13]
        assert all(f.severity == "error" for f in got)
        assert "trace time" in got[0].message

    def test_near_misses_stay_clean(self):
        src = """
        import time
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            jax.debug.print("x = {}", x)   # in-graph print: fine
            k = jax.random.PRNGKey(0)      # traced randomness: fine
            return jnp.sum(x)

        def host_loop(x):
            print("eager print is fine")
            t = time.time()
            return x
        """
        assert findings_for(src, "TRACE001") == []


# ---------------------------------------------------------------------------
# TRACE002 — tensor-valued control flow under jax.jit


class TestTrace002:
    def test_catches_tensor_if_and_while(self):
        src = """
        import jax

        @jax.jit
        def f(x):
            if x > 0:               # line 6
                x = x * 2
            while x.sum() < 3:      # line 8
                x = x + 1
            return x

        def g(y):
            return y
        g_j = jax.jit(g)

        def h(y):
            z = y * 2
            if z.mean() > 0:        # line 18: taint through assignment
                return z
            return y
        h_j = jax.jit(h)
        """
        got = findings_for(src, "TRACE002")
        assert lines_of(got) == [6, 8, 18]
        assert all(f.severity == "error" for f in got)

    def test_near_misses_stay_clean(self):
        src = """
        import jax

        @jax.jit
        def shape_branch(x):
            if x.shape[0] > 2:      # static shape info: fine
                return x * 2
            return x

        def static_flag(x, training):
            if training:            # declared static below: fine
                return x * 2
            return x
        sf = jax.jit(static_flag, static_argnames=("training",))

        def eager(x):
            if x > 0:               # not a jit region: fine
                return x
            return -x

        @to_static
        def converted(x):
            if x.mean() > 0:        # dy2static converts this: fine
                return x
            return -x
        """
        assert findings_for(src, "TRACE002") == []


# ---------------------------------------------------------------------------
# RECOMP001 — recompile/sync triggers in hot loops


class TestRecomp001:
    def test_catches_item_and_varying_scalar_arg(self):
        src = """
        import jax

        def fn(x, i):
            return x + i
        step = jax.jit(fn)

        def train(xs):
            total = 0.0
            for i in range(100):
                y = step(xs, i)         # line 11: retrace per i
                total += y.item()       # line 12: sync per step
            return total
        """
        got = findings_for(src, "RECOMP001")
        assert lines_of(got) == [11, 12]
        assert all(f.severity == "warning" for f in got)
        assert "retraces" in got[0].message
        assert "device sync" in got[1].message

    def test_near_misses_stay_clean(self):
        src = """
        import jax
        import jax.numpy as jnp

        def fn(x, i):
            return x + i
        step = jax.jit(fn, static_argnums=(1,))
        plain = jax.jit(fn)

        def train(xs):
            for i in range(100):
                y = step(xs, i)             # static_argnums: fine
                z = plain(xs, jnp.asarray(i))  # on-device scalar: fine
            final = z.item()                # outside the loop: fine
            return final
        """
        assert findings_for(src, "RECOMP001") == []


# ---------------------------------------------------------------------------
# COLL001 — rank-conditional collectives


class TestColl001:
    def test_catches_one_sided_collectives(self):
        src = """
        from paddle_tpu import distributed as dist

        def save_and_sync(t, rank):
            if rank == 0:
                dist.broadcast(t, src=0)    # line 6
            return t

        def gather_stats(t):
            if dist.get_rank() == 0:
                pass
            else:
                out = dist.all_gather(t)    # line 13
            return t
        """
        got = findings_for(src, "COLL001")
        assert lines_of(got) == [6, 13]
        assert all(f.severity == "error" for f in got)
        assert "hang" in got[0].message

    def test_near_misses_stay_clean(self):
        src = """
        from paddle_tpu import distributed as dist

        def log_on_master(t, rank):
            if rank == 0:
                print("loss:", t)           # rank-conditional logging
            return t

        def p2p(t, rank):
            if rank == 0:
                dist.send(t, dst=1)         # send/recv pairing is the
            else:                           # correct conditional idiom
                t = dist.recv(src=0)
            return t

        def both_sides(t, rank):
            if rank == 0:
                dist.all_reduce(t)
            else:
                dist.all_reduce(t)          # matched: every rank calls
            return t

        def unconditional(t):
            dist.broadcast(t, src=0)
            return t
        """
        assert findings_for(src, "COLL001") == []


# ---------------------------------------------------------------------------
# DDL001 — blocking calls without a Deadline


class TestDdl001:
    def test_catches_unbounded_blocking_calls(self):
        src = """
        import time
        from paddle_tpu.utils.retries import Deadline

        def drain(sock, work_q):
            data = sock.recv(1024)          # line 6
            item = work_q.get()             # line 7
            return data, item

        def reap(proc):
            while proc.poll() is None:
                time.sleep(0.1)             # line 12: unbudgeted poll
            proc_out = proc.communicate()   # line 13
            return proc_out
        """
        got = findings_for(src, "DDL001")
        assert lines_of(got) == [6, 7, 12, 13]
        assert all(f.severity == "warning" for f in got)

    def test_near_misses_stay_clean(self):
        src = """
        import time
        from paddle_tpu.utils.retries import Deadline

        def bounded(sock, work_q, deadline):
            sock.settimeout(deadline.timeout(5.0))
            data = sock.recv(1024)                     # settimeout'd
            item = work_q.get(timeout=deadline.remaining())
            return data, item

        def peek(work_q):
            return work_q.get(block=False)  # non-blocking get

        def config(cfg):
            return cfg.get("op")            # dict-style get

        def heartbeat(stop_event, interval):
            while not stop_event.wait(interval):  # bounded wait
                pass
        """
        assert findings_for(src, "DDL001") == []

    def test_only_applies_to_retries_disciplined_modules(self):
        src = """
        def drain(sock):
            return sock.recv(1024)
        """
        assert findings_for(src, "DDL001") == []


# ---------------------------------------------------------------------------
# DONATE001 — use after donation


class TestDonate001:
    def test_catches_use_after_donation(self):
        src = """
        import jax

        def fn(pools, x):
            return pools
        step = jax.jit(fn, donate_argnums=(0,))

        def bad_read(pools, x):
            out = step(pools, x)
            return pools                    # line 10: dead buffer

        def bad_pass(pools, x):
            out = step(pools, x)
            checkpoint(pools)               # line 14: dead buffer
            return out
        """
        got = findings_for(src, "DONATE001")
        assert lines_of(got) == [10, 14]
        assert all(f.severity == "error" for f in got)
        assert "donated" in got[0].message

    def test_near_misses_stay_clean(self):
        src = """
        import jax

        def fn(pools, x):
            return pools
        step = jax.jit(fn, donate_argnums=(0,))
        nodonate = jax.jit(fn)

        def rebind(pools, x):
            pools = step(pools, x)          # the engine idiom
            return pools                    # reads the NEW buffer

        def rebound_later(pools, x):
            out = step(pools, x)
            pools = out
            return pools

        def no_donation(pools, x):
            out = nodonate(pools, x)
            return pools                    # nothing was donated

        def eager_reference(pools, x):
            out = fn(pools, x)              # the RAW function: plain
            return pools                    # eager call, no donation
        """
        assert findings_for(src, "DONATE001") == []

    def test_fused_optimizer_rebind_writeback_stays_clean(self):
        """The fused-AdamW writeback idiom (optimizer._fused_update):
        the kernel returns FRESH buffers and the caller rebinds the
        param/accumulator slots — in-place-looking, but no read of a
        donated original ever follows the compiled call."""
        src = """
        import jax

        def kernel(p, g, m, v):
            return p, m, v
        fused = jax.jit(kernel, donate_argnums=(0, 2, 3))

        def fused_update(p, g, m, v):
            p_new, m_new, v_new = fused(p, g, m, v)
            p = p_new                   # rebind: the NEW buffer
            m = m_new
            v = v_new
            return p, m, v
        """
        assert findings_for(src, "DONATE001") == []

    def test_raw_function_in_loop_is_not_a_jit_wrapper(self):
        """`step = jax.jit(fn)` must not make eager `fn(...)` calls
        look compiled — the eager/reference-path idiom stays clean for
        RECOMP001 too."""
        src = """
        import jax

        def fn(x, i):
            return x + i
        step = jax.jit(fn)

        def reference(xs):
            for i in range(10):
                y = fn(xs, i)               # eager: retraces nothing
            return y
        """
        assert findings_for(src, "RECOMP001") == []


# ---------------------------------------------------------------------------
# HOTSYNC001 — blocking fetch of a jitted output in a serving hot loop


INFER_PATH = "paddle_tpu/inference/fixture.py"


class TestHotsync001:
    def test_catches_blocking_fetch_in_while_loop(self):
        src = """
        import numpy as np

        class Engine:
            def run(self):
                while self.pending():
                    toks, self._pools = self._run_jit(
                        self._decode_jit, self._pools)
                    out = np.asarray(toks)      # line 9: device sync
                return out
        """
        got = findings_for(src, "HOTSYNC001", path=INFER_PATH)
        assert lines_of(got) == [9]
        assert "hot path" in got[0].message or "loop" in got[0].message

    def test_catches_item_in_step_function(self):
        """A fetch in a `step`/`*_step` function is flagged even
        without a lexical loop — step() IS the loop body (run() and
        the supervisor call it every engine iteration)."""
        src = """
        import numpy as np

        class Engine:
            def _decode_step(self):
                nxt = decode_jit(self._pools, self._tok)
                first = nxt.item()              # line 7: device sync
                return first
        """
        got = findings_for(src, "HOTSYNC001", path=INFER_PATH)
        assert lines_of(got) == [7]
        assert ".item()" in got[0].message

    def test_near_miss_copy_to_host_async_is_sanctioned(self):
        """The copy-ring idiom: starting the async D2H copy first means
        the later gather does not stall the dispatch pipeline."""
        src = """
        import numpy as np

        class Engine:
            def step(self):
                toks, self._pools = self._run_jit(
                    self._decode_jit, self._pools)
                toks.copy_to_host_async()       # copy already in flight
                out = np.asarray(toks)
                return out
        """
        assert findings_for(src, "HOTSYNC001", path=INFER_PATH) == []

    def test_near_miss_host_value_and_cold_path_stay_clean(self):
        """np.asarray on a host value in a loop, and a jit fetch
        OUTSIDE any loop in a non-step function (a one-off drain /
        debug probe), are both fine."""
        src = """
        import numpy as np

        class Engine:
            def collect(self, reqs):
                out = []
                while reqs:
                    r = reqs.pop()
                    out.append(np.asarray(r.prompt))   # host array
                return out

            def debug_probe(self):
                toks, self._pools = self._run_jit(
                    self._decode_jit, self._pools)
                return np.asarray(toks)      # cold path: not a loop
        """
        assert findings_for(src, "HOTSYNC001", path=INFER_PATH) == []

    def test_near_miss_outside_inference_modules(self):
        """The rule scopes to inference/ — ops/bench/reference code
        fetches eagerly by design."""
        src = """
        import numpy as np

        def step(pools):
            toks = decode_jit(pools)
            return np.asarray(toks)
        """
        assert findings_for(
            src, "HOTSYNC001", path="paddle_tpu/ops/fixture.py") == []
        # ...and the identical source IS flagged under inference/
        assert lines_of(findings_for(
            src, "HOTSYNC001", path=INFER_PATH)) == [6]

    def test_suppression_comment_works(self):
        src = """
        import numpy as np

        class Engine:
            def step(self):
                toks = self._decode_jit(self._pools)
                return np.asarray(toks)  # graft-lint: disable=HOTSYNC001
        """
        assert findings_for(src, "HOTSYNC001", path=INFER_PATH) == []


# ---------------------------------------------------------------------------
# OBS001 — obs span/metric calls inside traced regions


class TestObs001:
    def test_catches_spans_and_metric_factories_under_jit(self):
        src = """
        import jax
        from paddle_tpu import obs as _obs
        from paddle_tpu.obs.metrics import registry as _obs_registry

        @jax.jit
        def step(x):
            with _obs.span("decode_math"):   # line 8: trace-time span
                y = x * 2
            _obs.instant("stepped")          # line 10
            _obs_registry().counter("steps_total").inc()  # line 11
            return y

        def fwd(x):
            _obs.start_span("fwd")           # line 15
            return x + 1
        fwd_s = to_static(fwd)
        """
        got = findings_for(src, "OBS001")
        assert lines_of(got) == [8, 10, 11, 15]
        assert all(f.severity == "error" for f in got)
        assert "trace time" in got[0].message

    def test_near_misses_stay_clean(self):
        src = """
        import jax
        from paddle_tpu import obs as _obs

        @jax.jit
        def step(x):
            # a non-obs receiver whose method happens to be named
            # span/instant must not match
            y = doc.span(x)
            z = clock.instant()
            return y + z

        def host_loop(x):
            # obs on the host side of the jit boundary: the POINT
            with _obs.span("dispatch"):
                out = step(x)
            _obs.instant("harvested")
            return out
        """
        assert findings_for(src, "OBS001") == []

    def test_suppression_comment_works(self):
        src = """
        import jax
        from paddle_tpu import obs as _obs

        @jax.jit
        def step(x):
            _obs.instant("trace-time marker")  # graft-lint: disable=OBS001
            return x
        """
        assert findings_for(src, "OBS001") == []


# ---------------------------------------------------------------------------
# OBS002 — unbounded dynamic label values on the serving/training path


class TestObs002:
    PATH = "paddle_tpu/inference/engine.py"

    def test_catches_inline_interpolated_label_values(self):
        src = """
        from paddle_tpu.obs.metrics import registry as _obs_registry

        def admit(self, req):
            _reg = _obs_registry()
            _reg.counter(
                "reqs_total",
                {"req": f"r-{req.req_id}"}).inc()       # line 8: f-string
            _reg.histogram(
                "ttft_seconds",
                {"who": "tenant-" + req.tenant}).observe(0.1)  # line 11
            _obs_registry().counter(
                "by_step_total",
                {"step": "%d" % req.step}).inc()        # line 14
            _reg.gauge("depth", {"q": "{}".format(req.qid)}).set(1)  # 15
        """
        got = findings_for(src, "OBS002", path=self.PATH)
        assert lines_of(got) == [8, 11, 14, 15]
        assert all(f.severity == "warning" for f in got)
        assert "series" in got[0].message

    def test_catches_dynamic_metric_name(self):
        src = """
        def hook(reg, name):
            reg.counter(f"serving_{name}_total").inc()  # line 3
        """
        got = findings_for(src, "OBS002", path=self.PATH)
        assert lines_of(got) == [3]
        assert "metric NAME" in got[0].message

    def test_near_miss_bounded_values_stay_clean(self):
        # the sanctioned shapes: constants, plain variables, str(x),
        # dict-unpack of a prebuilt label set — the cardinality cap
        # governs these; only inline interpolation is the smell
        src = """
        def handles(self, tenant, pri):
            _reg.counter(
                "tenant_reqs_total",
                {**self._obs_labels, "tenant": str(tenant)}).inc()
            _reg.histogram("ttft_seconds",
                           {"priority": pri, "engine": "eng0"})
        """
        assert findings_for(src, "OBS002", path=self.PATH) == []

    def test_near_miss_outside_hot_paths_stays_clean(self):
        # same smell in a tool module: out of scope — one-shot scripts
        # may label however they like
        src = """
        def render(reg, run_id):
            reg.counter("runs_total", {"run": f"r{run_id}"}).inc()
        """
        assert findings_for(src, "OBS002",
                            path="paddle_tpu/tools/report.py") == []

    def test_near_miss_non_registry_receiver_stays_clean(self):
        # a .counter() on something that is not a registry alias
        src = """
        def tally(stats, key):
            stats.counter("hits", {"k": f"{key}"}).bump()
        """
        assert findings_for(src, "OBS002", path=self.PATH) == []

    def test_suppression_comment_works(self):
        src = """
        def handles(self, shard):
            _reg.gauge(
                "shard_depth",
                {"shard": f"s{shard}"}).set(0)  # graft-lint: disable=OBS002
        """
        assert findings_for(src, "OBS002", path=self.PATH) == []


class TestObs003:
    def test_catches_dynamic_series_reference(self):
        # seeded: the three constructors, three interpolation shapes —
        # the series a predicate resolves must be a literal name
        src = """
        from paddle_tpu.obs.alerts import (AbsenceRule, BurnRateRule,
                                           ThresholdRule)

        def rules_for(self, suffix, rep):
            return [
                ThresholdRule(
                    "queue_saturated",
                    f"serving_{suffix}", 0.95),             # line 9
                AbsenceRule("silent", source="rep-%d" % rep),  # line 10
                BurnRateRule(
                    "burn",
                    metric="serving_" + suffix),            # line 13
            ]
        """
        got = findings_for(src, "OBS003")
        assert lines_of(got) == [9, 10, 13]
        assert all(f.severity == "warning" for f in got)
        assert "literal name" in got[0].message

    def test_catches_format_call_via_kwarg(self):
        # seeded: .format() through the metric kwarg, nested in a loop
        src = """
        def build(self, tenants):
            out = []
            for t in tenants:
                out.append(ThresholdRule(
                    "t", metric="{}_queue".format(t), threshold=1))  # 6
            return out
        """
        got = findings_for(src, "OBS003")
        assert lines_of(got) == [6]
        assert ".format()" in got[0].message

    def test_near_miss_literals_and_variables_stay_clean(self):
        # literals are the point; a plain variable (e.g. the metric
        # loop in burn_rules_from_slo iterating a module-level tuple of
        # literals) is cap-governed and fix-at-source — not flagged.
        # The alert NAME may be dynamic: it's an identity, not a
        # series reference the predicate resolves.
        src = """
        def rules_for(self, metric, rep):
            return [
                ThresholdRule("queue_saturated",
                              "serving_queue_frac", 0.95),
                ThresholdRule(f"per_{metric}", metric, 1.0),
                AbsenceRule(f"silent_{rep}", source=None),
                BurnRateRule("burn", metric="serving_ttft_seconds"),
            ]
        """
        assert findings_for(src, "OBS003") == []


# ---------------------------------------------------------------------------
# Engine mechanics: suppressions, baseline, shared autograd-hazard core


class TestSuppressionsAndBaseline:
    SRC = """
    import jax

    @jax.jit
    def f(x):
        print(x)
        return x
    """

    def test_file_wide_suppression(self):
        src = "# graft-lint: disable=TRACE001\n" + textwrap.dedent(self.SRC)
        assert analyze_source(src, "s.py", select=["TRACE001"]) == []

    def test_line_scoped_suppression_only_hits_its_line(self):
        src = textwrap.dedent("""
        import time
        import jax

        @jax.jit
        def f(x):
            print(x)  # graft-lint: disable=TRACE001
            t = time.time()
            return x
        """)
        got = analyze_source(src, "s.py", select=["TRACE001"])
        assert lines_of(got) == [8]  # only the un-suppressed effect

    def test_baseline_absorbs_exactly_its_budget(self):
        src = textwrap.dedent(self.SRC)
        found = analyze_source(src, "pkg/mod.py", select=["TRACE001"])
        assert len(found) == 1
        entries = baseline_entries(found)
        assert entries == {"pkg/mod.py::TRACE001": 1}
        new, used = apply_baseline(found, entries)
        assert new == [] and used == 1
        # a SECOND violation exceeds the budget and surfaces
        src2 = src.replace("print(x)", "print(x)\n    print(x)")
        found2 = analyze_source(src2, "pkg/mod.py", select=["TRACE001"])
        new2, used2 = apply_baseline(found2, entries)
        assert used2 == 1 and len(new2) == 1

    def test_baseline_key_is_cwd_independent(self):
        src = textwrap.dedent(self.SRC)
        a = analyze_source(src, "paddle_tpu/x.py", select=["TRACE001"])
        b = analyze_source(
            src, "/somewhere/else/paddle_tpu/x.py", select=["TRACE001"])
        assert a[0].baseline_key() == b[0].baseline_key()

    def test_unknown_rule_select_raises(self):
        with pytest.raises(ValueError, match="NOPE999"):
            analyze_source("x = 1", "s.py", select=["NOPE999"])


class TestSharedAutogradHazardCore:
    def test_dy2static_is_a_client_of_the_analysis_core(self):
        """The piecewise splitter's hazard scan and the analyzer share
        ONE implementation (ISSUE 3 satellite)."""
        import ast

        from paddle_tpu.analysis.astutils import autograd_hazard
        from paddle_tpu.jit import dy2static

        for src, want in [
            ("optimizer.step()", True),
            ("loss.backward()", True),
            ("g = paddle.grad(loss, xs)", True),
            ("scheduler.step()", False),
            ("profiler.step()", False),
            ("node = y.grad_fn", False),
        ]:
            stmts = ast.parse(src).body
            assert autograd_hazard(stmts) is want, src
            assert dy2static._autograd_hazard(stmts) is want, src


# ---------------------------------------------------------------------------
# Self-lint gate + CLI


def test_self_lint():
    """paddle_tpu/ must produce ZERO findings at error severity beyond
    the committed baseline (the refactor-freely gate; the baseline is
    currently EMPTY — the package lints clean)."""
    findings = analyze_paths([PKG])
    new, _ = apply_baseline(
        findings, load_baseline(default_baseline_path()))
    errors = [f for f in new if f.severity == "error"]
    assert not errors, "\n".join(f.format() for f in errors)


class TestSelfLint:
    def test_cli_exits_zero_on_package(self):
        """The acceptance command: `python -m paddle_tpu.analysis
        paddle_tpu/` with the committed baseline exits 0 — the
        interprocedural pass (COLL002/COLL003/DDL002) is ON by
        default, so this also proves the graft-verify self-lint stays
        clean with an EMPTY baseline."""
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "paddle_tpu"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "graft-lint:" in proc.stdout

    def test_cli_interprocedural_explicit_flag_stays_clean(self):
        """`graft-lint --interprocedural` (the spelled-out acceptance
        form) over the package: zero new findings, empty baseline."""
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "paddle_tpu",
             "--interprocedural", "--no-baseline"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 new finding(s)" in proc.stdout

    def test_committed_baseline_is_empty(self):
        data = json.load(open(default_baseline_path()))
        assert data["entries"] == {}, (
            "the self-lint baseline must stay EMPTY: fix or "
            "suppress-with-reason anything the rules find in-tree")

    def test_cli_fails_on_seeded_violation(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            print(x)
            return x
        """))
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", str(bad),
             "--no-baseline"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 1
        assert "TRACE001" in proc.stdout

    def test_cli_json_and_list_rules(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", "--list-rules"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0
        for rid in ("TRACE001", "TRACE002", "RECOMP001", "COLL001",
                    "DDL001", "DONATE001"):
            assert rid in proc.stdout
        ok = tmp_path / "ok.py"
        ok.write_text("x = 1\n")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", str(ok),
             "--no-baseline", "--format", "json"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        data = json.loads(proc.stdout)
        assert data["findings"] == [] and data["gating"] == 0


class TestDeadlineThreading:
    def test_eager_recv_rejects_expired_deadline_before_blocking(self):
        """The DDL001 discipline threaded into the multi-controller p2p
        path: an already-expired deadline fails fast instead of
        entering the blocking KV get."""
        from paddle_tpu.distributed import multi_controller as mc
        from paddle_tpu.utils.retries import BudgetExceeded, Deadline

        clk = {"t": 0.0}
        dl = Deadline(1.0, clock=lambda: clk["t"])
        clk["t"] = 5.0  # budget lapses before the recv is attempted
        with pytest.raises(BudgetExceeded, match="eager_recv"):
            mc.eager_recv(src=0, deadline=dl)


# ---------------------------------------------------------------------------
# Runtime sanitizer: recompile_guard


class TestRecompileGuard:
    def test_counts_compiles_and_ignores_cache_hits(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.analysis import recompile_guard

        @jax.jit
        def guard_probe_fn(x):
            return x * 2 + 1

        with recompile_guard(match=r"^guard_probe_fn$") as g:
            guard_probe_fn(jnp.ones(3))
            guard_probe_fn(jnp.ones(3))   # cache hit
        assert g.count() == 1
        assert g.names() == ["guard_probe_fn"]
        assert "float32[3]" in g.events()[0].shapes

        # warmed: the same shape must not compile again
        with recompile_guard(max_compiles=0, match=r"^guard_probe_fn$"):
            guard_probe_fn(jnp.ones(3))

    def test_budget_violation_raises_with_events(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.analysis import RecompileError, recompile_guard

        @jax.jit
        def guard_probe_fn2(x):
            return x + 1

        guard_probe_fn2(jnp.ones(2))  # warm one shape
        with pytest.raises(RecompileError, match="guard_probe_fn2"):
            with recompile_guard(max_compiles=0,
                                 match=r"^guard_probe_fn2$"):
                guard_probe_fn2(jnp.ones(5))  # NEW shape: retrace

    def test_match_filter_scopes_the_budget(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.analysis import recompile_guard

        @jax.jit
        def noisy_neighbor(x):
            return x - 1

        # an unrelated compile inside the block must not trip a guard
        # scoped to another program's name
        with recompile_guard(max_compiles=0, match=r"^no_such_program$") \
                as g:
            noisy_neighbor(jnp.ones(7))
        assert g.count() == 0
        assert g.count(match=r"noisy") == 1

    def test_handler_detaches_on_exception_exit(self):
        """ISSUE 5 satellite: a failing guarded test must not leak the
        guard's logging handler (or the temporarily-lowered DEBUG
        level) into later tests — the restore runs in a finally."""
        import logging

        from paddle_tpu.analysis import recompile_guard
        from paddle_tpu.analysis.sanitizers import COMPILE_LOGGERS

        loggers = [logging.getLogger(n) for n in COMPILE_LOGGERS]
        before = [(lg.level, lg.propagate, list(lg.handlers))
                  for lg in loggers]
        with pytest.raises(RuntimeError, match="boom"):
            with recompile_guard(max_compiles=0):
                raise RuntimeError("boom")
        after = [(lg.level, lg.propagate, list(lg.handlers))
                 for lg in loggers]
        assert after == before, "guard leaked handlers/levels on an " \
                                "exception exit"

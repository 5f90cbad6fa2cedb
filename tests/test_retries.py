"""Deadline budgets + RetryPolicy (paddle_tpu/utils/retries.py) — the
shared fault-tolerance layer every blocking surface (bench supervisor,
TCP store, watchdog, elastic, serving) now consumes.

All timing runs on a ChaosClock, so expiry is exact and the tests take
no wall time.
"""
import pytest

from paddle_tpu.testing.chaos import ChaosClock
from paddle_tpu.utils.retries import (
    BudgetExceeded,
    Deadline,
    RetryPolicy,
    classify_text,
)


class TestDeadline:
    def test_remaining_and_expiry(self):
        clk = ChaosClock()
        d = Deadline(10.0, clock=clk)
        assert d.remaining() == 10.0 and not d.expired()
        clk.advance(4.0)
        assert d.remaining() == 6.0 and d.elapsed() == 4.0
        clk.advance(7.0)
        assert d.expired() and d.remaining() == 0.0
        with pytest.raises(BudgetExceeded):
            d.check("op")

    def test_unbounded_never_expires(self):
        clk = ChaosClock()
        d = Deadline.unbounded(clock=clk)
        clk.advance(1e9)
        assert not d.expired()
        assert d.remaining() == float("inf")
        assert d.timeout() is None          # block forever
        assert d.timeout(default=5.0) == 5.0  # caller's cap still applies
        assert d.fraction_consumed() == 0.0

    def test_sub_inherits_and_is_capped_by_parent(self):
        clk = ChaosClock()
        parent = Deadline(10.0, clock=clk)
        clk.advance(6.0)
        # asking for more than the parent has left clips to the parent
        child = parent.sub(seconds=100.0)
        assert child.budget == 4.0 and child.parent is parent
        # fraction splits the REMAINING budget, not the original
        half = parent.sub(fraction=0.5)
        assert half.budget == 2.0
        clk.advance(4.0)
        assert parent.expired() and child.expired() and half.expired()

    def test_timeout_clamps_for_socket_use(self):
        clk = ChaosClock()
        d = Deadline(10.0, clock=clk)
        assert d.timeout(default=3.0) == 3.0   # default smaller: wins
        clk.advance(8.0)
        assert d.timeout(default=3.0) == 2.0   # remaining smaller: wins
        clk.advance(5.0)
        assert d.timeout(default=3.0, floor=0.1) == 0.1

    def test_sleep_never_exceeds_remaining(self):
        clk = ChaosClock()
        d = Deadline(5.0, clock=clk)
        assert d.sleep(2.0) == 2.0
        assert clk.now() == 2.0            # chaos clock advanced, no real wait
        assert d.sleep(100.0) == 3.0       # clamped to the remaining budget
        assert d.expired()
        assert d.sleep(1.0) == 0.0

    def test_coerce(self):
        d = Deadline(5.0)
        assert Deadline.coerce(d) is d
        assert Deadline.coerce(None).budget is None
        assert Deadline.coerce(3).budget == 3.0

    def test_fraction_consumed_drives_ladders(self):
        clk = ChaosClock()
        d = Deadline(8.0, clock=clk)
        clk.advance(4.0)
        assert d.fraction_consumed() == 0.5
        clk.advance(2.0)
        assert d.fraction_consumed() == 0.75


class TestRetryPolicy:
    def test_transient_retries_then_succeeds(self):
        slept = []
        p = RetryPolicy(max_attempts=4, base_delay=1.0, multiplier=2.0,
                        sleep=slept.append)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionResetError("blip")
            return "ok"

        assert p.call(flaky) == "ok"
        assert len(calls) == 3
        assert slept == [1.0, 2.0]  # exponential, no jitter by default

    def test_fatal_propagates_immediately(self):
        p = RetryPolicy(max_attempts=5, base_delay=0.0)
        calls = []

        def broken():
            calls.append(1)
            raise ValueError("real bug")

        with pytest.raises(ValueError, match="real bug"):
            p.call(broken)
        assert len(calls) == 1  # no retry budget burned on a real error

    def test_exhaustion_reraises_last_transient(self):
        p = RetryPolicy(max_attempts=3, base_delay=0.0)
        with pytest.raises(ConnectionResetError):
            p.call(lambda: (_ for _ in ()).throw(ConnectionResetError("x")))

    def test_deadline_bounds_the_retry_loop(self):
        clk = ChaosClock()
        dl = Deadline(5.0, clock=clk)
        # base_delay 3: first retry sleeps 3 (ok), second would need 6
        # but only 2 remain — the loop stops at the budget, attempts
        # notwithstanding, and reports BudgetExceeded
        p = RetryPolicy(max_attempts=100, base_delay=3.0, multiplier=2.0,
                        sleep=clk.sleep)
        calls = []

        def always_down():
            calls.append(1)
            raise TimeoutError("down")

        with pytest.raises(BudgetExceeded):
            p.call(always_down, deadline=dl)
        assert dl.expired()
        assert len(calls) < 100  # the deadline, not max_attempts, stopped it

    def test_jitter_is_deterministic_under_seed(self):
        a = RetryPolicy(max_attempts=6, base_delay=1.0, jitter=0.5, seed=7)
        b = RetryPolicy(max_attempts=6, base_delay=1.0, jitter=0.5, seed=7)
        c = RetryPolicy(max_attempts=6, base_delay=1.0, jitter=0.5, seed=8)
        da, db, dc = list(a.delays()), list(b.delays()), list(c.delays())
        assert da == db
        assert da != dc

    def test_custom_classifier(self):
        p = RetryPolicy(max_attempts=2, base_delay=0.0,
                        transient=lambda e: "retry me" in str(e))
        calls = []

        def f():
            calls.append(1)
            raise RuntimeError("retry me" if len(calls) == 1 else "done")

        with pytest.raises(RuntimeError, match="done"):
            p.call(f)
        assert len(calls) == 2  # first was retried, second was fatal

    def test_max_delay_caps_backoff(self):
        p = RetryPolicy(max_attempts=10, base_delay=1.0, multiplier=10.0,
                        max_delay=5.0)
        assert max(p.delays()) == 5.0


class TestClassifyText:
    def test_shared_taxonomy(self):
        assert classify_text("Unable to initialize backend 'x'") == "transient"
        assert classify_text("connection reset by peer") == "transient"
        assert classify_text("UNAVAILABLE: channel closed") == "transient"
        # fatal override beats the transient init prefix it rides inside
        assert classify_text(
            "Unable to initialize backend 'x': 'x' is not in the list of "
            "known backends") == "fatal"
        assert classify_text("ValueError: shape mismatch") == "fatal"
        assert classify_text("") == "fatal"

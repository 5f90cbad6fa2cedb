"""Chaos fault-injection harness (paddle_tpu/testing/chaos.py) and the
recovery behaviour it exists to prove.

Covers: seeded schedules are reproducible; each fault kind fires
exactly where scheduled and is observable in the monkey's event log;
the TCP store's reconnect-with-backoff absorbs injected resets; a
dropped heartbeat really loses the beat; a mid-save kill leaves a torn
checkpoint that resume() skips; and the end-to-end recovery contract —
worker killed mid-training → elastic relaunch → auto-checkpoint resume
→ loss parity with an uninterrupted run.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.testing import chaos
from paddle_tpu.testing.chaos import ChaosClock, ChaosSchedule
from paddle_tpu.utils.retries import Deadline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_monkey():
    yield
    chaos.uninstall()


class TestSchedule:
    def test_explicit_plan_fires_exactly_where_scheduled(self):
        s = ChaosSchedule().at("site", 3, "reset").every("other", 2, "drop")
        hits = [s.fault_for("site", i) for i in range(1, 6)]
        assert [h.kind if h else None for h in hits] == [
            None, None, "reset", None, None]
        assert [s.fault_for("other", i) is not None
                for i in range(1, 7)] == [False, True] * 3

    def test_seeded_bernoulli_is_reproducible(self):
        a = ChaosSchedule(seed=42).with_probability("s", 0.3, "hang", 0.01)
        b = ChaosSchedule(seed=42).with_probability("s", 0.3, "hang", 0.01)
        c = ChaosSchedule(seed=43).with_probability("s", 0.3, "hang", 0.01)
        pa = [a.fault_for("s", i) is not None for i in range(1, 200)]
        pb = [b.fault_for("s", i) is not None for i in range(1, 200)]
        pc = [c.fault_for("s", i) is not None for i in range(1, 200)]
        assert pa == pb
        assert pa != pc
        assert 20 < sum(pa) < 100  # actually Bernoulli(0.3)-ish
        # draws depend only on (seed, site, index): query order is free
        assert a.fault_for("s", 150) == b.fault_for("s", 150)

    def test_spec_round_trip(self):
        s = (ChaosSchedule(seed=9)
             .at("store.request", 2, "reset")
             .every("elastic.heartbeat", 3, "drop")
             .with_probability("serving.step", 0.25, "slow", 0.01))
        r = ChaosSchedule.from_spec(s.to_spec())
        assert r.seed == 9
        for site, idx in (("store.request", 2), ("elastic.heartbeat", 6),
                          ("serving.step", 17)):
            assert r.fault_for(site, idx) == s.fault_for(site, idx)

    def test_bad_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            ChaosSchedule().at("s", 1, "explode")


class TestInjection:
    def test_reset_and_drop_and_counts(self):
        with chaos.active(ChaosSchedule()
                          .at("s", 2, "reset").at("s", 3, "drop")) as mk:
            assert chaos.inject("s") is True
            with pytest.raises(ConnectionResetError, match="chaos"):
                chaos.inject("s")
            assert chaos.inject("s") is False  # drop
            assert chaos.inject("s") is True
            assert mk.counts["s"] == 4
            assert mk.events == [("s", 2, "reset"), ("s", 3, "drop")]
        assert chaos.monkey() is None  # uninstalled on exit

    def test_hang_advances_the_chaos_clock_not_wall_time(self):
        clk = ChaosClock()
        with chaos.active(ChaosSchedule().at("s", 1, "hang", 3600.0),
                          clock=clk):
            chaos.inject("s")
        assert clk.now() == 3600.0  # a virtual hour, zero real seconds

    def test_uninstalled_is_a_noop(self):
        assert chaos.inject("anything") is True


class TestStoreChaos:
    def test_tcp_store_retries_through_injected_resets(self):
        from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer

        srv = TCPStoreServer(host="127.0.0.1")
        try:
            from paddle_tpu.utils.retries import RetryPolicy

            st = TCPKVStore("127.0.0.1", srv.port,
                            retry=RetryPolicy(max_attempts=4, base_delay=0.01,
                                              transient=TCPKVStore._is_transient))
            # request #2 (the get) is reset twice; the retry layer must
            # absorb both and still return the value
            with chaos.active(ChaosSchedule()
                              .at("store.request", 2, "reset")
                              .at("store.request", 3, "reset")) as mk:
                st.set("k", "v")                     # request 1: clean
                assert st.get("k") == "v"            # requests 2-4: retried
                assert [e[2] for e in mk.events] == ["reset", "reset"]
                assert mk.counts["store.request"] == 4
        finally:
            srv.stop()

    def test_wait_alive_waits_through_restart_and_times_out_when_dead(self):
        import socket as _socket
        import threading

        from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer

        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        st = TCPKVStore("127.0.0.1", port, timeout=2.0)
        # nothing listening: a bounded wait raises TimeoutError (not a
        # raw ConnectionRefusedError/ValueError leaking through)
        with pytest.raises(TimeoutError, match="not reachable"):
            st.wait_alive(deadline=Deadline(0.5))

        reborn = []
        t = threading.Timer(
            0.3, lambda: reborn.append(
                TCPStoreServer(host="127.0.0.1", port=port)))
        t.start()
        try:
            st.wait_alive(deadline=Deadline(10.0))  # returns once it's up
        finally:
            t.join()
            for srv in reborn:
                srv.stop()

    def test_dropped_request_is_a_lost_message_not_an_empty_reply(self):
        """A chaos 'drop' at store.request must look like a lost
        message (transient failure → retried), never a fabricated None
        response that wait_alive/dump would misread."""
        from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer
        from paddle_tpu.utils.retries import RetryPolicy

        srv = TCPStoreServer(host="127.0.0.1")
        try:
            st = TCPKVStore("127.0.0.1", srv.port,
                            retry=RetryPolicy(max_attempts=3, base_delay=0.01,
                                              transient=TCPKVStore._is_transient))
            with chaos.active(ChaosSchedule()
                              .at("store.request", 1, "drop")) as mk:
                st.set("k", "v")  # drop absorbed by retry, op still lands
                assert mk.events == [("store.request", 1, "drop")]
            assert st.get("k") == "v"
        finally:
            srv.stop()

    def test_retry_exhaustion_surfaces_the_reset(self):
        from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer
        from paddle_tpu.utils.retries import RetryPolicy

        srv = TCPStoreServer(host="127.0.0.1")
        try:
            st = TCPKVStore("127.0.0.1", srv.port,
                            retry=RetryPolicy(max_attempts=2, base_delay=0.01,
                                              transient=TCPKVStore._is_transient))
            with chaos.active(ChaosSchedule().every("store.request", 1,
                                                    "reset")):
                with pytest.raises(ConnectionError):
                    st.get("k")
        finally:
            srv.stop()

    def test_store_reconnects_after_real_server_restart(self):
        """Not just injected faults: kill the real server between ops;
        the store must ride its retry policy through the new server."""
        import socket as _socket

        from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer
        from paddle_tpu.utils.retries import RetryPolicy

        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        srv = TCPStoreServer(host="127.0.0.1", port=port)
        st = TCPKVStore("127.0.0.1", port, timeout=5.0,
                        retry=RetryPolicy(max_attempts=10, base_delay=0.05,
                                          transient=TCPKVStore._is_transient))
        st.set("a", "1")
        srv.stop()

        import threading

        reborn = []

        def restart():
            reborn.append(TCPStoreServer(host="127.0.0.1", port=port))

        t = threading.Timer(0.3, restart)
        t.start()
        try:
            # issued while the server is DOWN: retries until the
            # restarted server answers (fresh store: value is gone,
            # but the op succeeds instead of raising into the caller)
            assert st.get("a") is None
        finally:
            t.join()
            for s in reborn:
                s.stop()


class TestAddExactlyOnce:
    def test_replayed_add_rid_does_not_double_increment(self):
        """A retried 'add' whose first RESPONSE was lost must not
        double-increment: the server dedups on the request id and
        replays the cached result (rpc barriers count exact arrivals)."""
        from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer

        srv = TCPStoreServer(host="127.0.0.1")
        try:
            st = TCPKVStore("127.0.0.1", srv.port)
            assert st._req(op="add", k="ctr", amount=1, rid="r-1") == 1
            # the retry after a lost reply re-sends the SAME rid
            assert st._req(op="add", k="ctr", amount=1, rid="r-1") == 1
            assert st.get("ctr") == "1"
            assert st.add("ctr", 1) == 2  # fresh rid increments normally
        finally:
            srv.stop()

    def test_replayed_set_if_absent_rid_keeps_the_winner_winning(self):
        """Same lost-reply hazard for the claim op: the retried request
        replays True to the rightful winner instead of telling it the
        key (its own) is already taken."""
        from paddle_tpu.distributed.store import TCPKVStore, TCPStoreServer

        srv = TCPStoreServer(host="127.0.0.1")
        try:
            st = TCPKVStore("127.0.0.1", srv.port)
            assert st._req(op="set_if_absent", k="rank/0", v="alice",
                           rid="c-1") is True
            # the winner's retry after a lost reply: still True
            assert st._req(op="set_if_absent", k="rank/0", v="alice",
                           rid="c-1") is True
            # a genuine second claimant still loses
            assert st.set_if_absent("rank/0", "bob") is False
            assert st.get("rank/0") == "alice"
        finally:
            srv.stop()


class TestDroppedSaves:
    def test_dropped_write_saves_nothing(self, tmp_path):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
            AutoCheckpoint,
        )

        paddle.seed(3)
        m = nn.Linear(4, 2)
        ac = AutoCheckpoint(str(tmp_path), layers=[m],
                            save_interval_steps=1, async_save=False)
        with chaos.active(ChaosSchedule().at("ckpt.write", 1, "drop")):
            ac.save_now(1, block=True)
        assert os.listdir(str(tmp_path)) == []
        assert ac.resume() == 0

    def test_dropped_publish_leaves_torn_tmp_resume_skips(self, tmp_path):
        """'drop' at ckpt.publish abandons the save after the payload:
        same torn-tmp shape as a mid-save kill, provable in-process."""
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
            AutoCheckpoint,
        )

        paddle.seed(4)
        m = nn.Linear(4, 2)
        ac = AutoCheckpoint(str(tmp_path), layers=[m],
                            save_interval_steps=1, async_save=False)
        ac.save_now(1, block=True)
        with chaos.active(ChaosSchedule().at("ckpt.publish", 1, "drop")):
            ac.save_now(2, block=True)
        names = os.listdir(str(tmp_path))
        assert any(n.endswith(".tmp") for n in names), names
        assert ac.resume() == 2  # the step-1 checkpoint, not the torn 2


class TestElasticChaos:
    def test_dropped_heartbeat_loses_the_beat(self, tmp_path):
        from paddle_tpu.distributed.fleet.elastic import ElasticManager

        m = ElasticManager(str(tmp_path), node_id="n0", np=1,
                           heartbeat_interval=0.05, elastic_timeout=1.0)
        with chaos.active(ChaosSchedule().at("elastic.heartbeat", 2, "drop")):
            m._beat()  # lands
            v1 = m.store.get("nodes/n0")
            assert v1 is not None
            m._beat()  # dropped: the stored entry must not change
            assert m.store.get("nodes/n0") == v1
            m._beat()  # next beat lands again
            assert m.store.get("nodes/n0") != v1

    def test_register_honors_caller_deadline(self, tmp_path):
        from paddle_tpu.distributed.fleet.elastic import ElasticManager

        m = ElasticManager(str(tmp_path), node_id="solo", np=3,
                           heartbeat_interval=0.05, elastic_timeout=60.0)
        dl = Deadline(0.3)
        with pytest.raises(TimeoutError):
            m.register(deadline=dl)  # 0.3s, NOT the 60s elastic_timeout
        assert dl.expired()

    def test_watch_returns_on_deadline_with_membership_intact(self, tmp_path):
        from paddle_tpu.distributed.fleet.elastic import ElasticManager

        m = ElasticManager(str(tmp_path), node_id="n0", np=1,
                           heartbeat_interval=0.05, elastic_timeout=5.0)
        m.register()
        try:
            assert m.watch(deadline=Deadline(0.2)) == 0
        finally:
            m.exit()


class TestMidSaveKill:
    def test_kill_between_payload_and_publish_leaves_resumable_state(
            self, tmp_path):
        """A chaos 'kill' at ckpt.publish dies after the payload write
        but before the done marker: the torn tmp must be invisible to
        resume(), which falls back to the previous valid checkpoint."""
        script = (
            "import os\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import paddle_tpu as paddle\n"
            "import paddle_tpu.nn as nn\n"
            "from paddle_tpu.incubate.checkpoint.auto_checkpoint import "
            "AutoCheckpoint\n"
            "paddle.seed(0)\n"
            "m = nn.Linear(4, 2)\n"
            "ac = AutoCheckpoint(os.environ['CKPT_DIR'], layers=[m],\n"
            "                    save_interval_steps=1, async_save=False)\n"
            "ac.save_now(1, block=True)   # valid checkpoint\n"
            "ac.save_now(2, block=True)   # killed mid-save by chaos\n"
            "raise SystemExit('unreachable: chaos kill did not fire')\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""),
                   CKPT_DIR=str(tmp_path),
                   PADDLE_CHAOS="ckpt.publish@2=kill:9")
        p = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                           capture_output=True, text=True, timeout=240)
        assert p.returncode == 9, (p.returncode, p.stderr[-1500:])
        # the torn save exists on disk but has no done marker
        names = os.listdir(str(tmp_path))
        assert any(n.endswith(".tmp") for n in names), names
        assert not any(n == "ckpt-" + "2".zfill(12) for n in names)

        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
            AutoCheckpoint,
        )

        paddle.seed(0)
        m = nn.Linear(4, 2)
        ac = AutoCheckpoint(str(tmp_path), layers=[m], save_interval_steps=1)
        assert ac.resume() == 2  # step-1 checkpoint, NOT the torn step-2


class TestServingDeadlines:
    """Per-request deadlines in the continuous-batching engine. Lazily
    imports the engine (its module chain needs a Pallas-capable jax) and
    SKIPS — visibly, not via a hidden collection error — where that is
    unavailable, so the feature is exercised wherever it can run."""

    @pytest.fixture()
    def serving(self):
        try:
            from paddle_tpu.inference import serving as mod
            from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        except Exception as e:  # noqa: BLE001 — version-gated import chain
            pytest.skip(f"serving engine unavailable here: {e!r}")
        import paddle_tpu as paddle

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())

        def reference(prompt, max_new):
            from paddle_tpu.models.generation import generate

            ids = paddle.to_tensor(np.asarray(prompt, np.int64)[None])
            out = generate(model, ids, max_new_tokens=max_new,
                           use_jit=False)
            return list(np.asarray(out.numpy())[0][len(prompt):])

        return mod.ContinuousBatchingEngine, model, reference

    def test_expired_queue_request_is_rejected_at_admission(self, serving):
        """A request whose Deadline lapsed while queued must not burn a
        prefill: it surfaces as completed with status='expired' and no
        tokens."""
        Engine, model, reference = serving
        rng = np.random.RandomState(7)
        clk = ChaosClock()
        eng = Engine(model, max_batch=1, max_len=32, block_size=8,
                     num_blocks=4, prompt_pad=8)
        p = rng.randint(0, 250, (4,))
        eng.add_request("late", p, max_new_tokens=4,
                        deadline=Deadline(1.0, clock=clk))
        eng.add_request("ok", p, max_new_tokens=4)
        clk.advance(2.0)  # "late" expires before any engine step
        done = eng.run()
        assert done["late"].status == "expired"
        assert done["late"].out == []
        assert done["ok"].status == "ok"
        assert done["ok"].out == reference(p, 4)
        assert eng.manager.free_blocks == 4

    def test_expired_inflight_slot_is_evicted_and_blocks_recycle(
            self, serving):
        """One stuck/abandoned client cannot pin a slot: when its budget
        expires mid-decode the slot is evicted, its blocks recycle into
        the next admission, and the survivor's tokens stay exact."""
        Engine, model, reference = serving
        rng = np.random.RandomState(8)
        p_stuck = rng.randint(0, 250, (4,))
        p_live = rng.randint(0, 250, (5,))
        p_next = rng.randint(0, 250, (6,))
        clk = ChaosClock()

        # 4 blocks, 2 per request: "next" NEEDS the eviction to admit
        eng = Engine(model, max_batch=2, max_len=32, block_size=8,
                     num_blocks=4, prompt_pad=8)
        eng.add_request("stuck", p_stuck, max_new_tokens=12,
                        deadline=Deadline(1.0, clock=clk))
        eng.add_request("live", p_live, max_new_tokens=6)
        eng.add_request("next", p_next, max_new_tokens=5)

        eng.step()
        assert eng.num_active == 2  # stuck + live admitted, next waiting
        clk.advance(5.0)  # stuck's budget lapses mid-flight
        eng.step()
        assert eng._completed["stuck"].status == "expired"
        done = eng.run()
        assert set(done) == {"stuck", "live", "next"}
        assert done["live"].out == reference(p_live, 6)
        assert done["next"].out == reference(p_next, 5)
        assert done["next"].status == done["live"].status == "ok"
        assert eng.manager.free_blocks == 4


class TestEndToEndRelaunch:
    """The acceptance contract: kill mid-training via chaos → elastic
    relaunch → auto-checkpoint resume → final loss EQUALS the
    uninterrupted run's (same data schedule)."""

    def _run_worker(self, scratch, total, spec=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.pop("PADDLE_CHAOS", None)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["CHAOS_DIR"] = scratch
        env["CHAOS_TOTAL"] = str(total)
        if spec:
            env["PADDLE_CHAOS"] = spec
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "_chaos_worker.py")],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=240)

    @staticmethod
    def _final_loss(stdout):
        for line in stdout.splitlines():
            if "final_loss=" in line:
                return float(line.split("final_loss=")[1])
        return None

    @pytest.mark.slow   # 50-100 s of tier-1, on no cell's path (ROADMAP D14 a)
    def test_kill_relaunch_resume_loss_parity(self, tmp_path):
        total, kill_step = 14, 10

        ref = self._run_worker(str(tmp_path / "ref"), total)
        assert ref.returncode == 0, ref.stderr[-2000:]
        want = self._final_loss(ref.stdout)
        assert want is not None

        # wave 1: chaos kills the worker at step 10 (checkpoint at 8)
        scratch = str(tmp_path / "el")
        w1 = self._run_worker(
            scratch, total, spec=f"train.step@{kill_step}=kill:17")
        assert w1.returncode == 17, (w1.returncode, w1.stderr[-2000:])
        assert self._final_loss(w1.stdout) is None  # it really died mid-run

        # the relaunch agent (this test — the loop fleet.elastic/launch
        # implement) restarts the job; it resumes and completes
        w2 = self._run_worker(scratch, total)
        assert w2.returncode == 0, w2.stderr[-2000:]
        assert "resumed at step 9" in w2.stdout, w2.stdout
        got = self._final_loss(w2.stdout)
        assert got is not None
        np.testing.assert_allclose(got, want, rtol=1e-7)


class TestPodScaleSites:
    """The ISSUE 16 sites: ``train.kill_rank.<rank>`` (SIGKILL a NAMED
    rank at a scheduled executed step — the pod-scale one-worker-dies
    fault) and ``elastic.remesh`` (force a re-mesh decision with the
    membership intact)."""

    def test_kill_rank_spec_round_trips_and_targets_only_named_rank(self):
        s = (ChaosSchedule(seed=5)
             .at("train.kill_rank.1", 3, "kill")
             .at("elastic.remesh", 2, "drop"))
        r = ChaosSchedule.from_spec(s.to_spec())
        for site in ("train.kill_rank.0", "train.kill_rank.1",
                     "elastic.remesh"):
            for i in range(1, 6):
                a, b = s.fault_for(site, i), r.fault_for(site, i)
                assert (a is None) == (b is None)
                if a is not None:
                    assert (a.kind, a.arg) == (b.kind, b.arg)
        # the schedule names rank 1: rank 0's suffix never draws a fault
        assert all(s.fault_for("train.kill_rank.0", i) is None
                   for i in range(1, 20))
        hit = s.fault_for("train.kill_rank.1", 3)
        assert hit is not None and hit.kind == "kill"

    def test_supervisor_kill_rank_site_kills_exactly_the_named_rank(self):
        # a minimal supervised loop in a child per rank, sharing ONE
        # spec: rank 1 must die by SIGKILL at its 3rd executed step,
        # rank 0 must run to completion untouched
        prog = (
            "import os; os.environ.setdefault('JAX_PLATFORMS','cpu');\n"
            "import numpy as np\n"
            "from paddle_tpu.training.supervisor import TrainingSupervisor\n"
            "sup = TrainingSupervisor(lambda b: float(np.sum(b)),\n"
            "    lambda i: np.ones(2, np.float32) * (1 + 0.01 * i),\n"
            "    rank=int(os.environ['SUP_RANK']), snapshot_interval=100)\n"
            "sup.run(6)\n"
            "print('SUP_DONE step', sup.report()['final_step'])\n"
        )
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["PADDLE_CHAOS"] = "train.kill_rank.1@3=kill"
        out = {}
        for rank in (0, 1):
            env["SUP_RANK"] = str(rank)
            out[rank] = subprocess.run(
                [sys.executable, "-c", prog], env=env, cwd=REPO,
                capture_output=True, text=True, timeout=180)
        assert out[0].returncode == 0, out[0].stderr[-2000:]
        assert "SUP_DONE step 6" in out[0].stdout
        # rc < 0 is the genuine worker-death shape (SIGKILL)
        assert out[1].returncode < 0, (out[1].returncode,
                                       out[1].stderr[-2000:])
        assert "SUP_DONE" not in out[1].stdout

    def test_remesh_drop_forces_world_changed_and_latches_events(
            self, tmp_path):
        from paddle_tpu.distributed.fleet.elastic import ElasticManager

        m = ElasticManager(str(tmp_path), node_id="n0", np=1,
                           heartbeat_interval=0.05, elastic_timeout=5.0)
        m._beat()
        m._registered_world = m.alive_nodes()
        assert m.world_changed() is False
        assert m.remesh_events == 0
        with chaos.active(ChaosSchedule().at("elastic.remesh", 1, "drop")):
            assert m.world_changed() is True  # forced: membership intact
            assert m.remesh_events == 1
            assert m.world_changed() is False  # settles; latch resets
            assert m.remesh_events == 1
        # a REAL membership change counts once however often it is
        # re-polled (watch() asks every beat)
        m.store.delete("nodes/n0")
        assert m.world_changed() is True
        assert m.world_changed() is True
        assert m.remesh_events == 2
        assert m.health()["remesh_events"] == 2

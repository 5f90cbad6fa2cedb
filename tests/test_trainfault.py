"""Fault-tolerant training (paddle_tpu/training/): anomaly detection,
anomaly-triggered rollback with loss parity, batch quarantine,
peer-replicated in-memory snapshots, two-tier recovery order, and
cross-rank straggler/SDC telemetry. The 2-process kill -> peer-RAM
restore proof lives in TestTwoProcessKillPeerResume (slow lane, via
tests/_trainfault_worker.py)."""
import io
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as popt
from paddle_tpu.distributed.communication import flight_recorder as fr
from paddle_tpu.distributed.store import MemKVStore
from paddle_tpu.optimizer.lr import StepDecay
from paddle_tpu.testing import chaos
from paddle_tpu.testing.chaos import ChaosSchedule
from paddle_tpu.training import (
    AnomalyDetector,
    DataCursor,
    PeerReplicator,
    TrainingGaveUp,
    TrainingSupervisor,
    TrainTelemetry,
    pack_health,
    unpack_health,
)

pytestmark = pytest.mark.trainfault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    yield
    chaos.uninstall()
    fr.reset()


def make_rig(n_batches=64, poison_at=None, lr_sched=False, seed=0,
             data_seed=7):
    """A tiny deterministic training rig: (model, opt, scheds, batch_fn,
    step_fn). Identical (seed, data_seed) rigs are bit-identical dp
    replicas."""
    paddle.seed(seed)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    scheds = []
    if lr_sched:
        sched = StepDecay(learning_rate=1e-2, step_size=10)
        scheds.append(sched)
        lr = sched
    else:
        lr = 1e-2
    opt = popt.AdamW(learning_rate=lr, parameters=model.parameters())
    rng = np.random.RandomState(data_seed)
    data = [
        (rng.randn(8, 8).astype(np.float32),
         rng.randint(0, 4, (8,)).astype(np.int64))
        for _ in range(n_batches)
    ]
    if poison_at is not None:
        x, y = data[poison_at - 1]
        data[poison_at - 1] = (x * np.float32("nan"), y)

    def batch_fn(i):
        return data[(i - 1) % len(data)]

    def step_fn(batch):
        x = paddle.to_tensor(batch[0])
        y = paddle.to_tensor(batch[1])
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        for s in scheds:
            s.step()
        return loss

    return model, opt, scheds, batch_fn, step_fn


def make_sup(store=None, rank=0, world=1, tag="tf", **kw):
    model, opt, scheds, batch_fn, step_fn = make_rig(
        poison_at=kw.pop("poison_at", None),
        lr_sched=kw.pop("lr_sched", False))
    peer = PeerReplicator(store, rank, world, tag=tag) \
        if store is not None else None
    sup = TrainingSupervisor(
        step_fn, batch_fn, layers=[model], optimizers=[opt],
        lr_schedulers=scheds, snapshot_interval=kw.pop(
            "snapshot_interval", 5), peer=peer, **kw)
    return sup


class TestHealthWord:
    def test_pack_unpack_roundtrip(self):
        import jax.numpy as jnp

        word = pack_health(jnp.asarray(1.25), jnp.asarray(3.5))
        loss, gn, lfin, gfin = unpack_health(word)
        assert (loss, gn, lfin, gfin) == (1.25, 3.5, True, True)

    def test_nonfinite_flags_survive_the_f32_word(self):
        import jax.numpy as jnp

        word = pack_health(jnp.asarray(float("nan")),
                           jnp.asarray(float("inf")))
        loss, gn, lfin, gfin = unpack_health(word)
        assert not lfin and not gfin

    def test_packs_under_jit(self):
        import jax
        import jax.numpy as jnp

        word = jax.jit(lambda l, g: pack_health(l, g))(
            jnp.asarray(2.0), jnp.asarray(0.5))
        assert unpack_health(word)[:2] == (2.0, 0.5)

    def test_supervisor_parses_packed_word(self):
        """A step_fn returning pack_health() (the one-transfer jit
        idiom) drives the detector identically to a raw loss."""
        model, opt, _, batch_fn, step_fn = make_rig()

        def packed_step(batch):
            loss = step_fn(batch)
            return pack_health(loss._data)

        sup = TrainingSupervisor(packed_step, batch_fn, layers=[model],
                                 optimizers=[opt], snapshot_interval=5)
        rep = sup.run(12)
        assert rep["rollbacks"] == 0
        assert np.isfinite(rep["final_loss"])


class TestAnomalyDetector:
    def test_nonfinite_flags_immediately(self):
        det = AnomalyDetector()
        assert det.observe(float("nan")).kind == "loss_nonfinite"
        assert det.observe(1.0, float("inf")).kind == "grad_nonfinite"

    def test_spike_gate_trips_after_warmup_only(self):
        det = AnomalyDetector(warmup_steps=8, spike_k=8.0)
        # during warmup even a huge value just folds in
        assert det.observe(100.0) is None
        det2 = AnomalyDetector(warmup_steps=4, spike_k=8.0)
        for x in (1.0, 1.1, 0.9, 1.05, 0.95, 1.0):
            assert det2.observe(x) is None
        a = det2.observe(50.0)
        assert a is not None and a.kind == "loss_spike"

    def test_downward_moves_never_trip(self):
        det = AnomalyDetector(warmup_steps=4, spike_k=6.0)
        for x in (4.0, 3.5, 3.2, 3.0, 2.8):
            assert det.observe(x) is None
        assert det.observe(0.01) is None  # loss falling = training

    def test_anomalous_values_do_not_pollute_the_stats(self):
        det = AnomalyDetector(warmup_steps=4, spike_k=8.0)
        for x in (1.0, 1.1, 0.9, 1.0, 1.05):
            det.observe(x)
        mean_before = det.loss_gate.mean
        assert det.observe(500.0) is not None
        assert det.loss_gate.mean == mean_before  # spike not folded in
        assert det.observe(450.0) is not None     # still detected

    def test_small_upticks_below_relative_floor_pass(self):
        det = AnomalyDetector(warmup_steps=4, spike_k=6.0,
                              min_rel_spike=1.0)
        for x in (1.0, 1.0, 1.0, 1.0, 1.0, 1.0):
            assert det.observe(x) is None
        # MAD collapsed to ~0 on the plateau; a 10% uptick is many
        # "deviations" but under the relative floor — not an anomaly
        assert det.observe(1.1) is None
        assert det.observe(2.5) is not None  # 2.5x the level IS one

    def test_scaler_skip_run_is_an_anomaly(self):
        det = AnomalyDetector(max_consecutive_scaler_skips=2)
        for _ in range(3):
            det.notify_scaler_skip(0)
        a = det.observe(1.0)
        assert a is not None and a.kind == "scaler_skips"

    def test_healthy_observation_resets_the_skip_run(self):
        det = AnomalyDetector(max_consecutive_scaler_skips=2)
        det.notify_scaler_skip(0)
        det.notify_scaler_skip(1)
        assert det.observe(1.0) is None  # run of 2 == limit, not over
        det.notify_scaler_skip(2)
        assert det.observe(1.0) is None  # reset by the healthy step


class TestDataCursor:
    def test_identity_mapping_without_quarantine(self):
        c = DataCursor(lambda i: i)
        assert [c.batch(s) for s in (1, 2, 3)] == [1, 2, 3]

    def test_quarantine_shifts_only_later_steps(self):
        c = DataCursor(lambda i: i)
        c.quarantine(3)
        assert [c.index(s) for s in (1, 2, 3, 4)] == [1, 2, 4, 5]
        c.quarantine(5)
        assert [c.index(s) for s in (2, 3, 4)] == [2, 4, 6]

    def test_state_dict_roundtrip(self):
        c = DataCursor(lambda i: i)
        c.quarantine(7)
        c2 = DataCursor(lambda i: i)
        c2.set_state_dict(c.state_dict())
        assert c2.quarantined == [7]


class TestGradScalerSkipCounters:
    """Satellite: found_inf skips are observable (counters + callback)
    instead of silent."""

    def _inf_step(self, model, optimizer, scaler):
        x = paddle.to_tensor(np.full((2, 4), np.inf, np.float32))
        loss = model(x).sum()
        scaler.scale(loss).backward()
        scaler.step(optimizer)
        scaler.update()
        optimizer.clear_grad()

    def _clean_step(self, model, optimizer, scaler):
        loss = model(paddle.randn([2, 4])).sum()
        scaler.scale(loss).backward()
        scaler.step(optimizer)
        scaler.update()
        optimizer.clear_grad()

    def test_counters_and_callback(self):
        paddle.seed(0)
        model = nn.Linear(4, 4)
        optimizer = popt.SGD(learning_rate=0.1,
                             parameters=model.parameters())
        fired = []
        scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                       on_skip=fired.append)
        assert scaler.n_skipped_steps == 0
        assert scaler.last_skip_step == -1
        self._clean_step(model, optimizer, scaler)     # update 0: clean
        self._inf_step(model, optimizer, scaler)       # update 1: skip
        assert scaler.n_skipped_steps == 1
        assert scaler.last_skip_step == 1
        assert fired == [1]
        self._clean_step(model, optimizer, scaler)     # update 2: clean
        self._inf_step(model, optimizer, scaler)       # update 3: skip
        assert scaler.n_skipped_steps == 2
        assert scaler.last_skip_step == 3
        assert fired == [1, 3]

    def test_set_on_skip_feeds_a_detector(self):
        paddle.seed(0)
        model = nn.Linear(4, 4)
        optimizer = popt.SGD(learning_rate=0.1,
                             parameters=model.parameters())
        scaler = paddle.amp.GradScaler(init_loss_scaling=64.0)
        det = AnomalyDetector(max_consecutive_scaler_skips=1)
        scaler.set_on_skip(det.notify_scaler_skip)
        self._inf_step(model, optimizer, scaler)
        self._inf_step(model, optimizer, scaler)
        a = det.observe(1.0)
        assert a is not None and a.kind == "scaler_skips"


class TestRollback:
    def test_injected_nan_rolls_back_to_bitwise_loss_parity(self):
        clean = make_sup().run(30)
        assert clean["rollbacks"] == 0

        sup = make_sup()
        with chaos.active(ChaosSchedule().at("train.nan", 17, "drop")):
            rep = sup.run(30)
        assert rep["rollbacks"] == 1
        assert rep["anomalies"][0][1].startswith("loss_nonfinite")
        # deterministic replay: the recovered run IS the clean run
        assert rep["final_loss"] == clean["final_loss"]

    def test_injected_spike_trips_the_ewma_gate_and_recovers(self):
        clean = make_sup().run(30)
        sup = make_sup()
        with chaos.active(ChaosSchedule().at("train.spike", 20, "drop")):
            rep = sup.run(30)
        assert rep["rollbacks"] >= 1
        assert any("spike" in a[1] for a in rep["anomalies"])
        assert rep["final_loss"] == clean["final_loss"]

    def test_rollback_restores_optimizer_moments_and_lr_scheduler(self):
        clean = make_sup(lr_sched=True).run(30)
        sup = make_sup(lr_sched=True)
        with chaos.active(ChaosSchedule().at("train.nan", 12, "drop")):
            rep = sup.run(30)
        assert rep["rollbacks"] == 1
        # AdamW moments + LR schedule position replay exactly: any
        # drift would show in the final loss bits
        assert rep["final_loss"] == clean["final_loss"]
        # the schedule advanced exactly total_steps times net of replay
        assert sup.lr_schedulers[0].last_epoch == 30

    def test_poison_batch_quarantined_after_retries(self):
        sup = make_sup(poison_at=17)
        rep = sup.run(30)
        assert rep["quarantined"] == [17]
        assert rep["rollbacks"] == 3  # max_rollback_retries=2, then cut
        assert np.isfinite(rep["final_loss"])

    def test_rollback_budget_exhaustion_raises(self):
        sup = make_sup(poison_at=17, max_rollback_retries=100,
                       rollback_budget=3)
        with pytest.raises(TrainingGaveUp, match="budget exhausted"):
            sup.run(30)

    def test_anomaly_before_any_snapshot_is_fatal_not_silent(self):
        from paddle_tpu.training.anomaly import Anomaly

        model, opt, _, batch_fn, step_fn = make_rig()
        sup = TrainingSupervisor(step_fn, batch_fn, layers=[model],
                                 optimizers=[opt])
        # a caller bypassing run()'s step-0 snapshot must get a loud
        # failure, never a silent continue on poisoned state
        with pytest.raises(TrainingGaveUp, match="nothing to roll"):
            sup._handle_anomaly(1, Anomaly("loss_nonfinite"))


class TestReviewHardening:
    """Regressions for the review findings on the first cut."""

    def test_scaler_skip_anomaly_does_not_latch(self):
        # one transient skip-run must cost ONE anomaly, not the whole
        # rollback budget: the counter resets when flagged
        det = AnomalyDetector(max_consecutive_scaler_skips=2)
        for _ in range(5):
            det.notify_scaler_skip(0)
        assert det.observe(1.0).kind == "scaler_skips"
        assert det.observe(1.0) is None  # replayed step: clean

    def test_two_poison_batches_both_quarantined(self):
        # a later rollback restoring a pre-quarantine snapshot must not
        # forget the first quarantine (union, not replace)
        model, opt, _, batch_fn0, step_fn = make_rig()
        rng = np.random.RandomState(7)
        data = [(rng.randn(8, 8).astype(np.float32),
                 rng.randint(0, 4, (8,)).astype(np.int64))
                for _ in range(64)]
        for bad in (17, 19):
            x, y = data[bad - 1]
            data[bad - 1] = (x * np.float32("nan"), y)

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                              nn.Linear(16, 4))
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters())

        def step_fn(batch):
            x, y = paddle.to_tensor(batch[0]), paddle.to_tensor(batch[1])
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        sup = TrainingSupervisor(
            step_fn, lambda i: data[(i - 1) % len(data)],
            layers=[model], optimizers=[opt], snapshot_interval=10,
            rollback_budget=12)
        rep = sup.run(30)
        assert rep["quarantined"] == [17, 19], rep
        assert np.isfinite(rep["final_loss"])

    def test_stale_peer_replica_loses_to_fresher_disk(self, tmp_path):
        # fetch() falling back to an OLDER verified replica must not
        # shadow a fresher verified disk checkpoint
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
            AutoCheckpoint,
        )

        store = MemKVStore()

        def rig():
            model, opt, _, batch_fn, step_fn = make_rig()
            ac = AutoCheckpoint(str(tmp_path), layers=[model],
                                optimizers=[opt], save_interval_steps=10,
                                async_save=False)
            return TrainingSupervisor(
                step_fn, batch_fn, layers=[model], optimizers=[opt],
                snapshot_interval=5,
                peer=PeerReplicator(store, 0, 1, tag="stale", keep=2),
                auto_checkpoint=ac)

        ref = make_sup().run(30)
        sup = rig()
        sup.run(20)  # peer at 5..20, disk at 10+20
        sup.peer.wait()
        # vandalize ONLY the step-20 peer payload: fetch falls back to
        # step 15, which is OLDER than the verified disk step 20
        store.set("stale/snap/0/data/20", "garbage")
        sup2 = rig()
        assert sup2.resume() == 21
        assert any(k == "resume" and "disk" in d for k, d in sup2.events)
        rep = sup2.run(30)
        assert rep["final_loss"] == ref["final_loss"]

    def test_pack_health_loss_only_has_no_fingerprintable_grad(self):
        import jax.numpy as jnp

        _, gn, _, _ = unpack_health(pack_health(jnp.asarray(1.0)))
        assert gn is None  # not a fake 0.0 that freezes SDC detection
        _, gn2, _, _ = unpack_health(
            pack_health(jnp.asarray(1.0), jnp.asarray(0.0)))
        assert gn2 == 0.0  # a REAL zero norm survives

    def test_misaligned_peer_interval_rejected(self):
        with pytest.raises(ValueError, match="multiple of"):
            make_sup(snapshot_interval=10, peer_interval=3,
                     store=MemKVStore())

    def test_async_disk_save_survives_donated_compiled_state(
            self, tmp_path):
        # the disk tier's ASYNC capture races the donated buffers the
        # RAM tier copies around — the supervisor aligns copy_capture
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
            AutoCheckpoint,
        )

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                              nn.Linear(16, 4))
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters())
        rng = np.random.RandomState(7)
        data = [(rng.randn(8, 8).astype(np.float32),
                 rng.randint(0, 4, (8,)).astype(np.int64))
                for _ in range(32)]

        def body(x, y):
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        compiled = paddle.jit.to_static(body, layers=[model],
                                        optimizers=[opt])
        ac = AutoCheckpoint(str(tmp_path), layers=[model],
                            optimizers=[opt], save_interval_steps=3,
                            async_save=True)
        sup = TrainingSupervisor(
            lambda b: compiled(paddle.to_tensor(b[0]),
                               paddle.to_tensor(b[1])),
            lambda i: data[(i - 1) % len(data)],
            layers=[model], optimizers=[opt], snapshot_interval=5,
            auto_checkpoint=ac)
        assert ac.copy_capture  # aligned by the supervisor
        rep = sup.run(12)  # async saves interleave with donating steps
        assert np.isfinite(rep["final_loss"])
        assert ac.latest_step() == 12

    def test_telemetry_close_unregisters_dump_extra(self):
        store = MemKVStore()
        t = TrainTelemetry(store, 0, 2, tag="close",
                           straggler_patience=1, straggler_factor=1.5)
        t._stragglers = [1]
        buf = io.StringIO()
        fr.dump_on_watchdog(buf)
        assert "PERSISTENT straggler" in buf.getvalue()
        t.close()
        buf2 = io.StringIO()
        fr.dump_on_watchdog(buf2)
        assert "PERSISTENT straggler" not in buf2.getvalue()


class TestCompiledStepRollback:
    """Rollback under jit.to_static with donate_state=True (the
    default): the compiled step DONATES the old param/moment buffers,
    so snapshots must device-copy (copy_snapshots=True default) — a
    reference capture would restore deleted tombstones."""

    def _rig(self, copy_snapshots=True):
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                              nn.Linear(16, 4))
        opt = popt.AdamW(learning_rate=1e-2,
                         parameters=model.parameters())
        rng = np.random.RandomState(7)
        data = [(rng.randn(8, 8).astype(np.float32),
                 rng.randint(0, 4, (8,)).astype(np.int64))
                for _ in range(64)]

        def body(x, y):
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        compiled = paddle.jit.to_static(body, layers=[model],
                                        optimizers=[opt])

        def step_fn(batch):
            return compiled(paddle.to_tensor(batch[0]),
                            paddle.to_tensor(batch[1]))

        return TrainingSupervisor(
            step_fn, lambda i: data[(i - 1) % len(data)],
            layers=[model], optimizers=[opt], snapshot_interval=5,
            copy_snapshots=copy_snapshots)

    def test_nan_rollback_parity_with_donated_compiled_state(self):
        clean = self._rig().run(20)
        assert clean["rollbacks"] == 0
        sup = self._rig()
        with chaos.active(ChaosSchedule().at("train.nan", 12, "drop")):
            rep = sup.run(20)
        assert rep["rollbacks"] == 1
        assert rep["final_loss"] == clean["final_loss"]


class TestPeerSnapshot:
    def test_publish_fetch_roundtrip(self):
        store = MemKVStore()
        rep = PeerReplicator(store, 0, 2, tag="t1")
        rep.publish(10, b"payload-10", block=True)
        assert rep.peer == 1
        assert rep.latest_step() == 10
        assert rep.fetch() == (10, b"payload-10")

    def test_newest_wins_and_prune_keeps_a_fallback(self):
        store = MemKVStore()
        rep = PeerReplicator(store, 0, 2, tag="t2", keep=1)
        for s in (5, 10, 15):
            rep.publish(s, f"p{s}".encode(), block=True)
        assert rep.fetch() == (15, b"p15")
        keys = store.keys("t2/snap/0/data/")
        assert len(keys) == 2  # newest + one fallback

    def test_dropped_meta_leg_leaves_previous_publish_current(self):
        store = MemKVStore()
        rep = PeerReplicator(store, 0, 2, tag="t3")
        rep.publish(5, b"p5", block=True)
        # fault leg 2 of the second publish (the meta put): data lands,
        # commit doesn't — the torn publish must be invisible
        with chaos.active(ChaosSchedule().at("ckpt.peer", 2, "drop")):
            rep.publish(10, b"p10", block=True)
        assert rep.latest_step() == 5
        assert rep.fetch() == (5, b"p5")

    def test_corrupt_payload_fails_crc_and_falls_back(self):
        store = MemKVStore()
        rep = PeerReplicator(store, 0, 2, tag="t4", keep=1)
        rep.publish(5, b"good-payload", block=True)
        with chaos.active(ChaosSchedule().at("ckpt.peer", 1, "corrupt",
                                             17)):
            rep.publish(10, b"bit-flipped-en-route", block=True)
        # newest payload is provably corrupt (CRC frame): fetch returns
        # the older intact replica instead of garbage
        assert rep.fetch() == (5, b"good-payload")

    def test_dropped_data_leg_loses_the_whole_publish(self):
        store = MemKVStore()
        rep = PeerReplicator(store, 0, 2, tag="t5")
        rep.publish(5, b"p5", block=True)
        with chaos.active(ChaosSchedule().at("ckpt.peer", 1, "drop")):
            rep.publish(10, b"p10", block=True)
        assert rep.fetch() == (5, b"p5")


class TestTwoTierRecovery:
    def _disk(self, tmp_path, sup_kw=None):
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
            AutoCheckpoint,
        )

        model, opt, scheds, batch_fn, step_fn = make_rig()
        ac = AutoCheckpoint(str(tmp_path), layers=[model],
                            optimizers=[opt], save_interval_steps=10,
                            async_save=False)
        sup = TrainingSupervisor(
            step_fn, batch_fn, layers=[model], optimizers=[opt],
            snapshot_interval=5, auto_checkpoint=ac, **(sup_kw or {}))
        return sup

    def test_resume_prefers_fresher_peer_ram_over_disk(self, tmp_path):
        ref = make_sup().run(30)

        store = MemKVStore()
        model, opt, _, batch_fn, step_fn = make_rig()
        from paddle_tpu.incubate.checkpoint.auto_checkpoint import (
            AutoCheckpoint,
        )

        ac = AutoCheckpoint(str(tmp_path), layers=[model],
                            optimizers=[opt], save_interval_steps=10,
                            async_save=False)
        sup = TrainingSupervisor(
            step_fn, batch_fn, layers=[model], optimizers=[opt],
            snapshot_interval=5, peer=PeerReplicator(store, 0, 1,
                                                     tag="two"),
            auto_checkpoint=ac)
        sup.run(20)   # disk at 10+20, peer at 5/10/15/20
        sup.peer.wait()

        # relaunch: peer tier (step 20) ties disk (step 20) — RAM wins
        model2, opt2, _, batch_fn2, step_fn2 = make_rig()
        ac2 = AutoCheckpoint(str(tmp_path), layers=[model2],
                             optimizers=[opt2], save_interval_steps=10,
                             async_save=False)
        sup2 = TrainingSupervisor(
            step_fn2, batch_fn2, layers=[model2], optimizers=[opt2],
            snapshot_interval=5, peer=PeerReplicator(store, 0, 1,
                                                     tag="two"),
            auto_checkpoint=ac2)
        assert sup2.resume() == 21
        assert any(k == "resume" and "peer RAM" in d
                   for k, d in sup2.events)
        rep = sup2.run(30)
        assert rep["final_loss"] == ref["final_loss"]

    def test_corrupt_peer_tier_falls_back_to_disk(self, tmp_path):
        ref = make_sup().run(30)
        store = MemKVStore()
        sup = self._disk(tmp_path)
        peer = PeerReplicator(store, 0, 1, tag="corrupt")
        sup.peer = peer
        sup.run(20)
        peer.wait()
        # vandalize EVERY peer payload: resume must verify, reject, and
        # restore from disk (step 20) instead of crashing or loading junk
        for key in store.keys("corrupt/snap/0/data/"):
            store.set(key, "not-a-valid-frame")
        sup2 = self._disk(tmp_path)
        sup2.peer = PeerReplicator(store, 0, 1, tag="corrupt")
        assert sup2.resume() == 21
        assert any(k == "resume" and "disk" in d for k, d in sup2.events)
        rep = sup2.run(30)
        assert rep["final_loss"] == ref["final_loss"]

    def test_fresh_start_when_no_tier_exists(self, tmp_path):
        sup = self._disk(tmp_path)
        assert sup.resume() == 1


class TestTelemetry:
    def test_two_replica_sdc_detected_and_healed_with_parity(self):
        store = MemKVStore()

        def build(rank):
            model, opt, _, batch_fn, step_fn = make_rig()
            tele = TrainTelemetry(store, rank, 2, tag="sdc",
                                  straggler_patience=10_000)
            return TrainingSupervisor(
                step_fn, batch_fn, layers=[model], optimizers=[opt],
                snapshot_interval=5, telemetry=tele)

        clean = make_sup().run(20)
        s0, s1 = build(0), build(1)
        for step in range(1, 21):
            s0.run(step)
            if step == 12:
                with chaos.active(ChaosSchedule().at("train.sdc", 1,
                                                     "drop")):
                    s1.run(step)
            else:
                s1.run(step)
        assert s1.rollbacks == 1
        assert any("sdc" in a[1] for a in s1.anomalies)
        assert s0.report()["final_loss"] == clean["final_loss"]
        assert s1.report()["final_loss"] == clean["final_loss"]

    def test_majority_attribution_with_three_replicas(self):
        store = MemKVStore()
        t0 = TrainTelemetry(store, 0, 3, tag="maj")
        t1 = TrainTelemetry(store, 1, 3, tag="maj")
        t2 = TrainTelemetry(store, 2, 3, tag="maj")
        t0.publish(7, 0.1, "aaaa")
        t1.publish(7, 0.1, "bbbb")   # the corrupted minority
        t2.publish(7, 0.1, "aaaa")
        v = t0.check(7, "aaaa")
        assert v.sdc_suspects == [1]
        v1 = t1.check(7, "bbbb")
        assert v1.sdc_suspects == [1]  # every rank names the same rank

    def test_persistent_straggler_named_and_dumped(self):
        store = MemKVStore()
        fast = TrainTelemetry(store, 0, 2, tag="strag",
                              straggler_factor=2.0, straggler_patience=3)
        slow = TrainTelemetry(store, 1, 2, tag="strag")
        for step in range(1, 8):
            fast.publish(step, 0.01, "x")
            slow.publish(step, 0.2, "x")
            fast.check(step)
        assert fast.stragglers() == [1]
        # the watchdog dump names the straggling rank via the
        # flight-recorder dump-extra hook
        buf = io.StringIO()
        fr.dump_on_watchdog(buf)
        out = buf.getvalue()
        assert "PERSISTENT straggler" in out and "[1]" in out
        # and the per-step train_step beacons are in the ring itself
        assert "train_step" in out

    def test_lockstep_wait_bounded_when_peer_dead(self):
        store = MemKVStore()
        t = TrainTelemetry(store, 0, 2, tag="dead", lockstep=True,
                           lockstep_deadline_s=0.2)
        t.publish(3, 0.01, "x")
        v = t.check(3, "x")  # peer never publishes: bounded, no SDC
        assert not v.sdc

    def test_telemetry_store_outage_never_raises(self):
        from paddle_tpu.distributed.store import TCPKVStore
        from paddle_tpu.utils.retries import RetryPolicy

        # nothing listening on the port: publish/check absorb it
        t = TrainTelemetry(
            TCPKVStore("127.0.0.1", 1, timeout=0.2,
                       retry=RetryPolicy(max_attempts=1, base_delay=0.01,
                                         transient=(OSError, ValueError))),
            0, 2, tag="out", deadline_s=0.3)
        t.publish(1, 0.01, "x")
        v = t.check(1, "x")
        assert v.peers_seen == []


@pytest.mark.slow
class TestTwoProcessKillPeerResume:
    """The e2e acceptance proof: 2 real processes over a TCPKVStore,
    seeded chaos injecting a NaN step on rank 0 AND killing rank 1
    mid-run; the relaunched rank 1 resumes from its peer-RAM snapshot
    WITHOUT a disk tier configured, and both ranks finish with the
    final loss of an uninjected run."""

    def _spawn(self, rank, store_addr, total, tag, spec=None, env_extra=()):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.pop("PADDLE_CHAOS", None)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update({"TF_STORE": store_addr, "TF_RANK": str(rank),
                    "TF_WORLD": "2", "TF_TOTAL": str(total),
                    "TF_TAG": tag})
        env.update(dict(env_extra))
        if spec:
            env["PADDLE_CHAOS"] = spec
        return subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "tests", "_trainfault_worker.py")],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)

    @staticmethod
    def _finish(proc, timeout=240):
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out, err

    @staticmethod
    def _final_loss(stdout):
        for line in stdout.splitlines():
            if "final_loss=" in line:
                return float(line.split("final_loss=")[1].split()[0])
        return None

    def test_nan_plus_kill_recovers_to_clean_loss(self):
        from paddle_tpu.distributed.store import TCPStoreServer

        srv = TCPStoreServer(host="127.0.0.1")
        addr = f"127.0.0.1:{srv.port}"
        total = 24
        try:
            # clean wave
            p0 = self._spawn(0, addr, total, "clean")
            p1 = self._spawn(1, addr, total, "clean")
            rc0, o0, e0 = self._finish(p0)
            rc1, o1, e1 = self._finish(p1)
            assert rc0 == 0, e0[-2000:]
            assert rc1 == 0, e1[-2000:]
            want = self._final_loss(o0)
            assert want is not None and want == self._final_loss(o1)

            # fault wave: NaN on rank 0 at step 8; rank 1 killed at
            # step 14 (after the step-10 peer snapshot)
            p0 = self._spawn(0, addr, total, "fault",
                             spec="train.nan@8=drop")
            p1 = self._spawn(1, addr, total, "fault",
                             spec="train.step@14=kill:19")
            rc1, o1, e1 = self._finish(p1)
            assert rc1 == 19, (rc1, e1[-2000:])
            assert self._final_loss(o1) is None  # really died mid-run

            # relaunch rank 1 (no chaos): peer-RAM restore, no disk tier
            p1b = self._spawn(1, addr, total, "fault")
            rc1b, o1b, e1b = self._finish(p1b)
            rc0, o0, e0 = self._finish(p0)
            assert rc0 == 0, e0[-2000:]
            assert rc1b == 0, e1b[-2000:]
            assert "resumed step=" in o1b and "tier=peer" in o1b, o1b
            got0, got1 = self._final_loss(o0), self._final_loss(o1b)
            # rollback exercised on rank 0, peer-RAM restore on rank 1,
            # and BOTH land on the uninjected run's loss
            assert "rollbacks=1" in o0
            np.testing.assert_allclose(got0, want, rtol=0, atol=0)
            np.testing.assert_allclose(got1, want, rtol=0, atol=0)
        finally:
            srv.stop()

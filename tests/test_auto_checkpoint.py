"""Fault-tolerant auto-checkpointing (ref: base/incubate/checkpoint/
auto_checkpoint.py:70,615): periodic async saves, keep-last-k pruning,
resume from the newest VALID checkpoint, and a kill-and-relaunch test
that resumes within one save interval."""
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.incubate.checkpoint import AutoCheckpoint

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make(tmp_path, **kw):
    paddle.seed(0)
    model = nn.Linear(4, 3)
    optimizer = opt.AdamW(learning_rate=0.01, parameters=model.parameters())
    ac = AutoCheckpoint(str(tmp_path), layers=[model],
                        optimizers=[optimizer], **kw)
    return model, optimizer, ac


def _train_steps(model, optimizer, ac, start, n):
    rng = np.random.RandomState(7)
    losses = []
    for step in range(start, start + n):
        x = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
        y = paddle.to_tensor(rng.randint(0, 3, (8,)).astype(np.int64))
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        losses.append(float(loss))
        ac.step(step)
    return losses


class TestAutoCheckpoint:
    def test_interval_save_and_resume(self, tmp_path):
        model, optimizer, ac = _make(tmp_path, save_interval_steps=5,
                                     async_save=False)
        assert ac.resume() == 0  # fresh start
        _train_steps(model, optimizer, ac, 0, 12)
        # steps 5 and 10 saved
        steps = [s for s, _ in ac._list_ckpts()]
        assert steps == [5, 10]

        model2, optimizer2, ac2 = _make(tmp_path, save_interval_steps=5,
                                        async_save=False)
        start = ac2.resume()
        assert start == 11  # newest valid ckpt step + 1
        w_saved = np.asarray(model.weight._data)
        # weights at resume differ from the step-11 weights of the
        # original run (we rewound to step 10's state)... so compare
        # against a fresh run replayed to step 10
        model3, optimizer3, ac3 = _make(tmp_path / "b", save_interval_steps=999,
                                        async_save=False)
        _train_steps(model3, optimizer3, ac3, 0, 11)  # steps 0..10
        np.testing.assert_allclose(
            np.asarray(model2.weight._data),
            np.asarray(model3.weight._data), rtol=1e-6)

    def test_keep_last_k_prunes(self, tmp_path):
        model, optimizer, ac = _make(tmp_path, save_interval_steps=2,
                                     keep_last_k=2, async_save=False)
        _train_steps(model, optimizer, ac, 0, 11)
        steps = [s for s, _ in ac._list_ckpts()]
        assert steps == [8, 10]

    def test_async_save_drains(self, tmp_path):
        model, optimizer, ac = _make(tmp_path, save_interval_steps=3,
                                     async_save=True)
        _train_steps(model, optimizer, ac, 0, 7)
        ac.wait()
        steps = [s for s, _ in ac._list_ckpts()]
        assert 3 in steps and 6 in steps

    def test_torn_checkpoint_skipped(self, tmp_path):
        model, optimizer, ac = _make(tmp_path, save_interval_steps=4,
                                     async_save=False)
        _train_steps(model, optimizer, ac, 0, 9)
        # corrupt the newest checkpoint: remove its done marker
        newest = ac._list_ckpts()[-1][1]
        os.remove(os.path.join(newest, "meta.json"))
        model2, optimizer2, ac2 = _make(tmp_path, save_interval_steps=4,
                                        async_save=False)
        assert ac2.resume() == 5  # fell back to ckpt-4

    @pytest.mark.robustness
    def test_truncated_payload_quarantined_resume_falls_back(self, tmp_path):
        """ISSUE 4 satellite: a checkpoint whose PAYLOAD was truncated
        after publish (torn flush / disk fault — the shape a chaos kill
        mid-fsync leaves) fails its CRC32 at resume, is quarantined as
        ``*.corrupt``, and resume falls back to the newest valid one
        instead of crashing mid-restore."""
        model, optimizer, ac = _make(tmp_path, save_interval_steps=1,
                                     async_save=False)
        _train_steps(model, optimizer, ac, 0, 3)  # ckpt-1 and ckpt-2
        newest = ac._list_ckpts()[-1][1]
        payload = os.path.join(newest, "state.pdparams")
        data = open(payload, "rb").read()
        with open(payload, "wb") as f:
            f.write(data[: len(data) // 2])  # torn tail

        model2, optimizer2, ac2 = _make(tmp_path, save_interval_steps=1,
                                        async_save=False)
        assert ac2.resume() == 2  # ckpt-1, NOT the corrupt ckpt-2
        names = os.listdir(str(tmp_path))
        assert any(n.endswith(".corrupt") for n in names), names
        # quarantine is idempotent: a second resume still succeeds and
        # never rescans the corrupt directory
        model3, optimizer3, ac3 = _make(tmp_path, save_interval_steps=1,
                                        async_save=False)
        assert ac3.resume() == 2
        # the restored weights equal a clean replay through step 1
        model4, optimizer4, ac4 = _make(tmp_path / "replay",
                                        save_interval_steps=999,
                                        async_save=False)
        _train_steps(model4, optimizer4, ac4, 0, 2)
        np.testing.assert_allclose(np.asarray(model3.weight._data),
                                   np.asarray(model4.weight._data),
                                   rtol=1e-6)

    @pytest.mark.robustness
    def test_crc_recorded_and_verified(self, tmp_path):
        """Every published checkpoint records a CRC32 + byte count; a
        bit flip (same length) also fails verification."""
        import json

        model, optimizer, ac = _make(tmp_path, save_interval_steps=1,
                                     async_save=False)
        _train_steps(model, optimizer, ac, 0, 2)
        step, path = ac._list_ckpts()[-1]
        meta = json.load(open(os.path.join(path, "meta.json")))
        assert "crc32" in meta and "payload_bytes" in meta
        assert ac._verify(path)
        payload = os.path.join(path, "state.pdparams")
        raw = bytearray(open(payload, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        with open(payload, "wb") as f:
            f.write(bytes(raw))
        assert not ac._verify(path)

    def test_extra_state_roundtrip(self, tmp_path):
        holder = {"lr_step": 42}
        model, optimizer, ac = _make(
            tmp_path, save_interval_steps=1, async_save=False,
            extra_state=lambda: dict(holder),
            set_extra_state=lambda s: holder.update(s),
        )
        _train_steps(model, optimizer, ac, 0, 2)
        holder["lr_step"] = -1
        model2 = nn.Linear(4, 3)
        opt2 = opt.AdamW(learning_rate=0.01, parameters=model2.parameters())
        ac2 = AutoCheckpoint(str(tmp_path), layers=[model2],
                             optimizers=[opt2],
                             set_extra_state=lambda s: holder.update(s))
        ac2.resume()
        assert holder["lr_step"] == 42


_KILL_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    # force CPU in-process so both runs are hermetic and bit-exact
    # whatever platform the ambient environment selects
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.incubate.checkpoint import AutoCheckpoint

    ckdir, logpath = sys.argv[1], sys.argv[2]
    paddle.seed(0)
    model = nn.Linear(4, 3)
    optimizer = opt.AdamW(learning_rate=0.01, parameters=model.parameters())
    ac = AutoCheckpoint(ckdir, layers=[model], optimizers=[optimizer],
                        save_interval_steps=5, async_save=False)
    start = ac.resume()
    rng = np.random.RandomState(7)
    # deterministic data stream indexed by step so the relaunched run
    # sees the same batches the killed one would have
    for step in range(start, 40):
        st = np.random.RandomState(1000 + step)
        x = paddle.to_tensor(st.randn(8, 4).astype(np.float32))
        y = paddle.to_tensor(st.randint(0, 3, (8,)).astype(np.int64))
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        with open(logpath, "a") as f:
            f.write(f"{{step}} {{float(loss):.6f}}\\n")
        ac.step(step)
    print("DONE", start)
""")


class TestElasticKillRelaunch:
    def test_killed_run_resumes_within_one_interval(self, tmp_path):
        """Kill a training process mid-run; the relaunch must resume
        from the newest checkpoint (within one 5-step interval of the
        kill) and the loss curve must continue the original trajectory
        exactly (same steps -> same losses)."""
        script = tmp_path / "train.py"
        script.write_text(_KILL_SCRIPT.format(repo=_REPO))
        ckdir, log1 = str(tmp_path / "ck"), str(tmp_path / "run1.log")
        env = dict(os.environ, JAX_PLATFORMS="cpu")

        p = subprocess.Popen([sys.executable, str(script), ckdir, log1],
                             env=env)
        # wait until it has passed step 12 (so ckpt-5 and ckpt-10 exist)
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                lines = open(log1).read().strip().splitlines()
                if lines and int(lines[-1].split()[0]) >= 12:
                    break
            except OSError:
                pass
            time.sleep(0.2)
        else:
            p.kill()
            pytest.fail("first run never reached step 12")
        p.send_signal(signal.SIGKILL)
        p.wait()
        killed_at = int(open(log1).read().strip().splitlines()[-1].split()[0])

        log2 = str(tmp_path / "run2.log")
        out = subprocess.run(
            [sys.executable, str(script), ckdir, log2],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        lines2 = open(log2).read().strip().splitlines()
        resumed_at = int(lines2[0].split()[0])
        # resumed from a checkpoint at most one interval before the kill
        assert killed_at - resumed_at <= 5 + 1, (killed_at, resumed_at)
        assert "DONE" in out.stdout
        # overlapping steps must produce IDENTICAL losses (true resume,
        # not a restart): compare the original run's curve on the
        # overlap window
        run1 = {int(l.split()[0]): l.split()[1] for l in
                open(log1).read().strip().splitlines()}
        overlap = [l for l in lines2 if int(l.split()[0]) in run1]
        if not overlap:
            # boundary case: the kill landed right after a checkpoint
            # save, so the relaunch resumed at exactly killed_at+1 —
            # a perfect resume with no steps to replay
            assert resumed_at == killed_at + 1, (killed_at, resumed_at)
        for l in overlap:
            step, loss = l.split()
            assert run1[int(step)] == loss, (step, run1[int(step)], loss)


class TestReviewFindings:
    def test_async_capture_is_a_snapshot(self, tmp_path):
        """The async save must serialize step-N values even if the train
        thread rebinds parameters before the write happens."""
        import threading

        import jax.numpy as jnp

        model, optimizer, ac = _make(tmp_path, save_interval_steps=1,
                                     async_save=True)
        w_before = np.asarray(model.weight._data).copy()
        # block the writer until we've mutated the weights
        gate = threading.Event()
        from paddle_tpu.framework import io as fio

        orig_save = fio.save

        def slow_save(obj, path, *a, **k):
            gate.wait(5.0)
            return orig_save(obj, path, *a, **k)

        fio.save = slow_save
        try:
            ac.save_now(1)
            model.weight._data = jnp.zeros_like(model.weight._data)
            gate.set()
            ac.wait()
        finally:
            fio.save = orig_save
        model2, optimizer2, ac2 = _make(tmp_path / "r", save_interval_steps=1)
        ac2.dir = str(tmp_path)
        assert ac2.resume() == 2
        np.testing.assert_allclose(
            np.asarray(model2.weight._data), w_before)

    def test_wait_raises_failed_save(self, tmp_path):
        model, optimizer, ac = _make(tmp_path, save_interval_steps=1,
                                     async_save=True)
        from paddle_tpu.framework import io as fio

        orig_save = fio.save
        fio.save = lambda *a, **k: (_ for _ in ()).throw(OSError("disk full"))
        try:
            ac.save_now(1)
            import pytest as _pytest

            with _pytest.raises(RuntimeError, match="disk full"):
                ac.wait()
        finally:
            fio.save = orig_save


class TestPoolingEdgeFixes:
    def test_unpool1d_with_padding_roundtrip(self):
        import paddle_tpu.nn.functional as F

        x = paddle.to_tensor(
            (np.random.RandomState(0).permutation(16).astype(np.float32)
             * 0.5).reshape(1, 2, 8))
        out, idx = F.max_pool1d(x, 2, stride=2, padding=1, return_mask=True)
        up = F.max_unpool1d(out, idx, 2, stride=2, padding=1)
        assert up.shape == [1, 2, 8]

    def test_pool3d_ceil_mode_mask_shapes_agree(self):
        import paddle_tpu.nn.functional as F

        x = paddle.to_tensor(
            np.random.RandomState(0).randn(1, 1, 5, 5, 5).astype(np.float32))
        out, idx = F.max_pool3d(x, 2, stride=2, ceil_mode=True,
                                return_mask=True)
        assert tuple(out.shape) == tuple(idx.shape)

    def test_pool3d_negative_input_padding_indices_in_range(self):
        import paddle_tpu.nn.functional as F

        x = paddle.to_tensor(
            -np.abs(np.random.RandomState(0).randn(1, 1, 4, 4, 4))
            .astype(np.float32) - 1.0)
        out, idx = F.max_pool3d(x, 2, stride=2, padding=1, return_mask=True)
        ia = np.asarray(idx._data)
        assert ia.min() >= 0 and ia.max() < 4 * 4 * 4
        up = F.max_unpool3d(out, idx, 2, stride=2, padding=1)
        # every kept value scatters to a real input position
        assert np.isfinite(np.asarray(up._data)).all()

    def test_pool2d_negative_input_padding_indices_in_range(self):
        import paddle_tpu.nn.functional as F

        x = paddle.to_tensor(
            -np.abs(np.random.RandomState(1).randn(1, 1, 4, 4))
            .astype(np.float32) - 1.0)
        out, idx = F.max_pool2d(x, 2, stride=2, padding=1, return_mask=True)
        ia = np.asarray(idx._data)
        assert ia.min() >= 0 and ia.max() < 16


class TestMultiPrecisionRestoreOrder:
    def test_remap_uses_full_coverage_store_order(self):
        """A state dict whose FIRST store covers only a subset (the
        multi_precision master_weight pattern) must not cross-wire
        parameters in the positional remap."""
        import paddle_tpu.optimizer as popt

        paddle.seed(0)
        m = nn.Linear(4, 3)
        o = popt.AdamW(learning_rate=0.01, parameters=m.parameters())
        x = paddle.to_tensor(np.random.RandomState(0).randn(2, 4).astype(np.float32))
        m(x).sum().backward()
        o.step()
        o.clear_grad()
        sd = {k: v for k, v in o.state_dict().items()}
        live = [p.name for p in m.parameters()]
        # simulate a foreign-process dict: rename params AND put a
        # subset-coverage store first (dict order)
        renamed = {}
        renamed[f"{live[1]}_only.master_weight"] = sd[f"{live[1]}.moment1"]
        for k, v in sd.items():
            if k in ("global_step",):
                renamed[k] = v
                continue
            pn, _, acc = k.rpartition(".")
            renamed[f"{pn}_foreign.{acc}"] = v
        o2 = popt.AdamW(learning_rate=0.01, parameters=m.parameters())
        o2.set_state_dict(renamed)
        # the full-coverage stores must map foreign names onto live
        # params in parameter order
        np.testing.assert_allclose(
            np.asarray(o2._accumulators["moment1"][live[0]]),
            np.asarray(getattr(sd[f"{live[0]}.moment1"], "_data",
                               sd[f"{live[0]}.moment1"])))


class TestTrainEpochRange:
    """ref: auto_checkpoint.py:615 — epoch-range iteration resumes at
    the first unfinished epoch after a restart."""

    def test_resume_at_unfinished_epoch(self, tmp_path):
        from paddle_tpu.incubate.checkpoint import train_epoch_range

        paddle.seed(0)
        model = nn.Linear(4, 3)
        optimizer = opt.AdamW(learning_rate=0.01,
                              parameters=model.parameters())
        seen = []
        w_after1 = None
        r = train_epoch_range(5, str(tmp_path), layers=[model],
                              optimizers=[optimizer], async_save=False)
        for epoch in r:
            if epoch == 2:
                # crash before epoch 2 trains: 0 and 1 are checkpointed
                w_after1 = np.asarray(model.weight._data).copy()
                break
            seen.append(epoch)
            _train_steps(model, optimizer,
                         type("N", (), {"step": staticmethod(lambda s: None)}),
                         epoch * 3, 3)
        assert seen == [0, 1]

        model2 = nn.Linear(4, 3)
        opt2 = opt.AdamW(learning_rate=0.01, parameters=model2.parameters())
        r2 = train_epoch_range(5, str(tmp_path), layers=[model2],
                               optimizers=[opt2], async_save=False)
        # epochs 0 and 1 completed (checkpointed); resume at 2, and the
        # restored weights equal the first run's state after epoch 1
        assert r2.start_epoch == 2
        np.testing.assert_allclose(
            np.asarray(model2.weight._data), w_after1, rtol=1e-6)
        assert list(r2) == [2, 3, 4]
        # iterating again resumes past the completed epochs (no repeat)
        assert list(r2) == []
        assert r2.start_epoch == 5


class TestResumeExactness:
    """Satellite (ISSUE 9): the snapshot dict now records the RNG state
    and the dataloader cursor, and resume round-trips AdamW moments +
    the LR-scheduler step count exactly — token-exact rollback's disk
    tier."""

    def _rig(self, tmp_path, cursor=None):
        from paddle_tpu.optimizer.lr import StepDecay

        paddle.seed(0)
        model = nn.Linear(4, 3)
        sched = StepDecay(learning_rate=0.01, step_size=5)
        optimizer = opt.AdamW(learning_rate=sched,
                              parameters=model.parameters())
        ac = AutoCheckpoint(str(tmp_path), layers=[model],
                            optimizers=[optimizer], save_interval_steps=4,
                            async_save=False, data_cursor=cursor)
        return model, optimizer, sched, ac

    def _steps(self, model, optimizer, sched, ac, start, n):
        rng = np.random.RandomState(7)
        for step in range(1, start + n):
            x_np = rng.randn(8, 4).astype(np.float32)
            y_np = rng.randint(0, 3, (8,)).astype(np.int64)
            if step < start:
                continue
            loss = F.cross_entropy(model(paddle.to_tensor(x_np)),
                                   paddle.to_tensor(y_np))
            loss.backward()
            optimizer.step()
            optimizer.clear_grad()
            sched.step()
            ac.step(step)
        return float(loss)

    def test_adamw_moments_and_sched_step_round_trip(self, tmp_path):
        model, optimizer, sched, ac = self._rig(tmp_path)
        self._steps(model, optimizer, sched, ac, 1, 8)  # ckpt at 4, 8
        want_m = {k: np.asarray(v._data if hasattr(v, "_data") else v)
                  for k, v in optimizer.state_dict().items()
                  if hasattr(v, "_data")}
        want_epoch = sched.last_epoch

        model2, optimizer2, sched2, ac2 = self._rig(tmp_path)
        assert ac2.resume() == 9
        got = optimizer2.state_dict()
        # positional remap: compare per-accumulator in parameter order
        got_m = {k: np.asarray(v._data if hasattr(v, "_data") else v)
                 for k, v in got.items() if hasattr(v, "_data")}
        assert len(got_m) == len(want_m)
        for (wk, wv), (gk, gv) in zip(sorted(want_m.items()),
                                      sorted(got_m.items())):
            np.testing.assert_array_equal(wv, gv)
        assert optimizer2._global_step == optimizer._global_step
        assert sched2.last_epoch == want_epoch
        assert sched2() == sched()

    def test_rng_state_round_trips(self, tmp_path):
        model, optimizer, sched, ac = self._rig(tmp_path)
        self._steps(model, optimizer, sched, ac, 1, 4)
        paddle.seed(1234)
        _ = paddle.randn([3])       # advance the stream past the save
        ac.save_now(5, block=True)
        want = np.asarray(paddle.randn([4])._data)  # post-save draws

        model2, optimizer2, sched2, ac2 = self._rig(tmp_path)
        paddle.seed(999)  # a DIFFERENT stream the resume must replace
        assert ac2.resume() == 6
        got = np.asarray(paddle.randn([4])._data)
        np.testing.assert_array_equal(want, got)

    def test_data_cursor_round_trips(self, tmp_path):
        from paddle_tpu.training import DataCursor

        cursor = DataCursor(lambda i: i)
        cursor.quarantine(7)
        model, optimizer, sched, ac = self._rig(tmp_path, cursor=cursor)
        self._steps(model, optimizer, sched, ac, 1, 4)

        cursor2 = DataCursor(lambda i: i)
        model2, optimizer2, sched2, ac2 = self._rig(tmp_path,
                                                    cursor=cursor2)
        assert ac2.resume() == 5
        assert cursor2.quarantined == [7]

    def test_resumed_training_matches_uninterrupted(self, tmp_path):
        model, optimizer, sched, ac = self._rig(tmp_path / "ref")
        want = self._steps(model, optimizer, sched, ac, 1, 12)

        model1, optimizer1, sched1, ac1 = self._rig(tmp_path / "re")
        self._steps(model1, optimizer1, sched1, ac1, 1, 8)  # ckpt at 8
        model2, optimizer2, sched2, ac2 = self._rig(tmp_path / "re")
        start = ac2.resume()
        assert start == 9
        got = self._steps(model2, optimizer2, sched2, ac2, start, 4)
        np.testing.assert_allclose(got, want, rtol=0, atol=0)

    def test_latest_step_reports_newest_verified(self, tmp_path):
        model, optimizer, sched, ac = self._rig(tmp_path)
        assert ac.latest_step() is None
        self._steps(model, optimizer, sched, ac, 1, 8)
        assert ac.latest_step() == 8
        # corrupt the newest payload: latest_step quarantines it and
        # reports the older intact checkpoint
        newest = os.path.join(str(tmp_path), "ckpt-" + "8".zfill(12),
                              "state.pdparams")
        with open(newest, "r+b") as f:
            f.seek(10)
            f.write(b"\xff\xff")
        assert ac.latest_step() == 4

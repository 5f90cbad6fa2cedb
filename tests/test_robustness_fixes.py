"""Regressions for the round-5 advisor findings fixed in the
fault-tolerance PR: eager_recv seq-counter commit, multi-controller
scatter validation, get_world_size(default_group) consistency, and a
GradScaler cycle over a plain optimizer.

Single-process: multi-controller paths are driven through monkeypatched
``active()``/fake KV clients (the 2-real-process proof lives in
tests/_mc_worker.py, slow lane).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as popt


class TestEagerRecvSeqCommit:
    def test_timeout_then_retry_reads_the_same_seq(self, monkeypatch):
        """A timed-out get + caller retry must wait on the SAME seq the
        sender published — the counter commits only after a successful
        receive (round-5 advisor: pre-increment permanently desynced
        the pair after one timeout)."""
        import pickle

        from paddle_tpu.distributed import multi_controller as mc

        requested = []

        class FakeClient:
            def __init__(self):
                self.fail_first = True

            def blocking_key_value_get_bytes(self, key, timeout_ms):
                requested.append(key)
                if self.fail_first:
                    self.fail_first = False
                    raise TimeoutError("kv get timed out")
                return pickle.dumps(np.array([1.0, 2.0]))

            def key_value_delete(self, key):
                pass

        fake = FakeClient()
        monkeypatch.setattr(mc, "_kv_client", lambda: fake)
        monkeypatch.setattr(mc.jax, "process_index", lambda: 1)
        monkeypatch.setitem(mc._p2p_seq, (0, 1), 0)

        with pytest.raises(TimeoutError):
            mc.eager_recv(src=0)
        assert mc._p2p_seq[(0, 1)] == 0  # NOT advanced by the failure

        out = mc.eager_recv(src=0)  # retry
        np.testing.assert_allclose(out, [1.0, 2.0])
        assert mc._p2p_seq[(0, 1)] == 1  # committed after success
        # both attempts asked for seq 1 — no skipped key
        assert requested == ["ptpu_p2p/0/1/1", "ptpu_p2p/0/1/1"]


class TestScatterValidation:
    def test_tensor_list_length_mismatch_raises_clearly(self, monkeypatch):
        import jax

        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import multi_controller as mc

        dist.init_parallel_env()
        monkeypatch.setattr(mc, "active", lambda: True)
        buf = paddle.to_tensor(np.zeros(2, np.float32))
        wrong = [paddle.to_tensor(np.ones(2, np.float32))
                 for _ in range(jax.process_count() + 1)]
        with pytest.raises(ValueError, match="len\\(tensor_list\\)"):
            dist.scatter(buf, tensor_list=wrong, src=0)


class TestWorldSizeConsistency:
    def test_default_group_explicit_or_implicit_agree(self, monkeypatch):
        """get_world_size() and get_world_size(default_group) must report
        the same unit in multi-controller mode (they answered 2 vs 4 in
        tests/_mc_worker.py before the fix)."""
        import jax

        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import multi_controller as mc

        g = dist.init_parallel_env()
        monkeypatch.setattr(mc, "active", lambda: True)
        assert dist.get_world_size() == jax.process_count()
        assert dist.get_world_size(g) == dist.get_world_size()

    def test_subgroup_still_reports_its_nranks(self, monkeypatch):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import multi_controller as mc

        dist.init_parallel_env()
        monkeypatch.setattr(mc, "active", lambda: True)

        class SubGroup:
            nranks = 3
            id = 1

        assert dist.get_world_size(SubGroup()) == 3

    def test_single_controller_unchanged(self):
        import jax

        import paddle_tpu.distributed as dist

        g = dist.init_parallel_env()
        assert dist.get_world_size(g) == g.nranks
        assert dist.get_world_size() == g.nranks == jax.device_count()


class TestScalerDrivesPlainOptimizer:
    def test_plain_optimizer_scaling_still_works(self):
        import paddle_tpu.amp as amp

        paddle.seed(13)
        m = nn.Linear(4, 2)
        o = popt.AdamW(learning_rate=1e-2, parameters=m.parameters())
        scaler = amp.GradScaler(init_loss_scaling=2.0**4)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = scaler.scale((m(x) ** 2).mean())
        loss.backward()
        scaler.step(o)
        scaler.update()
        o.clear_grad()

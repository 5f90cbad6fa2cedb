"""Group-sharded (ZeRO 1/2/3) parity tests + paddle.save/load.

Pattern: every sharding stage must reproduce plain single-replica
numerics exactly — on TPU a stage is only a layout policy, so parity is
by construction and these tests pin that invariant (reference pattern:
test/collective/fleet/dygraph_group_sharded_stage{2,3}.py which compare
stage losses against DP losses).
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import group_sharded_parallel


def _mlp():
    return nn.Sequential(
        nn.Linear(16, 64),
        nn.GELU(),
        nn.Linear(64, 64),
        nn.GELU(),
        nn.Linear(64, 8),
    )


def _train(model, optimizer, steps=4, use_jit=True):
    rng = np.random.RandomState(0)
    xs = [rng.randn(8, 16).astype(np.float32) for _ in range(steps)]
    ys = [rng.randint(0, 8, (8,)) for _ in range(steps)]

    def step(x, y):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        return loss

    if use_jit:
        step = paddle.jit.to_static(step, layers=[model], optimizers=[optimizer])
    losses = []
    for x, y in zip(xs, ys):
        losses.append(float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy()))
    return losses


def _baseline_losses():
    paddle.seed(7)
    model = _mlp()
    optimizer = opt.AdamW(learning_rate=1e-2, parameters=model.parameters())
    return _train(model, optimizer)


class TestGroupSharded:
    @pytest.mark.parametrize("level", ["os", "os_g", "p_g_os"])
    def test_stage_matches_baseline(self, level):
        base = _baseline_losses()

        paddle.seed(7)
        model = _mlp()
        optimizer = opt.AdamW(learning_rate=1e-2, parameters=model.parameters())
        model, optimizer, _ = group_sharded_parallel(model, optimizer, level=level)
        losses = _train(model, optimizer)
        np.testing.assert_allclose(losses, base, rtol=1e-5, atol=1e-6)

    def test_stage3_param_layout_is_sharded(self):
        import jax

        paddle.seed(7)
        model = _mlp()
        optimizer = opt.AdamW(learning_rate=1e-2, parameters=model.parameters())
        model, optimizer, _ = group_sharded_parallel(model, optimizer, level="p_g_os")
        w = model[0].weight._data
        assert not w.sharding.is_fully_replicated
        # state after a step stays sharded (placement survives donation)
        _train(model, optimizer, steps=1)
        m = optimizer._accumulators["moment1"]
        assert any(not a.sharding.is_fully_replicated for a in m.values())

    def test_stage3_composes_with_tensor_parallel_placement(self):
        """fleet.distributed_model shards tp_axis dims over ``mp``;
        group_sharded_parallel must ADD the sharding axis, not replace
        the layout — else every matrix is only sharding_degree-way
        split and per-chip memory is a half, not a quarter (the
        four-chip bring-up finding, chip_smoke.py --fleet)."""
        from jax.sharding import PartitionSpec as P

        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import fleet

        base = _baseline_losses()
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"sharding_degree": 2, "mp_degree": 2}
        fleet.init(strategy=strategy)
        try:
            paddle.seed(7)
            model = _mlp()
            model[0].weight.tp_axis = 1  # column parallel
            model[2].weight.tp_axis = 0  # row parallel
            tp_model = fleet.distributed_model(model)
            optimizer = opt.AdamW(learning_rate=1e-2,
                                  parameters=model.parameters())
            group_sharded_parallel(tp_model, optimizer, level="p_g_os")
            assert model[0].weight._data.sharding.spec == P("sharding", "mp")
            assert model[2].weight._data.sharding.spec == P("mp", "sharding")
            shard = model[0].weight._data.addressable_shards[0].data
            assert shard.size * 4 == model[0].weight._data.size
            losses = _train(model, optimizer)
            np.testing.assert_allclose(losses, base, rtol=1e-5, atol=1e-6)
            # moments follow their parameter's composed layout
            m = optimizer._accumulators["moment1"][model[0].weight.name]
            assert m.sharding.spec == P("sharding", "mp")
        finally:
            dist.destroy_process_group()
            fleet.set_hybrid_communicate_group(None)

    def test_save_group_sharded_model(self, tmp_path):
        from paddle_tpu.distributed import save_group_sharded_model

        paddle.seed(7)
        model = _mlp()
        optimizer = opt.AdamW(learning_rate=1e-2, parameters=model.parameters())
        model, optimizer, _ = group_sharded_parallel(model, optimizer, level="p_g_os")
        _train(model, optimizer, steps=1)
        out = str(tmp_path / "ckpt")
        save_group_sharded_model(model, out, optimizer=optimizer)
        assert os.path.exists(os.path.join(out, "model.pdmodel"))
        sd = paddle.load(os.path.join(out, "model.pdmodel"))
        assert sd["0.weight"].shape == [16, 64]

    def test_dygraph_sharding_optimizer(self):
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            DygraphShardingOptimizer,
        )

        base = _baseline_losses()
        paddle.seed(7)
        model = _mlp()
        inner = opt.AdamW(learning_rate=1e-2, parameters=model.parameters())
        optimizer = DygraphShardingOptimizer(inner)
        losses = _train(model, optimizer._inner_opt)
        np.testing.assert_allclose(losses, base, rtol=1e-5, atol=1e-6)


class TestSaveLoad:
    def test_state_dict_roundtrip(self, tmp_path):
        paddle.seed(1)
        model = _mlp()
        path = str(tmp_path / "m.pdparams")
        paddle.save(model.state_dict(), path)
        loaded = paddle.load(path)
        paddle.seed(2)
        model2 = _mlp()
        model2.set_state_dict(loaded)
        for (k1, p1), (k2, p2) in zip(
            model.named_parameters(), model2.named_parameters()
        ):
            assert k1 == k2
            np.testing.assert_array_equal(p1.numpy(), p2.numpy())

    def test_optimizer_state_roundtrip_resumes_loss_curve(self, tmp_path):
        paddle.seed(7)
        model = _mlp()
        optimizer = opt.AdamW(learning_rate=1e-2, parameters=model.parameters())
        _train(model, optimizer, steps=2, use_jit=False)
        paddle.save(model.state_dict(), str(tmp_path / "m.pdparams"))
        paddle.save(optimizer.state_dict(), str(tmp_path / "m.pdopt"))
        cont = _train(model, optimizer, steps=2, use_jit=False)

        paddle.seed(9)
        model2 = _mlp()
        optimizer2 = opt.AdamW(learning_rate=1e-2, parameters=model2.parameters())
        model2.set_state_dict(paddle.load(str(tmp_path / "m.pdparams")))
        optimizer2.set_state_dict(paddle.load(str(tmp_path / "m.pdopt")))
        resumed = _train(model2, optimizer2, steps=2, use_jit=False)
        np.testing.assert_allclose(resumed, cont, rtol=1e-5, atol=1e-6)

    def test_nested_containers_and_scalars(self, tmp_path):
        obj = {
            "t": paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3)),
            "nested": [{"a": paddle.to_tensor([1, 2])}, (3, "s")],
            "epoch": 7,
        }
        path = str(tmp_path / "obj.pdz")
        paddle.save(obj, path)
        back = paddle.load(path)
        np.testing.assert_array_equal(back["t"].numpy(), obj["t"].numpy())
        assert back["nested"][1] == (3, "s")
        assert back["epoch"] == 7
        arr = paddle.load(path, return_numpy=True)["t"]
        assert isinstance(arr, np.ndarray)

    def test_bf16_roundtrip(self, tmp_path):
        t = paddle.to_tensor(np.random.RandomState(0).randn(4, 4)).astype("bfloat16")
        path = str(tmp_path / "bf16.pdparams")
        paddle.save({"w": t}, path)
        back = paddle.load(path)["w"]
        assert back.dtype == "bfloat16"
        np.testing.assert_array_equal(
            back.astype("float32").numpy(), t.astype("float32").numpy()
        )

    def test_save_to_dir_raises(self, tmp_path):
        with pytest.raises(ValueError):
            paddle.save({}, str(tmp_path))

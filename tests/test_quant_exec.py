"""Quantized EXECUTION paths: real int8 dots (llm.int8, converted QAT)
and fp8 GEMM — not fake-quant float (ref:
paddle/phi/kernels/impl/llm_int8_matmul_kernel_impl.h,
phi/kernels/fusion/cutlass fp8_gemm)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


def _jaxpr_has_int8_dot(fn, *args):
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    return "i8[" in jaxpr and "preferred_element_type=int32" in jaxpr


class TestLlmInt8Linear:
    def test_executes_int8_dot(self):
        from paddle_tpu.nn.quant import int8_dynamic_matmul, weight_quantize

        rng = np.random.RandomState(0)
        w = paddle.to_tensor(rng.randn(32, 16).astype(np.float32) * 0.1)
        q, s = weight_quantize(w)
        a = rng.randn(4, 32).astype(np.float32)

        def raw(av):
            return int8_dynamic_matmul(av, q._data, s._data, outlier_threshold=6.0)

        assert _jaxpr_has_int8_dot(raw, a)

    def test_accuracy_vs_float(self):
        from paddle_tpu.nn.quant import llm_int8_linear, weight_quantize

        rng = np.random.RandomState(1)
        w = paddle.to_tensor(rng.randn(64, 32).astype(np.float32) * 0.05)
        x = paddle.to_tensor(rng.randn(8, 64).astype(np.float32))
        q, s = weight_quantize(w)
        got = llm_int8_linear(x, q, weight_scale=s).numpy()
        want = (x.numpy() @ w.numpy())
        # int8 weights + int8 activations: ~1% relative error on gaussians
        rel = np.abs(got - want).mean() / np.abs(want).mean()
        assert rel < 0.02, rel

    def test_outlier_split_beats_plain_int8(self):
        """A huge activation outlier column wrecks plain int8 dynamic
        quantization; the llm.int8 top-K float split must recover it."""
        from paddle_tpu.nn.quant import llm_int8_linear, weight_quantize

        rng = np.random.RandomState(2)
        w = paddle.to_tensor(rng.randn(64, 32).astype(np.float32) * 0.05)
        x_np = rng.randn(8, 64).astype(np.float32)
        x_np[:, 7] = 80.0  # outlier feature
        x = paddle.to_tensor(x_np)
        q, s = weight_quantize(w)
        want = x_np @ w.numpy()
        with_split = llm_int8_linear(x, q, weight_scale=s, threshold=6.0).numpy()
        no_split = llm_int8_linear(x, q, weight_scale=s, threshold=1e9).numpy()
        err_split = np.abs(with_split - want).mean()
        err_plain = np.abs(no_split - want).mean()
        assert err_split < err_plain / 2, (err_split, err_plain)

    def test_bias(self):
        from paddle_tpu.nn.quant import llm_int8_linear, weight_quantize

        rng = np.random.RandomState(3)
        w = paddle.to_tensor(rng.randn(16, 8).astype(np.float32) * 0.1)
        b = paddle.to_tensor(rng.randn(8).astype(np.float32))
        x = paddle.to_tensor(rng.randn(2, 16).astype(np.float32))
        q, s = weight_quantize(w)
        got = llm_int8_linear(x, q, bias=b, weight_scale=s).numpy()
        want = x.numpy() @ w.numpy() + b.numpy()
        assert np.abs(got - want).mean() / np.abs(want).mean() < 0.05


class TestLlmInt8Grads:
    def test_ste_gradient_matches_float_matmul(self):
        from paddle_tpu.nn.quant import llm_int8_linear, weight_quantize

        rng = np.random.RandomState(6)
        w = paddle.to_tensor(rng.randn(32, 16).astype(np.float32) * 0.1)
        q, s = weight_quantize(w)
        x = paddle.to_tensor(rng.randn(4, 32).astype(np.float32))
        x.stop_gradient = False
        out = llm_int8_linear(x, q, weight_scale=s)
        out.sum().backward()
        # straight-through: grad == float-matmul grad = row-sum of W_dequant
        w_deq = q.numpy().astype(np.float32) * s.numpy()
        want = np.broadcast_to(w_deq.sum(axis=1), x.shape)
        np.testing.assert_allclose(x.grad.numpy(), want, rtol=1e-4, atol=1e-5)


class TestQATInt8Convert:
    def test_convert_int8_runs_int8_and_matches(self):
        from paddle_tpu.quantization import (
            QAT, Int8InferenceLinear, QuantConfig, quanter,
        )

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        float_out = model(x).numpy()

        cfg = QuantConfig(activation=quanter(moving_rate=0.9),
                          weight=quanter(moving_rate=0.9))
        qat = QAT(cfg)
        model = qat.quantize(model)
        model(x)  # observe
        model = qat.convert(model, execute_dtype="int8")
        assert isinstance(model[0], Int8InferenceLinear)
        assert model[0].qweight.numpy().dtype == np.int8
        int8_out = model(x).numpy()
        rel = np.abs(int8_out - float_out).mean() / (np.abs(float_out).mean() + 1e-9)
        assert rel < 0.05, rel

        # the executed program must contain an int8 dot
        lin = model[0]

        def raw(av):
            from paddle_tpu.nn.quant import int8_dynamic_matmul

            return int8_dynamic_matmul(av, lin.qweight._data, lin.scale._data)

        assert _jaxpr_has_int8_dot(raw, x.numpy())

    def test_convert_default_still_folds(self):
        from paddle_tpu.nn import Linear
        from paddle_tpu.quantization import QAT, QuantConfig, quanter

        paddle.seed(1)
        model = nn.Sequential(nn.Linear(8, 8))
        qat = QAT(QuantConfig(activation=None, weight=quanter(moving_rate=0.9)))
        model = qat.quantize(model)
        x = paddle.to_tensor(np.random.RandomState(1).randn(2, 8).astype(np.float32))
        model(x)
        model = qat.convert(model)
        assert isinstance(model[0], Linear)


class TestFP8Gemm:
    def test_fp8_dot_executes_and_tolerates(self):
        import ml_dtypes

        from paddle_tpu.tensor.linalg import fp8_fp8_half_gemm_fused

        rng = np.random.RandomState(4)
        a = rng.randn(8, 32).astype(np.float32) * 0.5
        b = rng.randn(32, 16).astype(np.float32) * 0.5
        out = fp8_fp8_half_gemm_fused(
            paddle.to_tensor(a), paddle.to_tensor(b), output_dtype="bfloat16"
        )
        want = a @ b
        got = out.numpy().astype(np.float32)
        rel = np.abs(got - want).mean() / np.abs(want).mean()
        assert rel < 0.06, rel  # e4m3 has ~2 decimal digits

        def raw(av, bv):
            aa = av.astype(ml_dtypes.float8_e4m3fn)
            bb = bv.astype(ml_dtypes.float8_e4m3fn)
            return jax.lax.dot_general(
                aa, bb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        jaxpr = str(jax.make_jaxpr(raw)(a, b))
        assert "f8_e4m3" in jaxpr

    def test_fp8_act_fusion(self):
        from paddle_tpu.tensor.linalg import fp8_fp8_half_gemm_fused

        rng = np.random.RandomState(5)
        a = rng.randn(4, 16).astype(np.float32)
        b = rng.randn(16, 8).astype(np.float32)
        out = fp8_fp8_half_gemm_fused(
            paddle.to_tensor(a), paddle.to_tensor(b), act="relu"
        ).numpy().astype(np.float32)
        assert (out >= 0).all()
        with pytest.raises(ValueError, match="unsupported act"):
            fp8_fp8_half_gemm_fused(
                paddle.to_tensor(a), paddle.to_tensor(b), act="tanh"
            )


class TestInt8Serving:
    """convert(execute_dtype='int8') wired into the generation decode
    path (ref: llm_int8_matmul_kernel_impl.h): int8 generate must run,
    stay close to the bf16/f32 logits, and keep argmax in the float
    top-5 (greedy match on a RANDOM-init model is a worst-case metric —
    near-tie logits flip under tiny perturbations)."""

    def test_int8_generate_matches_float_logits(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.generation import generate
        from paddle_tpu.quantization import QAT, QuantConfig

        paddle.seed(3)
        cfg_m = LlamaConfig.tiny(num_hidden_layers=2)
        model = LlamaForCausalLM(cfg_m)
        model.eval()
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rng.randint(0, cfg_m.vocab_size, (4, 12)).astype(np.int64))

        ref_logits = np.asarray(model(ids)._data[:, -1].astype("float32"))
        ref_out = generate(model, ids, max_new_tokens=6, temperature=0.0)

        qat = QAT(QuantConfig(activation=None, weight=None))
        model = qat.quantize(model)
        model = qat.convert(model, execute_dtype="int8")
        int8_logits = np.asarray(model(ids)._data[:, -1].astype("float32"))
        rel = np.abs(int8_logits - ref_logits).mean() / (
            np.abs(ref_logits).mean() + 1e-9)
        assert rel < 0.08, rel
        top5 = np.argsort(ref_logits, -1)[:, -5:]
        hits = sum(int8_logits[i].argmax() in top5[i] for i in range(4))
        assert hits >= 3, hits

        out = generate(model, ids, max_new_tokens=6, temperature=0.0,
                       decode_chunk=4)
        assert out.shape == ref_out.shape  # int8 decode runs end-to-end

    def test_observer_first_scale_is_absmax(self):
        """Regression: accum/state zero-init — one observation must set
        scale == absmax (the old 1.0 init skewed it ~(r+a)/(r+1))."""
        from paddle_tpu.quantization import FakeQuanterWithAbsMaxObserver

        q = FakeQuanterWithAbsMaxObserver(moving_rate=0.9)
        q.train()
        x = paddle.to_tensor(np.array([0.5, -2.0, 1.0], np.float32))
        q(x)
        np.testing.assert_allclose(float(q.scale), 2.0, rtol=1e-6)


class TestWeightOnlyInt4:
    """int4 weight-only path (ref: quantized_linear.py:39,156 with
    weight_only_int4): packed two-per-byte storage, per-channel or
    group-wise scales, exact linear vs the dequantized weight."""

    def test_pack_roundtrip_exact(self):
        from paddle_tpu.nn.quant import (
            weight_dequantize, weight_quantize,
        )

        rng = np.random.RandomState(0)
        w = rng.randn(128, 16).astype(np.float32)
        q, s = weight_quantize(paddle.to_tensor(w),
                               algo="weight_only_int4")
        assert list(q.shape) == [64, 16]  # packed along in-dim
        wd = weight_dequantize(q, s, algo="weight_only_int4",
                               out_dtype="float32").numpy()
        # every dequant value sits on the int4 grid of its channel
        scale = np.asarray(s.numpy())
        grid = np.round(wd / scale[None, :])
        assert np.abs(grid).max() <= 8
        np.testing.assert_allclose(wd, grid * scale[None, :], rtol=1e-5)
        # quant error bounded by half a step per element
        assert np.abs(wd - w).max() <= 0.5 * scale.max() + 1e-6

    @pytest.mark.parametrize("gs", [-1, 64, 128])
    def test_linear_matches_dequant(self, gs):
        from paddle_tpu.nn.quant import (
            weight_dequantize, weight_only_linear, weight_quantize,
        )

        rng = np.random.RandomState(1)
        w = rng.randn(128, 12).astype(np.float32)
        x = rng.randn(5, 128).astype(np.float32)
        q, s = weight_quantize(paddle.to_tensor(w),
                               algo="weight_only_int4", group_size=gs)
        if gs > 0:
            assert list(s.shape) == [128 // gs, 12]
        out = weight_only_linear(paddle.to_tensor(x), q, weight_scale=s,
                                 weight_dtype="int4", group_size=gs)
        # exactness vs the dequantized weight is the op's contract
        if gs > 0:
            sc = np.repeat(np.asarray(s.numpy()), gs, axis=0)
        else:
            sc = np.asarray(s.numpy())[None, :]
        import jax.numpy as jnp

        from paddle_tpu.nn.quant import _unpack_int4

        wd = np.asarray(_unpack_int4(q._data)).astype(np.float32) * sc
        np.testing.assert_allclose(out.numpy(), x @ wd, rtol=2e-4,
                                   atol=2e-4)

    def test_groupwise_beats_or_matches_per_channel_on_outliers(self):
        from paddle_tpu.nn.quant import weight_dequantize, weight_quantize

        rng = np.random.RandomState(2)
        w = rng.randn(128, 8).astype(np.float32)
        w[0, :] *= 50  # an outlier row blows up per-channel scales
        errs = {}
        for gs in (-1, 64):
            q, s = weight_quantize(paddle.to_tensor(w),
                                   algo="weight_only_int4", group_size=gs)
            wd = weight_dequantize(q, s, algo="weight_only_int4",
                                   out_dtype="float32").numpy()
            errs[gs] = np.abs(wd[64:] - w[64:]).mean()  # clean group rows
        # the outlier contaminates only ITS group: the clean group's
        # error must drop to plain-gaussian levels (per-channel scales
        # stay blown up everywhere)
        assert errs[64] < 0.2 * errs[-1], errs

    def test_convert_model_and_serve(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.nn.quant import (
            WeightOnlyLinear, convert_to_weight_only,
        )

        paddle.seed(0)
        m = nn.Sequential(nn.Linear(64, 32), nn.GELU(), nn.Linear(32, 8))
        x = paddle.to_tensor(
            np.random.RandomState(3).randn(4, 64).astype(np.float32))
        ref = m(x).numpy()
        n = convert_to_weight_only(m, weight_dtype="int4")
        assert n == 2
        assert isinstance(m[0], WeightOnlyLinear)
        out = m(x).numpy()
        rel = np.abs(out - ref).mean() / np.abs(ref).mean()
        assert rel < 0.2, rel  # int4 noise, but same function
        # under jit too
        sf = paddle.jit.to_static(lambda t: m(t), layers=[m])
        np.testing.assert_allclose(np.asarray(sf(x).numpy()), out,
                                   rtol=1e-3, atol=1e-3)

    def test_odd_input_dim_rejected(self):
        from paddle_tpu.nn.quant import weight_quantize

        with pytest.raises(ValueError, match="even"):
            weight_quantize(
                paddle.to_tensor(np.zeros((7, 4), np.float32)),
                algo="weight_only_int4")

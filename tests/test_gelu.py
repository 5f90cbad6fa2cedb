"""Exact GELU (``F.gelu(x)``, ``approximate=False``): one float32 ``erf``
in the forward, the derivative ``Phi(x) + x pdf(x)`` as the only residual,
one multiply in the backward (PERF.md section 6, PR 30).

Values and gradients are held against float64 ``math.erf`` / ``math.erfc``
and against the path this replaces, ``jax.nn.gelu(a, approximate=False)``:
``erfc`` in the input's own type.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.base.tape import apply
from paddle_tpu.models.gpt import GPTBlock, GPTConfig
from paddle_tpu.nn.functional.activation import _gelu_exact

DTYPES = ["bfloat16", "float16", "float32"]
RANGES = {"core": (-1.0, 1.0), "neg_tail": (-8.0, -1.0), "pos_tail": (1.0, 8.0)}
SPECIALS = {"zero": 0.0, "pos_inf": math.inf, "neg_inf": -math.inf, "nan": math.nan}
EPS32 = float(np.finfo(np.float32).eps)


def _old(a):
    return jax.nn.gelu(a, approximate=False)


def old_gelu(x, approximate=False, name=None):
    """``F.gelu`` as it was before PR 30."""
    return apply(lambda a: jax.nn.gelu(a, approximate=approximate), x, op_name="gelu")


_cdf = np.vectorize(lambda v: 0.5 * math.erfc(-v / math.sqrt(2.0)))
_pdf = np.vectorize(lambda v: math.exp(-0.5 * v * v) / math.sqrt(2.0 * math.pi))


def _ref(v):
    return v * _cdf(v)


def _dref(v):
    return _cdf(v) + v * _pdf(v)


def _d2ref(v):
    return _pdf(v) * (2.0 - v * v)


def _draw(dtype, lo, hi, n=4096, seed=0):
    a = jnp.asarray(np.random.RandomState(seed).uniform(lo, hi, n), jnp.float32)
    a = a.astype(dtype)
    return a, np.asarray(a.astype(jnp.float32), np.float64)


def _f64(a):
    return np.asarray(jnp.asarray(a).astype(jnp.float32), np.float64)


def _allowed(dtype, old_err, x64):
    """The new error may not pass the old path's. In float32 both read a few
    units in the last place: there ``erf`` saturates three units short of 1
    beyond |x| ~ 4, which the ``erfc`` form does not, so the bound is 8
    units at the range's size (1e-6 at 8)."""
    if dtype == "float32":
        return max(old_err, 8 * EPS32 * max(1.0, float(np.abs(x64).max())))
    return old_err


class TestValue:
    @pytest.mark.parametrize("rng", list(RANGES))
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_no_farther_from_float64_than_erfc_in_the_inputs_type(self, dtype, rng):
        a, x64 = _draw(dtype, *RANGES[rng])
        want = _ref(x64)
        got = F.gelu(paddle.to_tensor(a))._data
        new = np.abs(_f64(got) - want).max()
        old = np.abs(_f64(_old(a)) - want).max()
        assert new <= _allowed(dtype, old, x64), (new, old)
        assert got.dtype == a.dtype

    @pytest.mark.parametrize("point", list(SPECIALS))
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_special_points(self, dtype, point):
        a = jnp.full((4,), SPECIALS[point], dtype)
        t = paddle.to_tensor(a, stop_gradient=False)
        y = F.gelu(t)
        y.sum().backward()
        y, g = _f64(y._data), _f64(t.grad._data)
        if point == "zero":
            assert (y == 0).all() and (g == 0.5).all()
        elif point == "pos_inf":
            assert (y == math.inf).all()
        elif point == "nan":
            assert np.isnan(y).all() and np.isnan(g).all()
        else:
            # the limit is -0; 0 * inf is what both forms compute, the old
            # one NaN, this one NaN or -inf by how far erf saturates
            assert not (np.isfinite(y) & (y != 0)).any()

    def test_integers_become_float32_as_under_jax(self):
        y = F.gelu(paddle.to_tensor(np.arange(-2, 3, dtype=np.int32)))
        assert y._data.dtype == jnp.float32
        np.testing.assert_allclose(_f64(y._data), _ref(np.arange(-2.0, 3.0)), atol=1e-6)

    def test_layer_is_the_functional(self):
        a, _ = _draw("float32", -3, 3, n=64)
        t = paddle.to_tensor(a)
        assert (nn.GELU()(t)._data == F.gelu(t)._data).all()
        assert (nn.GELU(approximate=True)(t)._data == jax.nn.gelu(a, approximate=True)).all()


def _grad_eager(fn, a):
    t = paddle.to_tensor(a, stop_gradient=False)
    fn(t).backward()
    return t.grad._data


def _grad_static(fn, a):
    def step(x):
        x = x.detach()
        x.stop_gradient = False
        fn(x).backward()
        return x.grad

    return paddle.jit.to_static(step)(paddle.to_tensor(a))._data


def _once(t):
    return F.gelu(t).astype("float32").sum()


def _twice(t):
    # the input is read again after the activation: the gradient is a sum
    # of two contributions to one tensor
    return (F.gelu(t) * t).astype("float32").sum()


class TestGradient:
    @pytest.mark.parametrize("use", ["once", "twice"])
    @pytest.mark.parametrize("mode", ["tape", "to_static"])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_against_float64_derivative(self, dtype, mode, use, monkeypatch):
        run = _grad_eager if mode == "tape" else _grad_static
        fn = _once if use == "once" else _twice
        for rng, (lo, hi) in RANGES.items():
            a, x64 = _draw(dtype, lo, hi, n=1024, seed=1)
            want = _dref(x64)
            if use == "twice":
                # d/dx [gelu(x) x] with gelu(x) as the forward rounds it
                want = want * x64 + _f64(F.gelu(paddle.to_tensor(a))._data)
            got = run(fn, a)
            new = np.abs(_f64(got) - want).max()
            with monkeypatch.context() as m:
                m.setattr(F, "gelu", old_gelu)
                old = np.abs(_f64(run(fn, a)) - want).max()
            slack = np.abs(want).max() * float(jnp.finfo(dtype).eps)
            assert new <= _allowed(dtype, old, x64) + (slack if use == "twice" else 0.0), (
                rng, new, old)
            assert got.dtype == a.dtype

    def test_second_derivative(self):
        a, x64 = _draw("float32", -4, 4, n=256, seed=2)
        t = paddle.to_tensor(a, stop_gradient=False)
        (g,) = paddle.grad(F.gelu(t).sum(), [t], create_graph=True)
        (gg,) = paddle.grad(g.sum(), [t])
        np.testing.assert_allclose(_f64(gg._data), _d2ref(x64), atol=2e-6)

    @pytest.mark.parametrize("level,black,want", [
        ("O2", None, "bfloat16"), ("O2", ["gelu"], "float32"), ("O1", None, "float32")])
    def test_amp_still_finds_the_op_by_its_name(self, level, black, want):
        t = paddle.to_tensor(np.linspace(-2, 2, 8).astype(np.float32), stop_gradient=False)
        with paddle.amp.auto_cast(level=level, custom_black_list=black, dtype="bfloat16"):
            y = F.gelu(t)
        assert str(y._data.dtype) == want
        y.astype("float32").sum().backward()
        assert t.grad._data.dtype == jnp.float32


class TestApproximateUntouched:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_tanh_form_bit_identical_to_jax(self, dtype):
        a, _ = _draw(dtype, -8, 8, n=2048, seed=3)
        t = paddle.to_tensor(a, stop_gradient=False)
        y = F.gelu(t, approximate=True)
        y.astype("float32").sum().backward()
        want, vjp = jax.vjp(lambda v: jax.nn.gelu(v, approximate=True), a)
        assert (y._data == want).all()
        assert (t.grad._data == vjp(jnp.ones_like(a))[0]).all()


def _count(jaxpr, names, out=None):
    out = {n: 0 for n in names} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in out:
            out[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, names, out)
    return out


class TestLowering:
    PRIMS = ("erf", "erfc", "exp", "erf_inv", "tanh", "logistic")

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_step_holds_one_erf_and_one_exp(self, dtype):
        def step(a):
            t = paddle.to_tensor(a, stop_gradient=False)
            y = F.gelu(t)
            y.astype("float32").sum().backward()
            return y._data, t.grad._data

        got = _count(jax.make_jaxpr(step)(jnp.zeros((8, 128), dtype)).jaxpr, self.PRIMS)
        assert got == {"erf": 1, "erfc": 0, "exp": 1, "erf_inv": 0, "tanh": 0, "logistic": 0}

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_backward_is_one_multiply_of_the_kept_derivative(self, dtype):
        a = jnp.zeros((8, 128), dtype)
        y, vjp = jax.vjp(_gelu_exact, a)
        jaxpr = jax.make_jaxpr(vjp)(jnp.ones_like(y)).jaxpr
        assert _count(jaxpr, self.PRIMS) == {n: 0 for n in self.PRIMS}
        assert _count(jaxpr, ("mul",)) == {"mul": 1}
        # the derivative, in the input's type, is all the backward closes over
        assert [(v.aval.shape, str(v.aval.dtype)) for v in jaxpr.constvars] == [((8, 128), dtype)]

    def test_forward_alone_pays_no_exp(self):
        got = _count(jax.make_jaxpr(lambda a: F.gelu(paddle.to_tensor(a))._data)(
            jnp.zeros((8, 128), jnp.bfloat16)).jaxpr, self.PRIMS)
        assert got["erf"] == 1 and got["erfc"] == 0 and got["exp"] == 0

    def test_arithmetic_type_follows_the_input(self):
        for dtype, wide in (("bfloat16", "float32"), ("float16", "float32"), ("float32", "float32")):
            jaxpr = jax.make_jaxpr(lambda a: jax.vjp(_gelu_exact, a)[0])(jnp.zeros((4,), dtype)).jaxpr
            (erf,) = [e for e in jaxpr.eqns if e.primitive.name == "erf"]
            assert str(erf.outvars[0].aval.dtype) == wide

    def test_lowered_for_the_tpu_keeps_erf_whole(self):
        fn = jax.jit(lambda a, ct: jax.vjp(_gelu_exact, a)[1](ct))
        a = jnp.zeros((256, 256), jnp.bfloat16)
        text = fn.trace(a, a).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("chlo.erf ") + text.count("chlo.erf(") == 1, text
        assert "erfc" not in text and "optimization_barrier" in text


class TestGPTBlock:
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_block_agrees_with_the_old_expression(self, dtype, monkeypatch):
        """One toy GPT block forward and backward, against the same block
        with the activation as it was, inside the type's tolerance."""
        def run():
            paddle.seed(7)
            block = GPTBlock(GPTConfig.tiny())
            if dtype == "bfloat16":
                block.bfloat16()
            x = paddle.to_tensor(
                np.random.RandomState(7).randn(2, 16, 64).astype(np.float32)).astype(dtype)
            x.stop_gradient = False
            out = block(x)
            (out.astype("float32") ** 2).mean().backward()
            grads = {n: _f64(p.grad._data) for n, p in block.named_parameters()}
            grads["x"] = _f64(x.grad._data)
            return _f64(out._data), grads

        out_new, g_new = run()
        monkeypatch.setattr(F, "gelu", old_gelu)
        out_old, g_old = run()
        tol = 2e-2 if dtype == "bfloat16" else 1e-5

        def gap(a, b):
            return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)

        assert gap(out_new, out_old) < tol
        assert set(g_new) == set(g_old) and "fc1.bias" in g_new
        for name in g_new:
            assert gap(g_new[name], g_old[name]) < tol, name

"""Activation functionals (ref: python/paddle/nn/functional/activation.py).

All lower to jax.nn / jnp primitives through the tape dispatch point so
XLA fuses them into adjacent matmuls (SURVEY §7.1: phi activation kernels
collapse to jnp lowering on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...base.tape import apply
from ...base.tensor import Tensor

__all__ = [
    "celu", "elu", "gelu", "glu", "gumbel_softmax", "hardshrink", "hardsigmoid",
    "hardswish", "hardtanh", "leaky_relu", "log_sigmoid", "log_softmax",
    "maxout", "mish", "prelu", "relu", "relu6", "relu_", "rrelu", "selu",
    "sigmoid", "silu", "softmax", "softmax_", "softplus", "softshrink",
    "softsign", "swish", "tanh", "tanh_", "tanhshrink", "thresholded_relu",
    "elu_", "hardtanh_", "leaky_relu_", "thresholded_relu_",
]


def _unary(fn, name):
    # NB: the user-facing ``name=None`` kwarg must not shadow the op name
    # (amp list lookup keys on op_name at the dispatch point)
    def wrapper(x, name=None, _op=name):
        return apply(fn, x, op_name=_op)

    wrapper.__name__ = name
    return wrapper


relu = _unary(jax.nn.relu, "relu")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
silu = _unary(jax.nn.silu, "silu")
tanh = _unary(jnp.tanh, "tanh")
softsign = _unary(jax.nn.soft_sign, "softsign")
log_sigmoid = _unary(jax.nn.log_sigmoid, "log_sigmoid")
mish = _unary(lambda x: x * jnp.tanh(jax.nn.softplus(x)), "mish")
tanhshrink = _unary(lambda x: x - jnp.tanh(x), "tanhshrink")


def relu_(x, name=None):
    return x._inplace_from(relu(x))


def tanh_(x, name=None):
    return x._inplace_from(tanh(x))


def relu6(x, name=None):
    return apply(lambda a: jnp.clip(a, 0, 6), x, op_name="relu6")


def elu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.elu(a, alpha), x, op_name="elu")


def celu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.celu(a, alpha), x, op_name="celu")


def selu(
    x,
    scale=1.0507009873554804934193349852946,
    alpha=1.6732632423543772848170429916717,
    name=None,
):
    return apply(
        lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)), x, op_name="selu"
    )


_SQRT_HALF = 0.7071067811865476  # 1 / sqrt(2)
_INV_SQRT_2PI = 0.3989422804014327  # 1 / sqrt(2 pi)


def _gelu_cdf(a):
    """``x`` in the type the exact GELU is computed in (float32 for bfloat16
    and float16, the input's own for float32 / float64) and ``Phi(x) = 0.5 (1
    + erf(x / sqrt 2))``: ONE ``erf``, which the TPU evaluates as one clamped
    rational polynomial. ``jax.nn.gelu`` takes ``erfc`` in the input's type,
    a two-branch expansion of some 70 vector ops an element that outweighs
    the matmul whose epilogue it rides in (PERF.md section 6, PR 30)."""
    x = a.astype(jnp.promote_types(a.dtype, jnp.float32))
    return x, 0.5 * (1.0 + jax.lax.erf(x * _SQRT_HALF))


@jax.custom_vjp
def _gelu_erf(a):
    x, cdf = _gelu_cdf(a)
    return (x * cdf).astype(a.dtype)


def _gelu_erf_fwd(a):
    # The derivative Phi(x) + x pdf(x) is the only residual: the input has
    # no reader after the forward and the backward is one multiply. The
    # barrier makes the producer (a matmul's epilogue, in an MLP) evaluate
    # erf once and write both results; without it XLA evaluates the
    # activation again inside every consumer, which costs more than the
    # expansion did (PERF.md section 6, PR 30).
    x, cdf = _gelu_cdf(a)
    slope = cdf + x * (_INV_SQRT_2PI * jnp.exp(-0.5 * x * x))
    return jax.lax.optimization_barrier(
        ((x * cdf).astype(a.dtype), slope.astype(a.dtype)))


def _gelu_erf_bwd(slope, ct):
    wide = jnp.promote_types(slope.dtype, jnp.float32)
    return ((ct.astype(wide) * slope.astype(wide)).astype(slope.dtype),)


_gelu_erf.defvjp(_gelu_erf_fwd, _gelu_erf_bwd)


def _gelu_exact(a):
    # integers and booleans become float32, as under jax.nn.gelu
    if not jnp.issubdtype(a.dtype, jnp.floating):
        a = a.astype(jnp.float32)
    return _gelu_erf(a)


def gelu(x, approximate=False, name=None):
    if approximate:
        return apply(lambda a: jax.nn.gelu(a, approximate=True), x, op_name="gelu")
    return apply(_gelu_exact, x, op_name="gelu")


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(lambda a: jax.nn.leaky_relu(a, negative_slope), x, op_name="leaky_relu")


def prelu(x, weight, data_format="NCHW", name=None):
    def _f(a, w):
        if w.size == 1:
            return jnp.where(a > 0, a, w.reshape(()) * a)
        # per-channel: broadcast along channel axis
        ch_axis = 1 if data_format[1] == "C" else a.ndim - 1
        shape = [1] * a.ndim
        shape[ch_axis] = w.size
        return jnp.where(a > 0, a, w.reshape(shape) * a)

    return apply(_f, x, weight, op_name="prelu")


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=False, name=None):
    if training:
        from ...base import random as _random

        def _f(a):
            r = jax.random.uniform(_random.next_key(), a.shape, jnp.float32, lower, upper)
            return jnp.where(a >= 0, a, a * r.astype(a.dtype))

        return apply(_f, x, op_name="rrelu")
    mid = (lower + upper) / 2.0
    return leaky_relu(x, mid)


def hardshrink(x, threshold=0.5, name=None):
    return apply(
        lambda a: jnp.where(jnp.abs(a) > threshold, a, jnp.zeros((), a.dtype)),
        x,
        op_name="hardshrink",
    )


def softshrink(x, threshold=0.5, name=None):
    return apply(
        lambda a: jnp.where(a > threshold, a - threshold, jnp.where(a < -threshold, a + threshold, jnp.zeros((), a.dtype))),
        x,
        op_name="softshrink",
    )


def hardtanh(x, min=-1.0, max=1.0, name=None):  # noqa: A002
    return apply(lambda a: jnp.clip(a, min, max), x, op_name="hardtanh")


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return apply(lambda a: jnp.clip(a * slope + offset, 0.0, 1.0), x, op_name="hardsigmoid")


def hardswish(x, name=None):
    return apply(lambda a: a * jnp.clip(a + 3.0, 0.0, 6.0) / 6.0, x, op_name="hardswish")


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(
        lambda a: jnp.where(a * beta > threshold, a, jax.nn.softplus(a * beta) / beta),
        x,
        op_name="softplus",
    )


def swish(x, name=None):
    return silu(x)


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    return apply(
        lambda a: jnp.where(a > threshold, a, jnp.asarray(value, a.dtype)),
        x,
        op_name="thresholded_relu",
    )


def softmax(x, axis=-1, dtype=None, name=None):
    def _f(a):
        if dtype is not None:
            from ...base import dtype as _dt

            a = a.astype(_dt.canonical_dtype(dtype))
        return jax.nn.softmax(a, axis=axis)

    return apply(_f, x, op_name="softmax")


def softmax_(x, axis=-1, dtype=None, name=None):
    return x._inplace_from(softmax(x, axis=axis, dtype=dtype))


def log_softmax(x, axis=-1, dtype=None, name=None):
    def _f(a):
        if dtype is not None:
            from ...base import dtype as _dt

            a = a.astype(_dt.canonical_dtype(dtype))
        return jax.nn.log_softmax(a, axis=axis)

    return apply(_f, x, op_name="log_softmax")


def glu(x, axis=-1, name=None):
    def _f(a):
        a1, a2 = jnp.split(a, 2, axis=axis)
        return a1 * jax.nn.sigmoid(a2)

    return apply(_f, x, op_name="glu")


def maxout(x, groups, axis=1, name=None):
    def _f(a):
        ax = axis % a.ndim
        c = a.shape[ax]
        shape = list(a.shape)
        shape[ax : ax + 1] = [c // groups, groups]
        return jnp.max(a.reshape(shape), axis=ax + 1)

    return apply(_f, x, op_name="maxout")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    from ...base import random as _random

    def _f(a):
        u = jax.random.uniform(
            _random.next_key(), a.shape, jnp.float32, 1e-10, 1.0 - 1e-10
        ).astype(a.dtype)
        g = -jnp.log(-jnp.log(u))
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y).at[...].set(0)
            y_hard = jnp.where(
                jnp.arange(y.shape[axis]).reshape([-1 if i == (axis % y.ndim) else 1 for i in range(y.ndim)]) == idx,
                jnp.ones((), y.dtype),
                jnp.zeros((), y.dtype),
            )
            y = y_hard - jax.lax.stop_gradient(y) + y
        return y

    return apply(_f, x, op_name="gumbel_softmax")


# in-place activation variants (functional rebinding, ref: the
# `@inplace_apis_in_dygraph_only` activations in nn/functional/activation.py)
def elu_(x, alpha=1.0, name=None):
    return x._inplace_from(elu(x, alpha))


def hardtanh_(x, min=-1.0, max=1.0, name=None):  # noqa: A002
    return x._inplace_from(hardtanh(x, min, max))


def leaky_relu_(x, negative_slope=0.01, name=None):
    return x._inplace_from(leaky_relu(x, negative_slope))


def thresholded_relu_(x, threshold=1.0, value=0.0, name=None):
    return x._inplace_from(thresholded_relu(x, threshold, value))

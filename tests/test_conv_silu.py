"""``ops/conv_silu.py`` (interpret mode, CPU) against the shifted products
it computes: forward and the three gradients (the taps', the bias's and
the input's) over several blocks of the sequence and of the channels, in
float32 (only the order of sums differs: 1e-5 of each array's largest
entry) and in bfloat16 (a hundredth).

``conftest._SLOW_FILES`` does not list this file: it guards new code.
"""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.conv_silu import _blocks, conv_silu

NAMES = ("x", "w", "b")


def shifted(x, w, b):
    """``silu(b + sum_j w_j x_(t - (taps - 1 - j)))``, zeros before the
    sequence."""
    taps, s = w.shape[0], x.shape[1]
    p = b + sum(jnp.pad(x, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :s] * w[j]
                for j in range(taps))
    return p * jax.nn.sigmoid(p)


def _inputs(s, c, bsz=2, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(s + c), 4)
    x = jax.random.normal(ks[0], (bsz, s, c))
    w = jax.random.normal(ks[1], (4, c)) * 0.5
    b = jax.random.normal(ks[2], (c,)) * 0.5
    g = jax.random.normal(ks[3], (bsz, s, c))
    return tuple(v.astype(dtype) for v in (x, w, b)), g


def _close(got, want, tol):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) <= \
        tol * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 0.01)])
def test_forward_and_gradients_across_blocks(dtype, tol):
    # two blocks of 256 rows, five blocks of 128 columns (640 has no
    # larger divisor of whole registers up to 512)
    args, g = _inputs(512, 640, dtype=dtype)
    assert _blocks(512, 640, 4) == (256, 128)
    wide = tuple(v.astype(jnp.float32) for v in args)
    y = conv_silu(*args)
    assert y.dtype == dtype and _close(y, shifted(*wide), tol)
    got = jax.grad(lambda *a: jnp.sum(conv_silu(*a).astype(jnp.float32) * g),
                   argnums=(0, 1, 2))(*args)
    want = jax.grad(lambda *a: jnp.sum(shifted(*a) * g),
                    argnums=(0, 1, 2))(*wide)
    for name, u, v in zip(NAMES, got, want):
        assert u.shape == v.shape and u.dtype == dtype
        assert _close(u, v, 2 * tol), name


def test_the_bias_is_inside_the_silu():
    (x, w, b), _ = _inputs(64, 128, bsz=1)
    zero = jnp.zeros_like(b)
    assert not _close(conv_silu(x, w, b), conv_silu(x, w, zero), 1e-3)
    assert not _close(conv_silu(x, w, b), conv_silu(x, w, zero) + b, 1e-3)


def test_the_cells_channels_fall_into_seventeen_blocks():
    # 4096 + 2 x 128 channels: 17 x 256
    assert _blocks(16384, 4352, 4) == (256, 256)


@pytest.mark.parametrize("s, c, taps, message", [
    (64, 100, 4, "no multiple of 128 lanes"),
    (100, 128, 4, "no multiple of a block"),
    (64, 128, 10, "taps reach past"),
])
def test_what_is_not_built_is_an_error(s, c, taps, message):
    with pytest.raises(ValueError, match=message):
        conv_silu(jnp.zeros((1, s, c)), jnp.zeros((taps, c)), jnp.zeros((c,)))


def test_mismatched_arguments_are_errors():
    with pytest.raises(ValueError, match="channels"):
        conv_silu(jnp.zeros((1, 64, 128)), jnp.zeros((4, 256)),
                  jnp.zeros((128,)))

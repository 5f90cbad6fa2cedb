"""``ops/mamba2_ssd.py`` (interpret mode, CPU) against the recurrence it
computes, token by token: forward and all six gradients (``dA``, ``d dt``
and ``dD`` among them), over several chunks, several blocks of the
sequence and several blocks of heads, B and C shared by every head, with
a head that forgets inside a chunk beside one whose state outlives the
sequence. A scan that DROPS its carried state at each chunk's start — the
one fault a chunked scan invites — must fail the same tolerance.

Tolerances: kernel and recurrence both run in float32 here, so only the
order of sums differs: 1e-4 of each array's largest entry (the readings
are 1e-6 to 1e-5). bfloat16 inputs are held to a hundredth.

``conftest._SLOW_FILES`` does not list this file: it guards new code.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import mamba2_ssd
from paddle_tpu.ops.mamba2_ssd import CHUNK, ssd

TOL = 1e-4
NAMES = ("x", "dt", "a", "b", "c", "d")
HEADS, P, N = 4, 64, 128
# exp(dt A) a token: from a head that keeps 0.9995 to one that keeps 0.6
RATES = jnp.array([-0.005, -0.3, -1.0, -5.0])


def recurrence(x, dt, a, b, c, d):
    """``S <- exp(dt_t a) S + dt_t x_t b_t^T; y_t = S c_t + d x_t`` per
    head, state [P, N] from zero; b and c are every head's; x [B, S, H
    P], heads side by side."""
    bsz, s, h = dt.shape
    p = x.shape[2] // h
    x = x.reshape(bsz, s, h, p)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", state, c_t, precision="highest")
        return state, y + d[None, :, None] * x_t

    xs = [jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)]
    zero = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, zero, xs)[1], 0, 1).reshape(
        bsz, s, h * p)


def dropped_state(x, dt, a, b, c, d):
    """The kernel with every chunk started from a zero state: each chunk
    run as a sequence of its own."""
    bsz, s = x.shape[:2]
    apart = [v.reshape(bsz * s // CHUNK, CHUNK, *v.shape[2:])
             for v in (x, dt, b, c)]
    return ssd(apart[0], apart[1], a, apart[2], apart[3], d).reshape(x.shape)


def _inputs(s, bsz=2, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed + s), 6)
    x = jax.random.normal(ks[0], (bsz, s, HEADS * P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, HEADS)) - 2.0)
    b = jax.random.normal(ks[2], (bsz, s, N)) / np.sqrt(N)
    c = jax.random.normal(ks[3], (bsz, s, N))
    d = jax.random.normal(ks[4], (HEADS,))
    w = jax.random.normal(ks[5], (bsz, s, HEADS * P))
    return tuple(v.astype(dtype) for v in (x, dt, RATES, b, c, d)), w


@pytest.fixture
def small_steps(monkeypatch):
    """Two chunks a block of the sequence and two heads a block of heads:
    512 tokens of 4 heads then cross chunk, block and head-block borders
    (the launchers are jitted: their caches forget the real constants)."""
    monkeypatch.setattr(mamba2_ssd, "_BLOCK", 2 * CHUNK)
    monkeypatch.setattr(mamba2_ssd, "_HEADS", 2)
    for f in (mamba2_ssd._ssd_fwd, mamba2_ssd._ssd_bwd):
        f.clear_cache()
    yield
    for f in (mamba2_ssd._ssd_fwd, mamba2_ssd._ssd_bwd):
        f.clear_cache()


def _close(got, want, tol=TOL):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) <= \
        tol * float(jnp.max(jnp.abs(want)))


def test_forward_and_every_gradient_across_chunk_block_and_head_borders(
        small_steps):
    args, w = _inputs(4 * CHUNK)
    assert mamba2_ssd._block(4 * CHUNK) == 2 * CHUNK
    assert mamba2_ssd._heads(HEADS, P) == 2
    assert _close(ssd(*args), recurrence(*args))
    every = tuple(range(6))
    got = jax.grad(lambda *a: jnp.sum(ssd(*a) * w), argnums=every)(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                    argnums=every)(*args)
    for name, g, r in zip(NAMES, got, want):
        assert g.shape == r.shape and _close(g, r), name
    # a scan that drops its carry fails the same tolerance, forward and in
    # every gradient but D's own (D x never meets the state)
    assert not _close(dropped_state(*args), recurrence(*args))
    lost = jax.grad(lambda *a: jnp.sum(dropped_state(*a) * w),
                    argnums=every)(*args)
    for name, g, r in zip(NAMES, lost, want):
        assert _close(g, r) == (name == "d"), name


def test_the_real_steps_hold_eight_heads_and_512_tokens():
    assert (mamba2_ssd._block(16384), mamba2_ssd._heads(64, 64)) == (512, 8)
    assert (mamba2_ssd._block(384), mamba2_ssd._heads(2, 64)) == (128, 2)
    args, _ = _inputs(CHUNK, bsz=1)
    assert _close(ssd(*args), recurrence(*args))


def test_b_and_c_are_shared_by_every_head():
    """One group: a head's output is the recurrence of ITS x, dt and rate
    under the one B and C, whatever the other heads hold."""
    (x, dt, a, b, c, d), _ = _inputs(2 * CHUNK, bsz=1)
    whole = ssd(x, dt, a, b, c, d)
    pair = ssd(x[:, :, 2 * P:], dt[:, :, 2:], a[2:], b, c, d[2:])
    assert _close(whole[:, :, 2 * P:], pair, 1e-6)
    assert not _close(whole[:, :, :2 * P], pair)


def test_bfloat16_inputs(small_steps):
    args, w = _inputs(4 * CHUNK, dtype=jnp.bfloat16)
    # y's cotangent reaches the kernel in bfloat16: the recurrence gets
    # the same numbers (the rounding of w alone moves a head's dA, a sum
    # that all but cancels, by 2%)
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    wide = tuple(v.astype(jnp.float32) for v in args)
    y = ssd(*args)
    assert y.dtype == jnp.bfloat16 and _close(y, recurrence(*wide), 0.01)
    got = jax.grad(lambda *a: jnp.sum(ssd(*a).astype(jnp.float32) * w),
                   argnums=tuple(range(6)))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                    argnums=tuple(range(6)))(*wide)
    for name, g, r in zip(NAMES, got, want):
        assert g.dtype == jnp.bfloat16 and _close(g, r, 0.02), name


def test_a_fast_head_underflows_harmlessly():
    """``exp(cs_last)`` of a head that forgets inside a chunk is zero and
    nothing is divided by it."""
    (x, dt, _, b, c, d), w = _inputs(2 * CHUNK, bsz=1)
    a = jnp.array([-0.01, -50.0, -400.0, -2000.0])
    assert float(jnp.exp(jnp.sum(dt[0, :CHUNK, 3]) * a[3])) == 0.0
    y = ssd(x, dt, a, b, c, d)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert _close(y, recurrence(x, dt, a, b, c, d))
    grads = jax.grad(lambda *v: jnp.sum(ssd(*v) * w),
                     argnums=tuple(range(6)))(x, dt, a, b, c, d)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


@pytest.mark.parametrize("shape, message", [
    ((1, 200, 4, 64), "no multiple of the chunk"),
    ((1, 128, 4, 32), "a head of 32 is not built"),
    ((1, 128, 3, 64), "no whole pairs"),
])
def test_what_is_not_built_is_an_error(shape, message):
    bsz, s, h, p = shape
    x = jnp.zeros((bsz, s, h * p))
    with pytest.raises(ValueError, match=message):
        ssd(x, jnp.ones((bsz, s, h)), -jnp.ones((h,)), jnp.zeros((bsz, s, N)),
            jnp.zeros((bsz, s, N)), jnp.ones((h,)))


def test_mismatched_arguments_are_errors():
    (x, dt, a, b, c, d), _ = _inputs(CHUNK, bsz=1)
    with pytest.raises(ValueError, match="heads"):
        ssd(x, dt[:, :, :2], a, b, c, d)
    with pytest.raises(ValueError, match="one group"):
        ssd(x, dt, a, b, c[:, :, :64], d)

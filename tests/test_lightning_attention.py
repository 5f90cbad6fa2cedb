"""``ops/lightning_attention.py`` (interpret mode, CPU) against the
recurrence it computes, token by token: forward and all three gradients,
over several chunks and several grid blocks, with a head that forgets
inside sixteen tokens (``lam`` 0.43) beside one whose state lives for a
thousand (0.996). A scan that DROPS its carried state at each chunk's
start — the one fault a chunked scan invites — must fail the same
tolerance.

Tolerances: kernel and recurrence both run in float32 here, so only the
order of sums differs: 1e-4 of each array's largest entry (the readings
are 1e-6 to 1e-5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.lightning_attention import (CHUNK, alibi_slopes,
                                                lightning_attention)

TOL = 1e-4
NAMES = ("q", "k", "v")
HEADS = 4
# the fastest and the slowest of MiniCPM-SALA's 32 heads, and two between
SLOPES = jnp.array([2.0 ** -0.25, 0.1, 2.0 ** -8.0, 0.02])


def recurrence(q, k, v, slopes=SLOPES):
    """``S <- lam S + k_t v_t^T; o_t = S^T q_t / sqrt(d)`` per head, state
    from zero."""
    b, _, h, d = q.shape
    lam = jnp.exp(-slopes)[None, :, None, None]

    def step(state, x):
        q_t, k_t, v_t = x
        state = lam * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision="highest") / np.sqrt(d)

    xs = [jnp.moveaxis(a, 1, 0) for a in (q, k, v)]
    zero = jnp.zeros((b, h, d, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, zero, xs)[1], 0, 1)


def kernel(q, k, v):
    return lightning_attention(q, k, v, SLOPES)


def dropped_state(q, k, v):
    """The kernel with every chunk started from a zero state: each chunk
    run as a sequence of its own."""
    b, s = q.shape[:2]
    apart = [a.reshape(b * s // CHUNK, CHUNK, *a.shape[2:])
             for a in (q, k, v)]
    return kernel(*apart).reshape(v.shape)


def _inputs(s, b=2, d=128):
    ks = jax.random.split(jax.random.key(s), 4)
    q, k, v, w = (jax.random.normal(key, (b, s, HEADS, d)) for key in ks)
    return (q, k, v), w


def _worst(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# 256: two chunks in one grid block; 384: three blocks of one chunk;
# 1024: two blocks of four chunks
@pytest.fixture(scope="module", params=[256, 384, 1024],
                ids=["s256", "s384", "s1024"])
def case(request):
    args, weight = _inputs(request.param, b=1 if request.param > 512 else 2)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a) * weight))
    both = lambda fn: jax.value_and_grad(loss(fn), argnums=(0, 1, 2))
    return {"args": args, "want_o": recurrence(*args),
            "want": both(recurrence)(*args)[1], "both": both}


def test_forward_is_the_recurrence(case):
    got = kernel(*case["args"])
    assert got.shape == case["want_o"].shape
    assert _worst(got, case["want_o"]) <= TOL


@pytest.mark.parametrize("leaf", range(3), ids=NAMES)
def test_every_gradient_is_the_recurrences(case, leaf):
    if "got" not in case:
        case["got"] = case["both"](kernel)(*case["args"])[1]
    got, want = case["got"][leaf], case["want"][leaf]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _worst(got, want) <= TOL, NAMES[leaf]


def test_a_scan_that_drops_its_state_fails_the_same_tolerance(case):
    assert _worst(dropped_state(*case["args"]), case["want_o"]) > 100 * TOL
    got = case["both"](dropped_state)(*case["args"])[1]
    assert all(_worst(a, b) > 100 * TOL for a, b in zip(got, case["want"]))


@pytest.mark.parametrize("head", [0, 2], ids=["fast", "slow"])
def test_a_fast_head_forgets_and_a_slow_head_carries(head):
    """Head 0 (lam 0.43: 2e-12 after 32 tokens) is the same with or
    without the state that enters a chunk from its 32nd token on; head 2
    (lam 0.996) is far from it there: the carry is what the slow heads
    are for."""
    (q, k, v), _ = _inputs(256, b=1)
    late = lambda o: o.reshape(2, CHUNK, HEADS, -1)[:, 32:, head]
    gap = _worst(late(dropped_state(q, k, v)), late(kernel(q, k, v)))
    assert gap <= TOL if head == 0 else gap > 0.1


def test_the_slopes_are_alibis():
    s = np.asarray(alibi_slopes(32))
    lam = np.exp(-s)
    assert abs(lam[0] - 0.431) < 1e-3 and abs(lam[-1] - 0.9961) < 1e-4
    assert abs(s[0] - 2.0 ** -0.25) < 1e-7 and abs(s[-1] - 2.0 ** -8) < 1e-9


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_an_error():
    (q, k, v), _ = _inputs(128, b=1)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        kernel(q[:, :100], k[:, :100], v[:, :100])
    with pytest.raises(ValueError, match="heads"):
        lightning_attention(q, k, v, SLOPES[:3])
    # one chunk alone, and bfloat16 operands against the float32 rule
    assert _worst(kernel(q, k, v), recurrence(q, k, v)) <= TOL
    low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    got = kernel(*low).astype(jnp.float32)
    assert _worst(got, recurrence(*(a.astype(jnp.float32) for a in low))) \
        <= 2e-2

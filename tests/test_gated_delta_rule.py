"""``ops/gated_delta_rule.py`` (interpret mode, CPU) against the
recurrence it computes, token by token: forward and all five gradients,
at two and four chunks with two value heads to a key head, and at two
chunks with one and with four (a grid step holds a key head's value
heads, however many), with decays drawn so that what survives a chunk
spans 0.05 to 0.99 over the heads.
A scan that DROPS its carried state at each chunk's start — the one
fault a chunked scan invites — must fail the same tolerance. The
inverse of a chunk's triangle is held against ``jnp.linalg.inv`` and to
its count of products.

Tolerances: kernel and recurrence both run in float32 here, so only the
order of sums differs: 1e-4 of each array's largest entry (the readings
are 1e-6 to 1e-5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.gated_delta_rule import (CHUNK, _inverse,
                                             gated_delta_rule)

TOL = 1e-4
NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    """``S <- exp(g_t) S; u = S^T k_t; delta = beta_t (v_t - u); S <- S +
    k_t delta^T; o_t = S^T q_t`` per value head, state from zero."""
    b, hv = q.shape[0], v.shape[2]
    rep = hv // q.shape[2]
    q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision="highest")
        delta = b_t[..., None] * (v_t - u)
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision="highest")

    xs = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)]
    zero = jnp.zeros((b, hv, q.shape[-1], v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, zero, xs)[1], 0, 1)


def dropped_state(q, k, v, g, beta):
    """The kernel with every chunk started from a zero state: each chunk
    run as a sequence of its own."""
    b, s = q.shape[:2]
    apart = [a.reshape(b * s // CHUNK, CHUNK, *a.shape[2:])
             for a in (q, k, v, g, beta)]
    return gated_delta_rule(*apart).reshape(v.shape)


def _inputs(s, b=2, hk=2, hv=4, d=128):
    ks = jax.random.split(jax.random.key(s), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, s, hk, d))) / np.sqrt(d)
    k = unit(jax.random.normal(ks[1], (b, s, hk, d)))
    v = jax.random.normal(ks[2], (b, s, hv, d))
    # what is left of a state after a chunk: 0.05 on head 0 to 0.99
    over_chunk = jnp.linspace(np.log(0.05), np.log(0.99), hv)
    g = over_chunk / CHUNK * jax.random.uniform(
        ks[3], (b, s, hv), minval=0.5, maxval=1.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, hv)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, s, hv, d))


def _worst(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# (sequence, key heads, value heads): a key head's group of 2, 1 and 4
CASES = {"s128": (128, 2, 4), "s256": (256, 2, 4), "group1": (128, 2, 2),
         "group4": (128, 1, 4)}


@pytest.fixture(scope="module", params=CASES.values(), ids=CASES.keys())
def case(request):
    s, hk, hv = request.param
    args, weight = _inputs(s, hk=hk, hv=hv)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a) * weight))
    both = lambda fn: jax.value_and_grad(loss(fn), argnums=(0, 1, 2, 3, 4))
    return {"args": args, "want_o": recurrence(*args),
            "want": both(recurrence)(*args)[1], "both": both}


def test_forward_is_the_recurrence(case):
    got = gated_delta_rule(*case["args"])
    assert got.shape == case["want_o"].shape
    assert _worst(got, case["want_o"]) <= TOL
    g = np.asarray(case["args"][3])
    left = np.exp(g.reshape(2, -1, CHUNK, g.shape[-1]).sum(2))
    assert left.min() < 0.08 and left.max() > 0.98     # the decays' spread


@pytest.mark.parametrize("leaf", range(5), ids=NAMES)
def test_every_gradient_is_the_recurrences(case, leaf):
    if "got" not in case:
        case["got"] = case["both"](gated_delta_rule)(*case["args"])[1]
    got, want = case["got"][leaf], case["want"][leaf]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _worst(got, want) <= TOL, NAMES[leaf]


def test_a_scan_that_drops_its_state_fails_the_same_tolerance(case):
    assert _worst(dropped_state(*case["args"]), case["want_o"]) > 100 * TOL
    got = case["both"](dropped_state)(*case["args"])[1]
    assert all(_worst(a, b) > 100 * TOL for a, b in zip(got, case["want"]))


@pytest.mark.parametrize("n, products", [(64, 6), (16, 4)])
def test_the_inverse_of_a_chunks_triangle(n, products):
    """``_inverse`` is (I + a)^-1 for a strictly lower triangular ``a``
    of a chunk's scale (entries of beta k.k' Gam: below one), in the
    products its factors need when the running product stays one factor
    behind the power: six at 64, not ten."""
    a = jnp.tril(jax.random.uniform(jax.random.key(n), (n, n), minval=-1.0),
                 -1) / np.sqrt(n)
    want = jnp.linalg.inv(jnp.eye(n) + a)
    got = _inverse(a, jnp.float32)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    assert np.allclose(got @ (jnp.eye(n) + a), jnp.eye(n), atol=1e-5)
    eqns = jax.make_jaxpr(lambda x: _inverse(x, jnp.float32))(a).jaxpr.eqns
    assert sum(e.primitive.name == "dot_general" for e in eqns) == products


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_an_error():
    (q, k, v, g, beta), _ = _inputs(128, b=1)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        gated_delta_rule(q[:, :100], k[:, :100], v[:, :100], g[:, :100],
                         beta[:, :100])
    with pytest.raises(ValueError, match="value heads"):
        gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3])
    # one chunk alone, and three (a block of 64 tokens a grid step)
    for s in (64, 192):
        cut = [a[:, :s] for a in (q, k, v, g, beta)]
        assert _worst(gated_delta_rule(*cut), recurrence(*cut)) <= TOL

"""``ops/gdn_inputs.py`` (interpret mode, CPU) against the plain float32
form of the same equations, written here: q, k, v and the gradients to
``qkv`` and ``w``, over sequences of one, two and five blocks, two and
one value heads to a key head, four and two taps, float32 and bfloat16.
A form that DROPS the rows before a block — each block convolved as a
sequence of its own, the one fault a blocked convolution invites — must
fail the same tolerance, and so must a case whose only non-zero rows
straddle a block's edge if either kernel loses them.

Tolerances: with float32 inputs kernel and plain form differ in the
order of sums alone: 1e-4 of each array's largest entry (the readings
are 2e-7). With bfloat16 inputs both round their results to bfloat16, so
a sum that lands the other side of a rounding boundary is one unit in
the last place of eight bits: 2^-7 of the largest entry."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.layer_metrics import gdn_fwd_roofline
from paddle_tpu.ops import gdn_inputs
from paddle_tpu.ops.gdn_inputs import conv_silu_l2norm

TOL = {"float32": 1e-4, "bfloat16": 2.0 ** -7}
NAMES = ("q", "k", "v", "d_qkv", "d_w")
D = 128


def plain(qkv, w, hk, hv, dk, dv):
    """The module docstring's equations on whole arrays, in float32."""
    b, s, _ = qkv.shape
    taps = w.shape[0]
    x, w = qkv.astype(jnp.float32), w.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    p = sum(padded[:, j:j + s] * w[j] for j in range(taps))
    a = p * jax.nn.sigmoid(p)
    q, k, v = jnp.split(a, [hk * dk, 2 * hk * dk], axis=-1)

    def unit(x):
        x = x.reshape(b, s, hk, dk)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return unit(q) / math.sqrt(dk), unit(k), v.reshape(b, s, hv, dv)


def apart(fn, rows):
    """``fn`` with every block of ``rows`` tokens run as a sequence of its
    own: the rows before a block are dropped."""
    def dropped(qkv, w, hk, hv, dk, dv):
        b, s, c = qkv.shape
        out = fn(qkv.reshape(b * s // rows, rows, c), w, hk, hv, dk, dv)
        return tuple(o.reshape(b, s, *o.shape[2:]) for o in out)
    return dropped


def _rows(s):
    return gdn_inputs._blocks(s, 8 * D, 2 * D, D, 4)[0]


def _results(fn, qkv, w, weights, heads):
    """-> (q, k, v, d qkv, d w) of ``fn``, float32, under the loss
    ``sum(out * weights)``."""
    def loss(qkv, w):
        out = fn(qkv, w, *heads)
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for o, c in zip(out, weights)), out

    (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(qkv, w)
    return [np.asarray(a.astype(jnp.float32)) for a in (*out, *grads)]


def _make(s, hk, hv, taps, dtype, seed=0, b=2):
    ks = jax.random.split(jax.random.key(seed), 5)
    c = 2 * hk * D + hv * D
    qkv = jax.random.normal(ks[0], (b, s, c)).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (taps, c))).astype(dtype)
    # the cotangents as the kernels meet them: in the inputs' type
    weights = [jax.random.normal(k, (b, s, h, D)).astype(dtype).astype(
        jnp.float32) for k, h in zip(ks[2:], (hk, hk, hv))]
    return qkv, w, weights, (hk, hv, D, D)


def _worst(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


# (sequence, key heads, value heads, taps, type): 64 is one block, 512 two
# of 256, 320 five of 64
CASES = {
    "one-block": (64, 2, 4, 4, "float32"),
    "two-blocks": (512, 2, 4, 4, "float32"),
    "five-blocks": (320, 2, 4, 4, "float32"),
    "one-block-bf16": (64, 2, 4, 4, "bfloat16"),
    "two-blocks-bf16": (512, 2, 4, 4, "bfloat16"),
    "five-blocks-bf16": (320, 2, 4, 4, "bfloat16"),
    "heads-2-2": (320, 2, 2, 4, "float32"),
    "two-taps": (320, 2, 4, 2, "float32"),
    "two-taps-heads-2-2-bf16": (512, 2, 2, 2, "bfloat16"),
}


@pytest.fixture(scope="module", params=CASES.values(), ids=CASES.keys())
def case(request):
    s, hk, hv, taps, dtype = request.param
    qkv, w, weights, heads = _make(s, hk, hv, taps, jnp.dtype(dtype))
    want = _results(plain, qkv.astype(jnp.float32), w.astype(jnp.float32),
                    weights, heads)
    # the plain form's results stored as the kernels store theirs
    return {"tol": TOL[dtype],
            "want": [np.asarray(jnp.asarray(a).astype(dtype).astype(
                jnp.float32)) for a in want],
            "got": _results(conv_silu_l2norm, qkv, w, weights, heads)}


@pytest.mark.parametrize("leaf", range(5), ids=NAMES)
def test_the_kernels_are_the_plain_form(case, leaf):
    got, want = case["got"][leaf], case["want"][leaf]
    assert got.shape == want.shape
    assert _worst(got, want) <= case["tol"], NAMES[leaf]


def test_results_come_in_the_inputs_type_and_shapes():
    qkv, w, _, heads = _make(64, 2, 4, 4, jnp.bfloat16, b=1)
    q, k, v = conv_silu_l2norm(qkv, w, *heads)
    assert (q.shape, k.shape, v.shape) == (
        (1, 64, 2, D), (1, 64, 2, D), (1, 64, 4, D))
    assert {a.dtype for a in (q, k, v)} == {jnp.dtype(jnp.bfloat16)}
    dqkv, dw = jax.grad(lambda *a: sum(
        o.astype(jnp.float32).sum() for o in conv_silu_l2norm(*a, *heads)),
        (0, 1))(qkv, w)
    assert (dqkv.shape, dqkv.dtype) == (qkv.shape, qkv.dtype)
    assert (dw.shape, dw.dtype) == (w.shape, w.dtype)
    # k is a unit vector a head, q one over sqrt(d_k)
    norm = np.linalg.norm(np.asarray(k.astype(jnp.float32)), axis=-1)
    assert np.abs(norm - 1).max() < 2e-2


@pytest.mark.parametrize("leaf", range(5), ids=NAMES)
def test_rows_that_straddle_a_blocks_edge(leaf, straddle):
    """Inputs and cotangents are zero but for rows 253 .. 258 of 512 (two
    blocks of 256): what rows 256 .. 258 read of 253 .. 255 is the rows
    before a block, what ``d qkv`` at 253 .. 255 reads of 256 .. 258 is
    the carried ``dp``."""
    got, want = straddle["got"][leaf], straddle["want"][leaf]
    assert _worst(got, want) <= TOL["float32"], NAMES[leaf]
    assert _worst(straddle["dropped"][leaf], want) > 100 * TOL["float32"]


@pytest.fixture(scope="module")
def straddle():
    assert _rows(512) == 256
    qkv, w, weights, heads = _make(512, 2, 4, 4, jnp.float32, seed=1)
    live = (jnp.arange(512) >= 253) & (jnp.arange(512) < 259)
    qkv = qkv * live[None, :, None]
    weights = [c * live[None, :, None, None] for c in weights]
    run = lambda fn: _results(fn, qkv, w, weights, heads)
    return {"got": run(conv_silu_l2norm), "want": run(plain),
            "dropped": run(apart(conv_silu_l2norm, 256))}


@pytest.mark.parametrize("s", [512, 320], ids=["two-blocks", "five-blocks"])
def test_a_form_that_drops_the_rows_before_a_block_fails_the_tolerance(s):
    qkv, w, weights, heads = _make(s, 2, 4, 4, jnp.float32, seed=2)
    want = _results(plain, qkv, w, weights, heads)
    for fn in (plain, conv_silu_l2norm):
        got = _results(apart(fn, _rows(s)), qkv, w, weights, heads)
        assert all(_worst(a, b) > 100 * TOL["float32"]
                   for a, b in zip(got, want)), fn


def test_shapes_the_kernels_cannot_take_are_errors():
    qkv, w, _, heads = _make(128, 2, 4, 4, jnp.float32, b=1)
    with pytest.raises(ValueError, match="head of 64"):
        conv_silu_l2norm(qkv[..., :512], w[:, :512], 2, 4, 64, 64)
    with pytest.raises(ValueError, match="sequence 100"):
        conv_silu_l2norm(qkv[:, :100], w, *heads)
    with pytest.raises(ValueError, match="columns"):
        conv_silu_l2norm(qkv, w[:, :512], *heads)
    with pytest.raises(ValueError, match="sequence 100"):
        jax.grad(lambda x: conv_silu_l2norm(x, w, *heads)[0].sum())(
            qkv[:, :100])


@pytest.mark.parametrize("name", gdn_inputs.KERNELS)
@pytest.mark.parametrize("way", ["fwd", "bwd"])
def test_the_recurrences_readers_do_not_time_these_kernels(name, way):
    """``gdn_fwd_roofline`` / ``gdn_bwd_roofline`` sum the device time of
    every instruction whose name holds ``gdn_fwd`` / ``gdn_bwd``."""
    shown = f"%{name}.1 = (bf16[1,16384,2048]{{2,1,0}}) custom-call("
    for pattern in gdn_fwd_roofline.patterns(way):
        assert not re.search(pattern, shown)
    assert re.search(gdn_fwd_roofline.patterns(way)[0],
                     f"%gdn_{way}.1 = (bf16[1,16384,4096]) custom-call(")


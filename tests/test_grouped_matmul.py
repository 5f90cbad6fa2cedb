"""``ops.grouped_matmul``: the forward and both backward forms against
the jnp reference with the kernels interpreted, over the group layouts
that break a tiled grouped matmul (empty groups, one group holding
everything, edges inside a tile), the tables the kernels find their
work by, and the dropless layer built on it.

That the kernels compile for the v5e is in
``tests/test_flash_compiles_for_v5e.py`` (one file describes the chip);
their names in a lowered program in ``tests/test_pallas_kernel_names.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.grouped_matmul import (
    Tiling, _gmm, _tgmm, _visits, gmm_tiling, grouped_matmul,
    grouped_matmul_reference, tgmm_tiling)

# (rows, k, n, group sizes): rows tile by 128 unless no power of two
# down to 128 divides them, then the whole axis is one tile
LAYOUTS = {
    "edges-inside-tiles": (256, 128, 128, [100, 56, 100]),
    "empty-groups": (256, 128, 128, [100, 0, 56, 0, 100]),
    "empty-first-and-last": (256, 128, 256, [0, 130, 126, 0]),
    "one-group-holds-all": (256, 128, 128, [0, 256, 0, 0]),
    "aligned-to-tiles": (384, 128, 128, [128, 256, 0]),
    "one-row-groups": (384, 256, 128, [1, 127, 130, 0, 125, 1]),
    "whole-axis-one-tile": (48, 32, 16, [10, 0, 38]),
    "many-groups-one-tile": (128, 128, 128, [8] * 16),
}


def _case(rows, k, n, sizes, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (rows, k), dtype)
    w = jax.random.normal(ks[1], (len(sizes), k, n), dtype)
    dy = jax.random.normal(ks[2], (rows, n), dtype)
    return x, w, dy, jnp.asarray(sizes, jnp.int32)


def _both(x, w, dy, sizes):
    out, vjp = jax.vjp(lambda x, w: grouped_matmul(x, w, sizes, True), x, w)
    f32 = [a.astype(jnp.float32) for a in (x, w, dy)]
    with jax.default_matmul_precision("highest"):
        ref, rvjp = jax.vjp(
            lambda x, w: grouped_matmul_reference(x, w, sizes), *f32[:2])
        return (out, *vjp(dy)), (ref, *rvjp(f32[2]))


@pytest.mark.parametrize("layout", LAYOUTS.values(), ids=LAYOUTS.keys())
def test_forward_and_both_backward_forms_float32(layout):
    got, ref = _both(*_case(*layout, jnp.float32))
    for g, r, name in zip(got, ref, ("out", "dx", "dw")):
        # float32 against float32, both accumulating in float32: only
        # the order of the sums differs
        scale = float(jnp.abs(r).max())
        assert float(jnp.abs(g - r).max()) <= 1e-5 * scale, name


@pytest.mark.parametrize("layout", ["edges-inside-tiles", "empty-groups",
                                    "one-group-holds-all"])
def test_bfloat16_operands_accumulate_in_float32(layout):
    got, ref = _both(*_case(*LAYOUTS[layout], jnp.bfloat16))
    for g, r, name in zip(got, ref, ("out", "dx", "dw")):
        assert g.dtype == jnp.bfloat16
        # one bfloat16 rounding of the result (2^-9 relative) on sums
        # whose largest is ``scale``: 1% of it leaves room for nothing else
        scale = float(jnp.abs(r).max())
        err = float(jnp.abs(g.astype(jnp.float32) - r).max())
        assert err <= 1e-2 * scale, (name, err, scale)


def test_an_empty_group_gets_a_zero_weight_gradient_not_garbage():
    x, w, dy, sizes = _case(*LAYOUTS["empty-first-and-last"], jnp.float32)
    dw = _tgmm(x, dy, sizes, None, True)
    assert float(jnp.abs(dw[0]).max()) == 0.0
    assert float(jnp.abs(dw[3]).max()) == 0.0
    assert float(jnp.abs(dw[1]).max()) > 0.0


@pytest.mark.parametrize("tiling", [Tiling(128, 64, 128), Tiling(128, 128, 64),
                                    Tiling(256, 128, 128)])
def test_other_tilings_give_the_same_result(tiling):
    """The contraction in steps (an accumulator across grid steps), the
    output in column blocks, a taller row tile: same numbers."""
    x, w, dy, sizes = _case(256, 128, 128, [100, 0, 56, 100], jnp.float32)
    want = _gmm(x, w, sizes, False, None, True)
    assert jnp.allclose(_gmm(x, w, sizes, False, tiling, True), want,
                        rtol=1e-5, atol=1e-4)
    want = _tgmm(x, dy, sizes, None, True)
    assert jnp.allclose(_tgmm(x, dy, sizes, tiling, True), want,
                        rtol=1e-5, atol=1e-4)


def test_visit_tables():
    sizes = jnp.asarray([100, 0, 56, 100], jnp.int32)
    (offsets, gid, tid), count = _visits(sizes, 256, 128, visit_empty=False)
    assert offsets.tolist() == [0, 100, 100, 156, 256]
    n = int(count)
    # group 0 in tile 0; group 2 straddles tiles 0 and 1; group 3 in tile 1
    assert gid[:n].tolist() == [0, 2, 2, 3]
    assert tid[:n].tolist() == [0, 0, 1, 1]
    (_, gid, tid), count = _visits(sizes, 256, 128, visit_empty=True)
    n = int(count)
    assert gid[:n].tolist() == [0, 1, 2, 2, 3]      # the empty group once
    assert all(0 <= t <= 1 for t in tid[:n].tolist())
    # a tile is revisited only by consecutive visits
    seen = tid[:n].tolist()
    assert seen == sorted(seen)
    # never more visits than the tables hold
    assert n <= 256 // 128 + 4 - 1


def test_the_cells_tilings_hold_a_groups_matrix_block_between_visits():
    # at the benchmark cell's shapes the contraction is one step: the
    # block of an expert's matrix is the same for consecutive visits of
    # that expert and is fetched once
    for k, n in ((2048, 4096), (2048, 2048)):
        t = gmm_tiling(4096, k, n, 2)
        assert t.tm == 128 and t.tk == k and n % t.tn == 0
        t = gmm_tiling(4096, n, k, 2)          # the input gradient's
        assert t.tk == n
        t = tgmm_tiling(4096, k, n, 2)
        assert 4 * t.tk * t.tn <= 8 * 1024 * 1024   # the accumulator


def test_no_gradient_reaches_the_group_sizes_and_jit_composes():
    x, w, dy, sizes = _case(128, 128, 128, [30, 98], jnp.float32)

    @jax.jit
    def loss_grads(x, w, sizes):
        return jax.grad(lambda x, w: jnp.sum(
            grouped_matmul(x, w, sizes, True) * dy), (0, 1))(x, w)

    dx, dw = loss_grads(x, w, sizes)
    with jax.default_matmul_precision("highest"):
        rdx, rdw = jax.grad(lambda x, w: jnp.sum(
            grouped_matmul_reference(x, w, sizes) * dy), (0, 1))(x, w)
    assert jnp.allclose(dx, rdx, rtol=1e-5, atol=1e-4)
    assert jnp.allclose(dw, rdw, rtol=1e-5, atol=1e-4)


def test_routed_experts_is_dropless_and_matches_a_dense_loop():
    """``nn.RoutedExperts`` with two choices a token against every expert
    run on every token and kept where chosen."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(3)
    t, h, f, e, k = 64, 32, 16, 4, 2
    layer = nn.RoutedExperts(h, f, e)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((t, h)).astype(np.float32)
    ids = np.stack([rng.permutation(e)[:k] for _ in range(t)]).astype(np.int32)
    gates = rng.uniform(0.1, 1.0, (t, k)).astype(np.float32)
    out = np.asarray(layer(paddle.to_tensor(x), paddle.to_tensor(ids),
                           paddle.to_tensor(gates))._data)
    w_gu, w_dn = np.asarray(layer.w_gu._data), np.asarray(layer.w_dn._data)
    want = np.zeros_like(x)
    for ex in range(e):
        gu = x @ w_gu[ex]
        y = (gu[:, :f] / (1 + np.exp(-gu[:, :f])) * gu[:, f:]) @ w_dn[ex]
        for c in range(k):
            want += np.where((ids[:, c] == ex)[:, None], gates[:, c:c + 1] * y, 0)
    assert np.abs(out - want).max() <= 1e-5 * np.abs(want).max()
    counts = np.asarray(layer.tokens_per_expert._data)
    assert counts.sum() == t * k                 # every pair computed once
    assert (counts == np.bincount(ids.reshape(-1), minlength=e)).all()

"""``ops.grouped_matmul``: the forward and both backward forms against
the jnp reference with the kernels interpreted, over the group layouts
that break a tiled grouped matmul (empty groups, one group holding
everything, edges inside a tile) or its walk over groups (blocks of the
weights fetched a group ahead past empty groups, an accumulator written
by a group's first visit and cast by its last), the tables the kernels
find their work by, and the dropless layer built on it.

That the kernels compile for the v5e is in
``tests/test_flash_compiles_for_v5e.py`` (one file describes the chip);
their names in a lowered program in ``tests/test_pallas_kernel_names.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import grouped_matmul as gm
from paddle_tpu.ops.grouped_matmul import (
    Tiling, _gmm, _next_with_rows, _tgmm, _visits, gmm_tiling,
    grouped_matmul, grouped_matmul_reference, tgmm_tiling, zero_tail)

# (rows, k, n, group sizes): rows tile by 128 (by 256 in the weight
# gradient where that divides them) unless no power of two down to 128
# divides them, then the whole axis is one tile
LAYOUTS = {
    "edges-inside-tiles": (256, 128, 128, [100, 56, 100]),
    "empty-groups": (256, 128, 128, [100, 0, 56, 0, 100]),
    "empty-first-and-last": (256, 128, 256, [0, 130, 126, 0]),
    "one-group-holds-all": (256, 128, 128, [0, 256, 0, 0]),
    "aligned-to-tiles": (384, 128, 128, [128, 256, 0]),
    "one-row-groups": (384, 256, 128, [1, 127, 130, 0, 125, 1]),
    "whole-axis-one-tile": (48, 32, 16, [10, 0, 38]),
    "many-groups-one-tile": (128, 128, 128, [8] * 16),
    # a weight-gradient visit that is its group's first and last at once,
    # beside two groups that share a tile
    "one-visit-groups": (512, 128, 128, [256, 128, 128]),
    # the ledger's load peak of 2.67 (PR 26): one group of two thirds of
    # the rows (a first, middle and last visit of 256), groups of 40 or
    # fewer beside it, two of them inside one tile
    "ledger-skew": (1024, 128, 128, [683, 40, 24, 36, 100, 141]),
    # the fetch ahead runs past empty groups: first, last, two in a row
    "empty-first-last-and-two-in-a-row": (512, 128, 128,
                                          [0, 100, 0, 0, 156, 256, 0]),
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
                                    "one-group-holds-all", "ledger-skew"])
def test_bfloat16_operands_accumulate_in_float32(layout):
    got, ref = _both(*_case(*LAYOUTS[layout], jnp.bfloat16))
    for g, r, name in zip(got, ref, ("out", "dx", "dw")):
        assert g.dtype == jnp.bfloat16
        # one bfloat16 rounding of the result (2^-9 relative) on sums
        # whose largest is ``scale``: 1% of it leaves room for nothing else
        scale = float(jnp.abs(r).max())
        err = float(jnp.abs(g.astype(jnp.float32) - r).max())
        assert err <= 1e-2 * scale, (name, err, scale)


@pytest.mark.parametrize("layout", ["empty-first-and-last",
                                    "empty-first-last-and-two-in-a-row"])
def test_an_empty_group_gets_a_zero_weight_gradient_not_garbage(layout):
    x, w, dy, sizes = _case(*LAYOUTS[layout], jnp.float32)
    dw = _tgmm(x, dy, sizes, None, True)
    for g, rows in enumerate(LAYOUTS[layout][3]):
        assert (float(jnp.abs(dw[g]).max()) > 0.0) == (rows > 0), g


@pytest.mark.parametrize("tiling", [None, Tiling(128, 128, 128)],
                         ids=["visits-of-256", "visits-of-128"])
@pytest.mark.parametrize("layout", ["ledger-skew", "one-visit-groups"])
def test_the_bfloat16_weight_gradient_is_the_float32_sum_rounded_once(
        layout, tiling):
    """A group's first visit writes the accumulator, its last casts
    accumulator plus product into the output: no visit may round to
    bfloat16 on the way."""
    x, w, dy, sizes = _case(*LAYOUTS[layout], jnp.bfloat16)
    got = _tgmm(x, dy, sizes, tiling, True)
    exact = _tgmm(x.astype(jnp.float32), dy.astype(jnp.float32), sizes,
                  tiling, True)
    assert got.dtype == jnp.bfloat16 and exact.dtype == jnp.float32
    assert jnp.array_equal(got, exact.astype(jnp.bfloat16))


@pytest.mark.parametrize("tilings", [
    (Tiling(128, 128, 32), Tiling(128, 64, 128)),
    (Tiling(128, 128, 64), Tiling(128, 128, 64)),
    (Tiling(256, 128, 128), Tiling(256, 128, 128)),
], ids=["contraction-in-steps", "column-blocks", "taller-tile"])
def test_other_tilings_give_the_same_result(tilings):
    """The output in column blocks (``moe_gmm`` carries its ring of
    weight blocks from one column block into the next, past the empty
    groups), the weight gradient's contraction in steps, a taller row
    tile: same numbers."""
    x, w, dy, sizes = _case(*LAYOUTS["empty-first-last-and-two-in-a-row"],
                            jnp.float32)
    for transpose_w, rows in ((False, x), (True, dy)):
        want = _gmm(rows, w, sizes, transpose_w, None, True)
        got = _gmm(rows, w, sizes, transpose_w, tilings[0], True)
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-4), transpose_w
    want = _tgmm(x, dy, sizes, None, True)
    assert jnp.allclose(_tgmm(x, dy, sizes, tilings[1], True), want,
                        rtol=1e-5, atol=1e-4)


# sizes that add up to LESS than the rows: a layer that holds a share of
# the experts sorts the rows of the absent ones last
TAILS = {
    "empty-tail": (256, 128, 128, [100, 56, 100]),
    "all-tail": (256, 128, 128, [0, 0, 0]),
    "a-tile-shared-with-the-tail": (384, 128, 128, [100, 60]),
    "tail-from-a-tile-edge": (384, 128, 128, [128, 0, 128]),
    "empty-groups-before-the-tail": (512, 128, 256, [0, 130, 0, 0]),
    "one-row-then-tail": (256, 128, 128, [0, 1]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layout", TAILS.values(), ids=TAILS.keys())
def test_sizes_that_add_up_to_less_than_the_rows(layout, dtype):
    """Forward, input gradient and weight gradient: the rows past the
    last group are no group's, are not visited, and read as zero through
    ``zero_tail``; the weight gradient holds the groups' rows alone."""
    x, w, dy, sizes = _case(*layout, dtype)
    total = jnp.sum(sizes)

    def run(x, w):
        return zero_tail(grouped_matmul(zero_tail(x, total), w, sizes, True),
                         total)

    out, vjp = jax.vjp(run, x, w)
    f32 = [a.astype(jnp.float32) for a in (x, w, dy)]
    with jax.default_matmul_precision("highest"):
        ref, rvjp = jax.vjp(
            lambda x, w: grouped_matmul_reference(x, w, sizes), *f32[:2])
        want = (ref, *rvjp(f32[2]))
    n = int(total)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for g, r, name in zip((out, *vjp(dy)), want, ("out", "dx", "dw")):
        g = g.astype(jnp.float32)
        assert bool(jnp.isfinite(g).all()), name
        if name != "dw":
            assert not np.asarray(g[n:]).any(), name
            assert not np.asarray(r[n:]).any(), name
        scale = max(float(jnp.abs(r).max()), 1e-30)
        assert float(jnp.abs(g - r).max()) <= tol * scale, name


def test_tail_tiles_are_never_visited():
    sizes = jnp.asarray([100, 60], jnp.int32)
    (offsets, gid, tid), count = _visits(sizes, 1024, 128, visit_empty=False)
    n = int(count)
    assert offsets.tolist() == [0, 100, 160]
    assert (gid[:n].tolist(), tid[:n].tolist()) == ([0, 1, 1], [0, 0, 1])
    (_, gid, tid), count = _visits(sizes, 1024, 128, visit_empty=True,
                                   parts=2)
    n = int(count)          # two tiles a visit: tiles 0-1 for each group
    assert (gid[:n].tolist(), tid[:n].tolist()) == ([0, 1], [0, 0])
    _, count = _visits(jnp.zeros((8,), jnp.int32), 1024, 128,
                       visit_empty=False)
    assert int(count) == 0


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
    # visits of two tiles are counted from the group's first tile: the 683
    # rows' six tiles make three visits, and the fourth group's 36 rows,
    # which straddle tiles 5 and 6, one (tiles of 256 aligned to the
    # array would give it two)
    skew = jnp.asarray(LAYOUTS["ledger-skew"][3], jnp.int32)
    (_, gid, tid), count = _visits(skew, 1024, 128, visit_empty=True, parts=2)
    n = int(count)
    assert gid[:n].tolist() == [0, 0, 0, 1, 2, 3, 4, 5]
    assert tid[:n].tolist() == [0, 2, 4, 5, 5, 5, 6, 6]
    # the order the weights' blocks are fetched in: from each group to the
    # next that has rows (4: none), the last entry the first such group
    assert _next_with_rows(sizes).tolist() == [2, 2, 3, 4, 0]
    empties = jnp.asarray([0, 100, 0, 0, 156, 256, 0], jnp.int32)
    assert _next_with_rows(empties).tolist() == [1, 4, 4, 4, 5, 7, 7, 1]
    assert _next_with_rows(jnp.zeros((3,), jnp.int32)).tolist() == [3] * 4


def test_the_cells_tilings_hold_a_groups_matrix_block_for_all_its_visits():
    # at the benchmark cell's shapes the contraction is whole and so is
    # the block: an expert's matrix is fetched once, a group ahead, into
    # one of the ring's slots, and every visit of the group multiplies by
    # it; ring, accumulator and the double-buffered tiles fit the VMEM
    # the kernels ask for
    for k, n in ((2048, 4096), (2048, 2048)):
        for kk, nn in ((k, n), (n, k)):        # forward, input gradient
            t = gmm_tiling(4096, kk, nn, 2)
            assert t.tm == 128 and t.tk == kk and t.tn == nn
            ring = gm._W_SLOTS * 2 * t.tk * t.tn
            tiles = 2 * 2 * t.tm * (t.tk + t.tn)
            assert ring + tiles + 4 * t.tm * t.tn <= gm._VMEM_LIMIT
        t = tgmm_tiling(4096, k, n, 2)
        acc, block = 4 * t.tk * t.tn, 2 * t.tk * t.tn
        assert t.tm == 256 and block <= gm._DW_BLOCK_BYTES
        rows = 2 * 2 * t.tm * (t.tk + t.tn)
        assert acc + 2 * block + rows <= gm._VMEM_LIMIT
    # float32 operands: the same bytes a block, half the columns
    assert tgmm_tiling(1024, 2048, 4096, 4).tn == 1024


def test_a_matrix_over_the_slot_is_fetched_in_column_blocks():
    # the 8k cell: 32,768 (token, choice) rows, an expert's gate-up matrix
    # 3072 x 6144 (37.7 MB) over the 16 MB slot: the largest block that
    # divides the columns, not the whole axis
    for (k, n), tn in (((3072, 6144), 2048), ((6144, 3072), 1024),
                       ((3072, 3072), 1536)):
        t = gmm_tiling(32768, k, n, 2)
        assert (t.tm, t.tk, t.tn) == (128, k, tn)
        assert 2 * t.tk * t.tn <= gm._W_BLOCK_BYTES
        ring = gm._W_SLOTS * 2 * t.tk * t.tn
        assert ring + 2 * 2 * t.tm * (t.tk + t.tn) <= gm._VMEM_LIMIT
    for n in (6144, 3072):
        t = tgmm_tiling(32768, 3072, n, 2)
        assert 3072 % t.tk == 0 and n % t.tn == 0
        assert 4 * t.tk * t.tn <= 2 * gm._DW_BLOCK_BYTES


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


# -- a share of the experts computes a bounded number of rows ----------------

# 256 tokens x 4 choices over 64 experts, 2 of them held: 1,024 pairs, an even
# share of 32, so the share computes 256 rows (the row tile) for 1,024
BOUND = dict(t=256, h=32, f=16, e=64, held=2, k=4, first=5)


def _bounded_case(held_pairs, seed=0, t=BOUND["t"]):
    """(x, w_gu, w_dn, ids, gates) for ``t`` tokens: the first
    ``held_pairs`` (token, choice) pairs of choices 0-1 go to the two held
    experts, every other pair to an absent one."""
    b = BOUND
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
    ids = np.tile(np.arange(20, 20 + b["k"], dtype=np.int32), (t, 1))
    mine = np.arange(held_pairs)
    ids[mine // 2, mine % 2] = b["first"] + mine % 2
    gates = jnp.asarray(rng.uniform(0.1, 1.0, ids.shape), jnp.float32)
    return (n(t, b["h"]), n(b["held"], b["h"], 2 * b["f"]),
            n(b["held"], b["f"], b["h"]), jnp.asarray(ids), gates)


def _dense_loop(x, w_gu, w_dn, ids, gates, first):
    """Every held expert on every token, kept where chosen."""
    f, out = w_dn.shape[1], jnp.zeros_like(x)
    for e in range(w_gu.shape[0]):
        gu = x @ w_gu[e]
        y = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_dn[e]
        out = out + jnp.sum(jnp.where(ids == first + e, gates, 0.0),
                            axis=-1)[:, None] * y
    return out


@pytest.mark.parametrize(
    "held_pairs, in_full, tokens, experts",
    [(0, 0, 256, 64), (40, 0, 256, 64), (256, 0, 256, 64), (257, 1, 256, 64),
     (512, 1, 256, 64), (700, 1, 768, 256), (1200, 1, 768, 256)],
    ids=["none", "few", "at-the-bound", "one-over", "every-token-twice",
         "three-windows", "five-windows"])
def test_a_bounded_share_drops_no_pair_at_the_bound_or_beyond_it(
        held_pairs, in_full, tokens, experts):
    """Up to the bound the share computes one window of 256 rows, past
    it as many as hold its pairs (two; of 768 tokens' pairs over 256
    experts three and five): the output and every gradient are the dense
    loop's either way, and ``calls_in_full`` counts the calls that passed
    the bound."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.nn.layer import moe

    b = dict(BOUND, e=experts)
    assert moe.row_bound(tokens * b["k"], b["held"], b["e"]) == 256
    x, w_gu, w_dn, ids, gates = _bounded_case(held_pairs, t=tokens)
    layer = nn.RoutedExperts(b["h"], b["f"], b["e"], b["held"], b["first"])
    layer.w_gu._data, layer.w_dn._data = w_gu, w_dn
    t = paddle.to_tensor
    for _ in range(2):
        out = layer(t(x), t(ids), t(gates))._data
    assert int(layer.calls_in_full._data) == 2 * in_full
    assert int(layer.pairs_routed._data) == 2 * tokens * b["k"]
    counts = np.asarray(layer.tokens_per_expert._data)
    assert (counts == [held_pairs + 1 >> 1 << 1, held_pairs >> 1 << 1]).all()

    def loss(run):
        return lambda *a: jnp.sum(jnp.square(run(*a)))

    program = lambda x, w_gu, w_dn, gates: moe.routed_experts(
        x, w_gu, w_dn, ids, gates, b["e"], b["first"])[0]
    dense = lambda x, w_gu, w_dn, gates: _dense_loop(
        x, w_gu, w_dn, ids, gates, b["first"])
    with jax.default_matmul_precision("highest"):
        want = dense(x, w_gu, w_dn, gates)
        want_grads = jax.grad(loss(dense), (0, 1, 2, 3))(x, w_gu, w_dn, gates)
    grads = jax.jit(jax.grad(loss(program), (0, 1, 2, 3)))(
        x, w_gu, w_dn, gates)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(out - want).max()) <= 1e-5 * max(scale, 1.0)
    for got, w, name in zip(grads, want_grads, ("x", "w_gu", "w_dn", "gates")):
        assert bool(jnp.isfinite(got).all()), name
        assert float(jnp.abs(got - w).max()) \
            <= 1e-5 * max(float(jnp.abs(w).max()), 1.0), name


@pytest.mark.parametrize("pairs, held, num_experts, rows", [
    (32768, 8, 256, 4096),          # train-trinity-5l-8k: 4 even shares
    (163840, 64, 512, 42240),       # train-qwen3next-4l-16k: 2.06
    (1024, 2, 64, 256),             # 7 shares of 32: one row tile
    (8192, 16, 256, 1792),          # 3.12 shares of 512
    (2048, 2, 32, 1024),            # 7 shares of 128 = 896: half the rows
    (2048, 2, 28, 1024),            # 7 shares of 146.3 = 1024: at the half
    (2048, 2, 27, None),            # ... and over it: would not halve
    (1536, 2, 16, None),            # 7 shares of 192: far over the half
    (4096, 16, 16, None)],          # every expert held (train-zaya1-6l-4k)
    ids=["trinity", "qwen3next", "one-tile", "sixteen-held", "under-a-half",
         "at-a-half", "over-a-half", "an-eighth-of-16",
         "every-expert"])
def test_a_shares_window_is_one_even_share_and_an_excess_by_the_held(
        pairs, held, num_experts, rows):
    """``row_bound``: one even share of the pairs plus three at 8 held
    experts, falling as 1 / sqrt(held), in whole row tiles; None where
    that would not halve the rows (a row for every pair)."""
    from paddle_tpu.nn.layer import moe

    assert moe.row_bound(pairs, held, num_experts) == rows
    if rows is not None:
        assert rows % moe._ROW_TILE == 0 and 2 * rows <= pairs
        shares = 1 + 3 * (8 / held) ** 0.5
        assert 0 <= rows - shares * pairs * held / num_experts < moe._ROW_TILE


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _shapes(jaxpr, loops):
    """The shape of every value in ``jaxpr`` and all it holds; ``loops``
    is given each ``while`` met that holds a grouped matmul."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while" and "moe_gmm" in str(eqn):
            loops.append(eqn)
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in _sub_jaxprs(eqn):
            yield from _shapes(sub, loops)


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["none", "recomputed"])
def test_a_bounded_share_holds_no_row_for_every_pair(recompute):
    """Forward + backward of a bounded share: no value of T*k rows (and
    H, F or 2F columns) anywhere, residuals included — the windows are
    256 rows — and the loops over windows are the forward's (twice where
    its output is needed again) and the backward's."""
    from paddle_tpu.nn.layer import moe

    b = BOUND
    x, w_gu, w_dn, ids, gates = _bounded_case(40)
    run = lambda x, w_gu, w_dn, gates: moe.routed_experts(
        x, w_gu, w_dn, ids, gates, b["e"], b["first"])[0]
    if recompute:   # ... of a function whose backward needs the output
        share, run = run, jax.checkpoint(lambda *a: jnp.tanh(share(*a)))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(jnp.square(run(*a))), (0, 1, 2, 3)))(
            x, w_gu, w_dn, gates)
    loops = []
    shapes = set(_shapes(jaxpr.jaxpr, loops))
    pairs = b["t"] * b["k"]
    assert not {s for s in shapes if len(s) == 2 and s[0] == pairs
                and s[1] > 1}
    assert {(256, b["h"]), (256, b["f"]), (256, 2 * b["f"])} <= shapes
    assert len(loops) == (3 if recompute else 2)


def test_with_every_expert_held_the_trace_is_the_one_before_shares():
    """``routed_experts`` with all experts held (``models/zaya.py``'s
    use, and every test that holds all) traces, forward and backward,
    to the ops it traced to before a share had a bound or a branch: the
    body below is that one, as PR 32's parent had it."""
    from paddle_tpu.nn.layer import moe

    def before(x, w_gu, w_dn, ids, gates):
        t, k = ids.shape
        held, f = w_gu.shape[0], w_dn.shape[1]
        flat = ids.reshape(t * k)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.bincount(flat, length=held).astype(jnp.int32)
        rows = x if k == 1 else jnp.repeat(x, k, axis=0)
        rows = moe._permute_rows(rows, order, inverse)
        gu = grouped_matmul(rows, w_gu, sizes)
        act = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
               * gu[:, f:].astype(jnp.float32)).astype(x.dtype)
        y = grouped_matmul(act, w_dn, sizes)
        y = moe._permute_rows(y, inverse, order).reshape(t, k, -1)
        out = jnp.sum(y.astype(jnp.float32)
                      * gates.astype(jnp.float32)[..., None], axis=1)
        return out.astype(x.dtype), sizes

    rng = np.random.default_rng(1)
    for k in (1, 2):
        t, h, f, e = 128, 32, 16, 4
        n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
        ids = jnp.asarray(rng.integers(0, e, (t, k)), jnp.int32)
        gates = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
        args = (n(t, h), n(e, h, 2 * f), n(e, f, h))

        def traced(run):
            def loss(x, w_gu, w_dn, gates):
                out, sizes = run(x, w_gu, w_dn, ids, gates)
                return jnp.sum(jnp.square(out.astype(jnp.float32))), sizes
            return str(jax.make_jaxpr(jax.value_and_grad(
                loss, (0, 1, 2, 3), has_aux=True))(*args, gates))

        now = traced(lambda *a: moe.routed_experts(*a, e, 0))
        assert now == traced(before), k
        assert "moe_gmm" in now

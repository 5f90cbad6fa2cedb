"""``ops/sparse_attention.py`` (interpret mode, CPU).

The selection against a brute-force loop over the tokens, the rule of
the module docstring line by line in float64: forced blocks, ties,
tokens with fewer than ``topk`` valid blocks, at MiniCPM-SALA's kernel
(32 / 16), block (64) and a small ``topk`` and window. The attention,
forward and all three gradients, against a masked softmax over [S, S]
for a RANDOM table (entries in any order, padding anywhere, blocks after
the token's own, rows without a key), for a table whose neighbouring
rows share no block beyond what they must, and for the selected one.
The band path (the rule that made the table handed to the op: the forced
blocks once a query block, the free ones a token) against the any-table
path and the same masked softmax, on selected tables.

Tolerances: kernel and masked softmax both run in float32 here, so only
the order of sums differs: 1e-5 of each array's largest entry."""
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import sparse_attention
from paddle_tpu.ops.sparse_attention import (BAND_KERNELS, BLOCK, KERNELS,
                                             band_blocks, band_engages,
                                             block_sparse_attention,
                                             select_blocks)

TOL = 1e-5
NAMES = ("q", "k", "v")
B, S, H, G, D = 1, 512, 4, 2, 128
RULE = dict(kernel_size=32, kernel_stride=16, block_size=64, topk=4,
            init_blocks=1, window_size=128)


def brute_selection(q, k, kernel_size, kernel_stride, block_size, topk,
                    init_blocks, window_size):
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    b_, s, h, d = q.shape
    g_ = k.shape[2]
    blocks = s // block_size
    kernels = (s - kernel_size) // kernel_stride + 1
    out = np.full((b_, g_, s, min(topk, blocks)), -1, np.int32)
    for b in range(b_):
        kc = np.stack([k[b, kernel_stride * j:kernel_stride * j
                         + kernel_size].mean(0) for j in range(kernels)])
        for g in range(g_):
            for t in range(s):
                seen = [j for j in range(kernels)
                        if kernel_stride * j + kernel_size - 1 <= t]
                a = np.zeros(kernels)
                for head in range(g * h // g_, (g + 1) * h // g_):
                    if seen:
                        logit = np.array([q[b, t, head] @ kc[j, g]
                                          for j in seen]) / np.sqrt(d)
                        e = np.exp(logit - logit.max())
                        a[seen] += e / e.sum()
                own, score = t // block_size, []
                for blk in range(blocks):
                    if blk > own:
                        score.append(-np.inf)
                    elif blk < init_blocks \
                            or blk > own - window_size // block_size:
                        score.append(np.inf)
                    else:
                        over = [j for j in range(kernels)
                                if kernel_stride * j < block_size * (blk + 1)
                                and kernel_stride * j + kernel_size
                                > block_size * blk]
                        score.append(max(a[j] for j in over))
                order = sorted(range(blocks), key=lambda i: (-score[i], i))
                chosen = sorted(i for i in order[:min(topk, blocks)]
                                if score[i] > -np.inf)
                out[b, g, t, :len(chosen)] = chosen
    return out


def masked_softmax(q, k, v, table):
    """Softmax over the keys i <= t in the blocks row t names."""
    h, g = q.shape[2], k.shape[2]
    tab = np.asarray(table)
    member = np.zeros(tab.shape[:3] + (S // BLOCK + 1,), bool)
    np.put_along_axis(member, np.where(tab >= 0, tab, S // BLOCK), True, -1)
    mask = np.repeat(member[..., :-1], BLOCK, axis=-1)        # [B, G, S, S]
    mask &= np.tril(np.ones((S, S), bool))[None, None]
    mask = jnp.asarray(np.repeat(mask, h // g, axis=1))
    kk, vv = (jnp.repeat(x, h // g, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") * D ** -0.5
    top = jnp.max(jnp.where(mask, s, -jnp.inf), -1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = p.sum(-1, keepdims=True)
    p = p / jnp.where(total > 0, total, 1.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision="highest")


def _worst(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.fixture(scope="module")
def arrays():
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k, v = (jax.random.normal(key, (B, S, G, D)) for key in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (B, S, H, D))


def _random_table(width=3):
    """Rows of 0 to ``width`` distinct blocks out of all eight, in any
    order, padding between them: blocks after a token's own among them,
    and rows that name no key at all."""
    rng = np.random.default_rng(0)
    tab = np.full((B, G, S, width), -1, np.int32)
    for row in tab.reshape(-1, width):
        n = rng.integers(0, width + 1)
        row[rng.permutation(width)[:n]] = rng.permutation(S // BLOCK)[:n]
    return tab


def _disjoint_table():
    """Token t reads its own block and block (t mod (own + 1)): the rows
    of neighbouring tokens agree in nothing but what causality forces."""
    t = np.arange(S)
    own = t // BLOCK
    other = np.where(t % (own + 1) == own, -1, t % (own + 1))
    tab = np.stack([own, other], -1).astype(np.int32)
    return np.broadcast_to(tab, (B, G, S, 2)).copy()


TABLES = {"random": _random_table, "disjoint": _disjoint_table,
          "selected": None}


@pytest.fixture(scope="module", params=TABLES, ids=list(TABLES))
def case(request, arrays):
    q, k, v, weight = arrays
    make = TABLES[request.param]
    table = select_blocks(q, k, **RULE) if make is None else make()
    table = jnp.asarray(table)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a, table) * weight))
    both = lambda fn: jax.value_and_grad(loss(fn), argnums=(0, 1, 2))
    return {"args": (q, k, v), "table": table, "both": both,
            "want_o": masked_softmax(q, k, v, table),
            "want": both(masked_softmax)(q, k, v)[1]}


def test_the_selection_is_the_rule_token_by_token(arrays):
    q, k, _, _ = arrays
    got = np.asarray(select_blocks(q, k, **RULE))
    want = brute_selection(q, k, **RULE)
    assert got.shape == want.shape == (B, G, S, 4) and got.dtype == np.int32
    assert (got == want).all()
    # forced: block 0 and the two up to a token's own; then one free pick
    assert (got[0, 0, 63] == [0, -1, -1, -1]).all()
    assert (got[0, 0, 64] == [0, 1, -1, -1]).all()
    assert (got[0, 0, 200] == [0, 1, 2, 3]).all()      # every valid block
    last = got[0, :, 448:]
    assert (last[..., 0] == 0).all() and (last[..., 2:] == [6, 7]).all()
    assert ((last[..., 1] >= 1) & (last[..., 1] <= 5)).all()
    assert len(np.unique(last[..., 1])) > 1             # a choice was made


def test_ties_go_to_the_lower_block():
    """Equal keys make every kernel's score equal: the free pick is the
    lowest block that is not forced."""
    q = jnp.ones((1, S, H, D))
    k = jnp.ones((1, S, G, D))
    got = np.asarray(select_blocks(q, k, **RULE))
    assert (got[0, :, 448:] == [0, 1, 6, 7]).all()
    assert (got == brute_selection(q, k, **RULE)).all()


def test_the_selection_has_no_gradient_and_refuses_a_ragged_length(arrays):
    q, k, _, _ = arrays
    grads = jax.grad(lambda q, k: jnp.sum(
        select_blocks(q, k, **RULE).astype(jnp.float32)),
        argnums=(0, 1), allow_int=True)(q, k)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)
    with pytest.raises(ValueError, match="do not tile"):
        select_blocks(q[:, :500], k[:, :500], **RULE)
    with pytest.raises(ValueError, match="multiple of the block"):
        block_sparse_attention(q[:, :500], k[:, :500], k[:, :500],
                               jnp.zeros((B, G, 500, 2), jnp.int32))
    with pytest.raises(ValueError, match="table"):
        block_sparse_attention(q, k, k, jnp.zeros((B, G, S - 64, 2),
                                                  jnp.int32))


def test_forward_is_the_masked_softmax(case):
    got = block_sparse_attention(*case["args"], case["table"])
    assert got.shape == case["want_o"].shape
    assert _worst(got, case["want_o"]) <= TOL
    if case["table"].shape[-1] == 3:          # the random one
        keyless = np.asarray((case["table"] < 0).all(-1))[0, 0]
        assert keyless.any()
        assert float(jnp.abs(got[0, keyless, :H // G]).max()) == 0.0


@pytest.mark.parametrize("leaf", range(3), ids=NAMES)
def test_every_gradient_is_the_masked_softmaxs(case, leaf):
    if "got" not in case:
        case["got"] = case["both"](block_sparse_attention)(*case["args"])[1]
    got, want = case["got"][leaf], case["want"][leaf]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _worst(got, want) <= TOL, NAMES[leaf]


def test_a_kernel_that_ignores_its_table_fails_the_same_tolerance(case):
    """Dense causal attention (every valid block in every row) is far
    from what the table asks for."""
    dense = jnp.broadcast_to(jnp.arange(S // BLOCK, dtype=jnp.int32),
                             (B, G, S, S // BLOCK))
    got = block_sparse_attention(*case["args"], dense)
    assert _worst(got, case["want_o"]) > 1000 * TOL


def test_neighbouring_rows_of_the_disjoint_table_share_only_their_own():
    tab = _disjoint_table()[0, 0]
    for t in range(65, S):
        if t % BLOCK:
            shared = set(tab[t]) & set(tab[t - 1]) - {-1}
            assert shared <= {t // BLOCK}


# -- the band path -----------------------------------------------------------

# (seed, init_blocks, keys a tile of the band pass holds): 8 blocks of keys
# for a topk of 3 + init_blocks and a window of 2, so the blocks up to
# init_blocks + 1 are all band and the later ones pick one free block of
# those between; the window as one tile, or as two (a whole one, then the
# one with the tokens' own block)
BANDS = {f"seed{seed}-init{init}-tiles-of-{keys}": (seed, init, keys)
         for seed, keys in ((0, 2048), (1, 64)) for init in (1, 2)}


def _band_of(rule):
    return dict(init_blocks=rule["init_blocks"],
                window_blocks=rule["window_size"] // rule["block_size"])


def _forced(rule):
    """[S, blocks] bool: the blocks the rule forces on each token."""
    own = (np.arange(S) // BLOCK)[:, None]
    at = np.arange(S // BLOCK)[None, :]
    band = _band_of(rule)
    return (at <= own) & ((at < band["init_blocks"])
                          | (at > own - band["window_blocks"]))


def _kernels(fn, *args):
    """The names of the kernels the gradient of ``fn`` calls."""
    program = jax.make_jaxpr(jax.grad(
        lambda q, k, v, table: jnp.sum(fn(q, k, v, table)), (0, 1, 2)))(*args)
    return sorted(set(re.findall(r"sparse_attn_\w+", str(program))))


@pytest.fixture(scope="module", params=BANDS.values(), ids=BANDS.keys())
def band_case(request):
    seed, init, keys = request.param
    launchers = (sparse_attention._sparse_fwd, sparse_attention._sparse_bwd)
    was, sparse_attention._BAND_KEYS = sparse_attention._BAND_KEYS, (keys,
                                                                     keys)
    for fn in launchers:             # the constant is no part of their key
        fn.clear_cache()
    yield _band_case(seed, init)
    sparse_attention._BAND_KEYS = was
    for fn in launchers:
        fn.clear_cache()


def _band_case(seed, init):
    rule = dict(RULE, init_blocks=init, topk=3 + init)
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k, v = (jax.random.normal(key, (B, S, G, D)) for key in ks[1:3])
    weight = jax.random.normal(ks[3], (B, S, H, D))
    table = select_blocks(q, k, **rule)
    band = lambda q, k, v, table: block_sparse_attention(
        q, k, v, table, **_band_of(rule))
    both = lambda fn: jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a, table) * weight), argnums=(0, 1, 2))
    return {"args": (q, k, v), "rule": rule, "table": table, "band": band,
            "got": both(band)(q, k, v)[1],
            "plain": both(masked_softmax)(q, k, v)[1],
            "any": both(block_sparse_attention)(q, k, v)[1]}


def test_the_band_engages_on_the_rules_tables(band_case):
    rule, table = band_case["rule"], band_case["table"]
    assert band_engages(S, table.shape[-1], **_band_of(rule))
    assert _kernels(band_case["band"], *band_case["args"], table) \
        == sorted(KERNELS + BAND_KERNELS)
    assert _kernels(block_sparse_attention, *band_case["args"], table) \
        == sorted(KERNELS)


def test_select_blocks_meets_the_bands_contract(band_case):
    """Every row holds each of its valid forced blocks and at most
    ``K - init_blocks - window_blocks`` others, for every token."""
    rule, table = band_case["rule"], np.asarray(band_case["table"])
    forced = _forced(rule)                                  # [S, blocks]
    named = np.zeros(table.shape[:3] + (S // BLOCK + 1,), bool)
    np.put_along_axis(named, np.where(table >= 0, table, S // BLOCK), True, -1)
    named = named[..., :-1]
    assert (named | ~forced).all()
    band = _band_of(rule)
    free = table.shape[-1] - band["init_blocks"] - band["window_blocks"]
    assert free >= 1
    assert ((named & ~forced).sum(-1) <= free).all()
    assert (named & ~forced).any()                  # and some are chosen


def test_the_band_forward_is_the_any_table_paths_and_the_softmaxs(band_case):
    q, k, v = band_case["args"]
    table = band_case["table"]
    got = band_case["band"](q, k, v, table)
    assert _worst(got, masked_softmax(q, k, v, table)) <= TOL
    assert _worst(got, block_sparse_attention(q, k, v, table)) <= TOL


@pytest.mark.parametrize("leaf", range(3), ids=NAMES)
def test_every_band_gradient_is_the_any_table_paths_and_the_softmaxs(
        band_case, leaf):
    got = band_case["got"][leaf]
    for other in ("plain", "any"):
        want = band_case[other][leaf]
        assert got.shape == want.shape and got.dtype == want.dtype
        assert _worst(got, want) <= TOL, (NAMES[leaf], other)


def test_a_band_path_that_drops_its_free_blocks_fails_the_same_tolerance(
        band_case):
    """The benchmark's ``local`` fault at kernel level: every row cut to
    its forced blocks."""
    q, k, v = band_case["args"]
    table = np.asarray(band_case["table"])
    forced = np.take_along_axis(
        np.broadcast_to(_forced(band_case["rule"]),
                        table.shape[:3] + (S // BLOCK,)),
        np.maximum(table, 0), -1)
    local = jnp.asarray(np.where(forced, table, -1))
    got = band_case["band"](q, k, v, local)
    assert _worst(got, masked_softmax(q, k, v, local)) <= TOL
    assert _worst(got, masked_softmax(q, k, v, band_case["table"])) \
        > 1000 * TOL


# (table columns, sequence, init_blocks, window_blocks): no free column;
# a sequence no longer than the band; a rule half given
FALLBACKS = {"no-free-column": (3, S, 1, 2), "short-sequence": (4, 192, 1, 2),
             "half-a-rule": (4, S, None, 2)}


@pytest.mark.parametrize("shape", FALLBACKS.values(), ids=FALLBACKS.keys())
def test_where_the_band_cannot_engage_the_any_table_path_runs(arrays, shape):
    width, s, init, window = shape
    assert not band_engages(s, width, init, window)
    assert band_blocks(s, width, init, window) == 0
    q, k, v = (a[:, :s] for a in arrays[:3])
    table = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32),
                             (B, G, s, width))
    given = lambda q, k, v, table: block_sparse_attention(
        q, k, v, table, init_blocks=init, window_blocks=window)
    assert _kernels(given, q, k, v, table) == sorted(KERNELS)
    assert (given(q, k, v, table)
            == block_sparse_attention(q, k, v, table)).all()


def test_band_blocks_is_the_rules_count():
    """A token of block b: the window's min(b + 1, 2) blocks and the
    first block where the window no longer holds it."""
    assert band_blocks(S, 4, 1, 2) == BLOCK * (1 + 2 + 6 * 3)
    assert band_blocks(S, 5, 2, 2) == BLOCK * (1 + 2 + 3 + 5 * 4)
    assert band_blocks(S, 4, 1, 2) == _forced(RULE).sum()
    # the cell's rule at its length: 30.94 of a query's 56.125 blocks
    cell = band_blocks(16384, 64, 1, 32)
    assert cell == 506_880 and round(cell / 16384, 2) == 30.94
    assert band_blocks(16384, 64, None, None) == 0


def test_the_bench_script_rehearses_off_the_chip(monkeypatch, tmp_path):
    """``benchmarks/sparse_bench.py``: without a chip it times nothing —
    it refuses, or with ``--rehearse`` walks both ways of the layer at a
    toy size and writes their agreement with the float32 masked softmax."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "sparse_bench.py")
    spec = importlib.util.spec_from_file_location("sparse_bench", path)
    bench = importlib.util.module_from_spec(spec)
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec.loader.exec_module(bench)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(sys, "argv", ["sparse_bench.py"])
    with pytest.raises(SystemExit, match="no chip here"):
        bench.main()
    monkeypatch.setattr(sys, "argv", ["sparse_bench.py", "--rehearse"])
    bench.main()
    with open(tmp_path / "chiprun_out" / "sparse_bench.json") as fh:
        (row,) = json.load(fh)
    assert row["kernels"] == "repository" and "ms" not in row
    ways = row["agreement_bf16_toy"]
    assert sorted(ways) == ["band", "columns_64"]
    for gaps in ways.values():       # bfloat16 against float32: ~2^-8
        assert sorted(gaps) == ["k", "o", "q", "v"]
        assert all(0 < gap < 0.02 for gap in gaps.values())

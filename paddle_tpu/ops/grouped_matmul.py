"""Grouped matmul — rows of ``x`` grouped by expert times that expert's
matrix — as Pallas TPU kernels with a custom VJP.

    grouped_matmul(x [T, K], w [E, K, N], group_sizes [E]) -> [T, N]

Rows ``offset[e] .. offset[e+1]`` of ``x`` (``offset`` = the running sum
of ``group_sizes``) are multiplied by ``w[e]``: what a dropless
mixture-of-experts layer needs once its tokens are sorted by expert. No
capacity, no padding buffer: the work is the rows the groups hold,
whatever the routing. The sizes may add up to LESS than T (a layer that
holds a share of the experts sorts the rows of the absent ones last):
rows past the last group belong to no group, no kernel visits their
tiles, and the result's rows there are left unwritten — ``zero_tail``
makes them read as zero downstream, in the result and in the input
gradient alike. XLA's form of the same contraction is
``jax.lax.ragged_dot``; the algorithm (tiles of rows visited group by
group, found through scalar-prefetched tables) is the one jax ships as
``jax.experimental.pallas.ops.tpu.megablox``, written here for one chip
holding every group.

Three launchers, jitted and named so that a device trace shows them as
``%moe_gmm.<n>`` / ``%moe_tgmm.<n>`` (PERF.md §3):

  forward          ``moe_gmm``:   out[rows of e] = x[rows of e] @ w[e]
  input gradient   ``moe_gmm``:   dx[rows of e] = dy[rows of e] @ w[e]^T
                   (the same kernel, contracting ``w``'s last axis: the
                   weights are not transposed in HBM)
  weight gradient  ``moe_tgmm``:  dw[e] = x[rows of e]^T @ dy[rows of e]

A row tile of 128 rows is VISITED once for every group that has rows
in it (``_visits``): a group whose edge falls inside a tile shares it
with its neighbour, and each visit masks the rows that are not its own.
The number of visits is data (at most ``T/128 + E - 1``), so the grid's
visit axis is a traced bound. An empty group visits nothing in
``moe_gmm``; in ``moe_tgmm`` it visits one tile and writes its zero
gradient.

The walk over groups (PR 27; PERF.md §6 has the prices). ``moe_gmm``
keeps ``w`` in HBM and fetches a group's block of it by hand into one of
two slots in VMEM: the block of the NEXT group that has rows is asked for
as soon as this group's block has arrived, a whole group ahead of its
use, and a visit waits only at a change of group (``pallas_call``'s own
pipeline asks one grid step ahead: one visit's 10.9 us for a fetch of
26). ``moe_tgmm`` visits two tiles at a time, counted from its group's
first tile, and pays its float32 accumulator once a visit: a group's
first visit writes its product, its last adds and casts straight into
the output block.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import fit, interpret_default

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b

_VMEM_LIMIT = 64 * 1024 * 1024    # of a v5e's 128 MiB
_W_BLOCK_BYTES = 16 * 1024 * 1024  # a slot of ``moe_gmm``'s ring: at the
                                   # cell's shapes an expert's whole matrix
_W_SLOTS = 2                       # this group's block and the next one's;
                                   # a third, two groups ahead, was slower:
                                   # fetches in flight together share the
                                   # HBM's rate and the block needed first
                                   # arrives later (PERF.md §6)
_DW_BLOCK_BYTES = 8 * 1024 * 1024  # a block of ``moe_tgmm``'s result; its
                                   # float32 accumulator is twice that for
                                   # bfloat16 operands


class Tiling(NamedTuple):
    """Rows of a visit, contraction and output columns of a block."""
    tm: int
    tk: int
    tn: int


def _widest(size: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``cap`` (6144 under a cap of 2730: 2048); else the whole axis."""
    for b in range(min(cap, size) // 128 * 128, 0, -128):
        if size % b == 0:
            return b
    return size


def gmm_tiling(t: int, k: int, n: int, itemsize: int) -> Tiling:
    """``moe_gmm`` at [t, k] x [E, k, n]. 128 rows a visit: at a few
    hundred rows a group, taller tiles spend on the rows of the
    neighbours they mask what they gain on the MXU (PERF.md §6, PR 26).
    The contraction is held WHOLE — a group's block is then the same for
    all of its visits and is fetched once — and the block's columns take
    what is left of ``_W_BLOCK_BYTES``: at 2048 x 4096 all of them, so
    the rows are read once and a grid step is one visit (10.9 us of MXU
    for 0.3 of pipeline); at 3072 x 6144 a third (2048), and the walk
    over the groups is made once a column block."""
    tn = _widest(n, max(128, _W_BLOCK_BYTES // (k * itemsize)))
    return Tiling(fit(t, 128), k, tn)


def tgmm_tiling(t: int, k: int, n: int, itemsize: int) -> Tiling:
    """``moe_tgmm`` at [t, k]^T x [t, n] -> [E, k, n]. A visit takes TWO
    consecutive row tiles of 128, counted from its group's first tile:
    every visit passes the whole float32 accumulator through the MXU's
    result path, which at 128 rows takes 4.25 us beside 2.73 of matmul;
    at 256 the matmul (5.45) covers it. Counted from the group's first
    tile, 16 uneven groups of 4096 rows make 24-31 such visits; on tiles
    of 256 aligned to the array, always 31 (PERF.md §6, PR 27). The
    accumulator [tk, tn] is as large as ``_DW_BLOCK_BYTES`` allows: the
    larger, the fewer times the rows are read again."""
    tile = fit(t, 128)
    tk = fit(k, 2048)
    tn = fit(n, max(128, _DW_BLOCK_BYTES // (itemsize * tk)))
    return Tiling(tile * min(2, t // tile), tk, tn)


def _visits(group_sizes, t: int, tile: int, visit_empty: bool,
            parts: int = 1):
    """The tables the kernels find their work by: ``offsets`` [E+1] (row
    where each group starts; the last entry is their sum), and for each
    visit the group (``gid``) and the first of the ``parts`` consecutive
    row tiles of ``tile`` rows it works on (``tid``; a tile past the
    group's last holds none of its rows, and may lie past the array), in
    group order, so a tile is revisited only by consecutive visits;
    ``count`` is how many visits there are. Entries past ``count`` are
    never read."""
    e = group_sizes.shape[0]
    tiles = t // tile
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = jnp.minimum(starts // tile, tiles - 1)
    spanned = (ends + tile - 1) // tile - first
    n = jnp.where(group_sizes > 0, (spanned + parts - 1) // parts,
                  1 if visit_empty else 0)
    length = tiles + e - 1
    gid = jnp.repeat(jnp.arange(e, dtype=jnp.int32), n,
                     total_repeat_length=length)
    before = jnp.cumsum(n) - n
    tid = first[gid] + parts * (jnp.arange(length, dtype=jnp.int32)
                                - before[gid])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), gid,
            jnp.clip(tid, 0, tiles - 1).astype(jnp.int32)), jnp.sum(n)


def _next_with_rows(group_sizes):
    """[E+1] int32: entry g is the first group after g that has rows (E:
    there is none), entry E the first group that has rows — the order
    ``moe_gmm`` fetches blocks in, empty groups skipped."""
    e = group_sizes.shape[0]
    idx = jnp.where(group_sizes > 0, jnp.arange(e, dtype=jnp.int32), e)
    first_from = jax.lax.cummin(
        jnp.concatenate([idx, jnp.full((1,), e, jnp.int32)]), reverse=True)
    return jnp.concatenate([first_from[1:], first_from[:1]])


def _own_rows(offsets, g, tile_index, tile: int, cols: int):
    """[tile, cols] mask: the rows of row tile ``tile_index`` that belong
    to group ``g``."""
    row = (jax.lax.broadcasted_iota(jnp.int32, (tile, cols), 0)
           + tile_index * tile)
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _group_edges(gid, visit, visits):
    """Whether ``visit`` is its group's first and its last."""
    g = gid[visit]
    first = jnp.logical_or(visit == 0, gid[jnp.maximum(visit - 1, 0)] != g)
    last = jnp.logical_or(visit == visits - 1,
                          gid[jnp.minimum(visit + 1, visits - 1)] != g)
    return first, last


def _gmm_kernel(offsets, gid, tid, nxt, x_ref, w_hbm, o_ref, ring, sems,
                state, *, t: Tiling, transpose_w: bool):
    # state (SMEM, carried over the grid): [0] groups begun — this
    # group's block sits in slot ([0] - 1) % _W_SLOTS and the next block
    # asked for goes into slot [0] % _W_SLOTS; [1], [2] the column block
    # and the group of the block to ask for next
    i_n, visit = pl.program_id(0), pl.program_id(1)
    e = nxt.shape[0] - 1
    first, _ = _group_edges(gid, visit, pl.num_programs(1))

    def fetch(g, col_block, slot):
        cols = pl.ds(pl.multiple_of(col_block * t.tn, t.tn), t.tn)
        src = w_hbm.at[g, cols, :] if transpose_w else w_hbm.at[g, :, cols]
        return pltpu.make_async_copy(src, ring.at[slot], sems.at[slot])

    def ask_for_next():
        col_block, g = state[1], state[2]

        @pl.when(col_block < pl.num_programs(0))
        def _():
            fetch(g, col_block, state[0] % _W_SLOTS).start()
            wrapped = nxt[g] == e
            state[1] = jnp.where(wrapped, col_block + 1, col_block)
            state[2] = jnp.where(wrapped, nxt[e], nxt[g])

    @pl.when(first)
    def _():
        very_first = jnp.logical_and(i_n == 0, visit == 0)

        @pl.when(very_first)
        def _():
            state[0] = 0
            state[1] = 0
            state[2] = nxt[e]
            ask_for_next()

        fetch(gid[visit], i_n, state[0] % _W_SLOTS).wait()
        state[0] = state[0] + 1
        ask_for_next()   # into the slot the group before has left

    product = jax.lax.dot_general(
        x_ref[...], ring[(state[0] - 1) % _W_SLOTS],
        _NT if transpose_w else _NN, preferred_element_type=jnp.float32)
    # the tile's other rows are another visit's: what it wrote (or will
    # write) stays — the block is resident between consecutive visits of
    # one tile
    own = _own_rows(offsets, gid[visit], tid[visit], t.tm, t.tn)
    o_ref[...] = jnp.where(own, product, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("transpose_w", "tiling", "interpret"))
def _gmm(x, w, group_sizes, transpose_w: bool, tiling: Optional[Tiling],
         interpret: bool):
    """x [T, K] by ``w[e]`` ([K, N], or [N, K] with ``transpose_w``) for
    the rows of each group e -> [T, N]."""
    rows, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    t = tiling or gmm_tiling(rows, k, n, x.dtype.itemsize)
    assert rows % t.tm == 0 and k == t.tk and n % t.tn == 0, (x.shape, t)
    tables, count = _visits(group_sizes, rows, t.tm, visit_empty=False)

    def at_x(i_n, v, offsets, gid, tid, nxt):
        return tid[v], 0

    def at_o(i_n, v, offsets, gid, tid, nxt):
        return tid[v], i_n

    w_block = (t.tn, t.tk) if transpose_w else (t.tk, t.tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, t=t, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // t.tn, count),
            in_specs=[pl.BlockSpec((t.tm, t.tk), at_x),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((t.tm, t.tn), at_o),
            scratch_shapes=[pltpu.VMEM((_W_SLOTS, *w_block), w.dtype),
                            pltpu.SemaphoreType.DMA((_W_SLOTS,)),
                            pltpu.SMEM((3,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # the ring is carried from one column block into the next
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm",
    )(*tables, _next_with_rows(group_sizes), x, w)


def _tgmm_kernel(offsets, gid, tid, *refs, t: Tiling, parts: int):
    x_refs, dy_refs = refs[:parts], refs[parts:2 * parts]
    o_ref, acc = refs[2 * parts:]
    visit = pl.program_id(2)
    g = gid[visit]
    first, last = _group_edges(gid, visit, pl.num_programs(2))
    middle, later = jnp.logical_not(last), jnp.logical_not(first)
    empty = offsets[g] == offsets[g + 1]

    def product():
        # rows that are a neighbour's (a whole tile of them past the
        # group's last) are zeroed on one side of the contraction
        dy = [jnp.where(_own_rows(offsets, g, tid[visit] + p, t.tm // parts,
                                  t.tn), ref[...], jnp.zeros_like(ref))
              for p, ref in enumerate(dy_refs)]
        x = [ref[...] for ref in x_refs]
        return jax.lax.dot_general(jnp.concatenate(x), jnp.concatenate(dy),
                                   _TN, preferred_element_type=jnp.float32)

    # one branch runs, each with the matmul inside it: a product formed
    # before the branches would pass through VMEM once more (1009 us for
    # 864, PERF.md §6)
    @pl.when(empty)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(first & last & jnp.logical_not(empty))
    def _():
        o_ref[...] = product().astype(o_ref.dtype)

    @pl.when(first & middle)
    def _():
        acc[...] = product()

    @pl.when(later & middle)
    def _():
        acc[...] += product()

    @pl.when(later & last)
    def _():
        o_ref[...] = (acc[...] + product()).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def _tgmm(x, dy, group_sizes, tiling: Optional[Tiling], interpret: bool):
    """x [T, K], dy [T, N] -> [E, K, N]: ``x^T dy`` over each group's rows."""
    rows, k = x.shape
    n = dy.shape[1]
    e = group_sizes.shape[0]
    t = tiling or tgmm_tiling(rows, k, n, x.dtype.itemsize)
    tile = fit(rows, 128)
    parts = t.tm // tile
    assert (t.tm == parts * tile and k % t.tk == 0 and n % t.tn == 0
            ), (x.shape, t)
    tables, count = _visits(group_sizes, rows, tile, visit_empty=True,
                            parts=parts)
    tiles = rows // tile

    def part(p, of_dy):
        # tile p of a visit, of ``x`` or of ``dy``; past the array's last
        # tile every row is masked, so any tile will do
        def at(i_n, i_k, v, offsets, gid, tid):
            return jnp.minimum(tid[v] + p, tiles - 1), i_n if of_dy else i_k
        return at

    def at_o(i_n, i_k, v, offsets, gid, tid):
        return gid[v], i_k, i_n

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, t=t, parts=parts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // t.tn, k // t.tk, count),
            in_specs=([pl.BlockSpec((tile, t.tk), part(p, False))
                       for p in range(parts)]
                      + [pl.BlockSpec((tile, t.tn), part(p, True))
                         for p in range(parts)]),
            out_specs=pl.BlockSpec((None, t.tk, t.tn), at_o),
            scratch_shapes=[pltpu.VMEM((t.tk, t.tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((e, k, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_tgmm",
    )(*tables, *[x] * parts, *[dy] * parts)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(x, w, group_sizes, interpret: Optional[bool] = None):
    """``out[rows of e] = x[rows of e] @ w[e]``; ``group_sizes`` (int32
    [E]) add up to at most ``x``'s rows. Rows past their sum are left
    unwritten, here and in ``x``'s gradient (``zero_tail``), and must be
    finite in ``x``: the weight gradient zeroes them on one side of its
    contraction only. Differentiable in ``x`` and ``w``."""
    return _gm_fwd(x, w, group_sizes, interpret)[0]


def _gm_fwd(x, w, group_sizes, interpret):
    if interpret is None:
        interpret = interpret_default()
    out = _gmm(x, w, group_sizes, False, None, interpret)
    return out, (x, w, group_sizes)


def _gm_bwd(interpret, res, dy):
    if interpret is None:
        interpret = interpret_default()
    x, w, group_sizes = res
    dy = dy.astype(x.dtype)
    dx = _gmm(dy, w, group_sizes, True, None, interpret)
    dw = _tgmm(x, dy, group_sizes, None, interpret)
    return dx, dw.astype(w.dtype), None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


def zero_tail(a, rows):
    """``a`` [T, ...] with the rows from ``rows`` (a traced count) on
    read as zero — and, a select's transpose being a select, its
    gradient's rows there as well: around a ``grouped_matmul`` whose
    sizes add up to ``rows`` < T it keeps what the kernels left unwritten
    out of everything downstream, forward and backward."""
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    return jnp.where(row < rows, a, jnp.zeros_like(a))


def grouped_matmul_reference(x, w, group_sizes):
    """The same contraction in plain jnp: every row against the matrix of
    the group it lies in, rows past the last group zero (a gather of
    [T, K, N] — for tests and small sizes only)."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(x.shape[0])
    group = jnp.searchsorted(ends, row, side="right")
    group = jnp.minimum(group, w.shape[0] - 1)
    out = jnp.einsum("tk,tkn->tn", x, w[group],
                     preferred_element_type=jnp.float32)
    return jnp.where((row < ends[-1])[:, None], out, 0.0).astype(x.dtype)

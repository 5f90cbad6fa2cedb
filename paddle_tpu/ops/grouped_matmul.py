"""Grouped matmul — rows of ``x`` grouped by expert times that expert's
matrix — as Pallas TPU kernels with a custom VJP.

    grouped_matmul(x [T, K], w [E, K, N], group_sizes [E]) -> [T, N]

Rows ``offset[e] .. offset[e+1]`` of ``x`` (``offset`` = the running sum
of ``group_sizes``, which must add up to T) are multiplied by ``w[e]``:
what a dropless mixture-of-experts layer needs once its tokens are
sorted by expert. No capacity, no padding buffer: the work is T rows
whatever the routing. XLA's form of the same contraction is
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

A row tile of ``tm`` rows is VISITED once for every group that has rows
in it (``_visits``): a group whose edge falls inside a tile shares it
with its neighbour, and each visit masks the rows that are not its own.
The number of visits is data (at most ``T/tm + E - 1``), so the grid's
visit axis is a traced bound. An empty group visits nothing in
``moe_gmm``; in ``moe_tgmm`` it visits one tile with every row masked,
which writes its zero gradient.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _fit, _interpret_default

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b

_VMEM_LIMIT = 64 * 1024 * 1024   # of a v5e's 128 MiB; the blocks below
                                 # take 10-20 MiB double-buffered
_W_BLOCK_BYTES = 4 * 1024 * 1024  # one block of an expert's matrix


class Tiling(NamedTuple):
    """Rows of a visit, contraction and output columns of a block."""
    tm: int
    tk: int
    tn: int


def gmm_tiling(t: int, k: int, n: int, itemsize: int) -> Tiling:
    """``moe_gmm`` at [t, k] x [E, k, n]. 128 rows a visit: at a few
    hundred rows a group, taller tiles spend on the rows of the
    neighbours they mask what they gain on the MXU (256 rows: 812 us for
    815 at the cell's 2048 x 4096, 512 rows 1072; PERF.md §6, PR 26).
    The contraction is held WHOLE as far as a block of the matrix stays
    under ``_W_BLOCK_BYTES`` — consecutive visits of one group then ask
    for the same block and it is fetched once (in two steps of 2048 the
    input gradient took 1254 us, whole 789) — and the block's columns
    take what is left of those bytes."""
    tk = _fit(k, max(128, _W_BLOCK_BYTES // (128 * itemsize)))
    tn = _fit(n, max(128, min(1024, _W_BLOCK_BYTES // (tk * itemsize))))
    return Tiling(_fit(t, 128), tk, tn)


def tgmm_tiling(t: int, k: int, n: int, itemsize: int) -> Tiling:
    """``moe_tgmm`` at [t, k]^T x [t, n] -> [E, k, n]: a float32
    accumulator of [tk, tn] (8 MiB at most) lives in VMEM while a
    group's visits pass; the larger it is, the fewer times the rows are
    read again (1028 us at 1024 x 1024, 949 at 2048 x 1024)."""
    del itemsize
    return Tiling(_fit(t, 128), _fit(k, 2048), _fit(n, 1024))


def _visits(group_sizes, t: int, tm: int, visit_empty: bool):
    """The tables the kernels find their work by: ``offsets`` [E+1] (row
    where each group starts; the last entry is their sum), and for each
    visit the group (``gid``) and the row tile (``tid``) it works on, in
    group order, so a tile is revisited only by consecutive visits;
    ``count`` is how many visits there are. Entries past ``count`` are
    never read."""
    e = group_sizes.shape[0]
    tiles = t // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = jnp.minimum(starts // tm, tiles - 1)
    n = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first,
                  1 if visit_empty else 0)
    length = tiles + e - 1
    gid = jnp.repeat(jnp.arange(e, dtype=jnp.int32), n,
                     total_repeat_length=length)
    before = jnp.cumsum(n) - n
    tid = first[gid] + jnp.arange(length, dtype=jnp.int32) - before[gid]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), gid,
            jnp.clip(tid, 0, tiles - 1).astype(jnp.int32)), jnp.sum(n)


def _own_rows(offsets, gid, tid, visit, tm: int, cols: int):
    """[tm, cols] mask: the rows of this visit's tile that belong to its
    group."""
    g = gid[visit]
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, cols), 0) + tid[visit] * tm
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _gmm_kernel(offsets, gid, tid, x_ref, w_ref, o_ref, acc, *, t: Tiling,
                k_steps: int, transpose_w: bool):
    visit, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], _NT if transpose_w else _NN,
        preferred_element_type=jnp.float32)

    @pl.when(ik == k_steps - 1)
    def _():
        # the tile's other rows are another visit's: what it wrote (or
        # will write) stays — the block is resident between consecutive
        # visits of one tile
        own = _own_rows(offsets, gid, tid, visit, t.tm, t.tn)
        o_ref[...] = jnp.where(own, acc[...],
                               o_ref[...].astype(jnp.float32)
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
    assert rows % t.tm == 0 and k % t.tk == 0 and n % t.tn == 0, (x.shape, t)
    tables, count = _visits(group_sizes, rows, t.tm, visit_empty=False)
    k_steps = k // t.tk

    def at_x(i_n, v, i_k, offsets, gid, tid):
        return tid[v], i_k

    def at_w(i_n, v, i_k, offsets, gid, tid):
        return (gid[v], i_n, i_k) if transpose_w else (gid[v], i_k, i_n)

    def at_o(i_n, v, i_k, offsets, gid, tid):
        return tid[v], i_n

    w_block = (None, t.tn, t.tk) if transpose_w else (None, t.tk, t.tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, t=t, k_steps=k_steps,
                          transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // t.tn, count, k_steps),
            in_specs=[pl.BlockSpec((t.tm, t.tk), at_x),
                      pl.BlockSpec(w_block, at_w)],
            out_specs=pl.BlockSpec((t.tm, t.tn), at_o),
            scratch_shapes=[pltpu.VMEM((t.tm, t.tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm",
    )(*tables, x, w)


def _tgmm_kernel(offsets, gid, tid, x_ref, dy_ref, o_ref, acc, *, t: Tiling):
    visit = pl.program_id(2)
    g = gid[visit]
    first = jnp.logical_or(visit == 0, gid[jnp.maximum(visit - 1, 0)] != g)
    last = jnp.logical_or(
        visit == pl.num_programs(2) - 1,
        gid[jnp.minimum(visit + 1, pl.num_programs(2) - 1)] != g)

    @pl.when(first)
    def _():
        acc[...] = jnp.zeros_like(acc)

    # rows of the tile that are a neighbour's (or, for an empty group,
    # all of them) are zeroed on one side of the contraction
    own = _own_rows(offsets, gid, tid, visit, t.tm, t.tn)
    dy = jnp.where(own, dy_ref[...], jnp.zeros_like(dy_ref))
    acc[...] += jax.lax.dot_general(x_ref[...], dy, _TN,
                                    preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def _tgmm(x, dy, group_sizes, tiling: Optional[Tiling], interpret: bool):
    """x [T, K], dy [T, N] -> [E, K, N]: ``x^T dy`` over each group's rows."""
    rows, k = x.shape
    n = dy.shape[1]
    e = group_sizes.shape[0]
    t = tiling or tgmm_tiling(rows, k, n, x.dtype.itemsize)
    assert rows % t.tm == 0 and k % t.tk == 0 and n % t.tn == 0, (x.shape, t)
    tables, count = _visits(group_sizes, rows, t.tm, visit_empty=True)

    def at_x(i_n, i_k, v, offsets, gid, tid):
        return tid[v], i_k

    def at_dy(i_n, i_k, v, offsets, gid, tid):
        return tid[v], i_n

    def at_o(i_n, i_k, v, offsets, gid, tid):
        return gid[v], i_k, i_n

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // t.tn, k // t.tk, count),
            in_specs=[pl.BlockSpec((t.tm, t.tk), at_x),
                      pl.BlockSpec((t.tm, t.tn), at_dy)],
            out_specs=pl.BlockSpec((None, t.tk, t.tn), at_o),
            scratch_shapes=[pltpu.VMEM((t.tk, t.tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((e, k, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_tgmm",
    )(*tables, x, dy)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(x, w, group_sizes, interpret: Optional[bool] = None):
    """``out[rows of e] = x[rows of e] @ w[e]``; ``group_sizes`` (int32
    [E]) must add up to ``x``'s rows (rows past their sum are left
    unwritten). Differentiable in ``x`` and ``w``."""
    return _gm_fwd(x, w, group_sizes, interpret)[0]


def _gm_fwd(x, w, group_sizes, interpret):
    if interpret is None:
        interpret = _interpret_default()
    out = _gmm(x, w, group_sizes, False, None, interpret)
    return out, (x, w, group_sizes)


def _gm_bwd(interpret, res, dy):
    if interpret is None:
        interpret = _interpret_default()
    x, w, group_sizes = res
    dy = dy.astype(x.dtype)
    dx = _gmm(dy, w, group_sizes, True, None, interpret)
    dw = _tgmm(x, dy, group_sizes, None, interpret)
    return dx, dw.astype(w.dtype), None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)


def grouped_matmul_reference(x, w, group_sizes):
    """The same contraction in plain jnp: every row against the matrix of
    the group it lies in (a gather of [T, K, N] — for tests and small
    sizes only)."""
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
    group = jnp.minimum(group, w.shape[0] - 1)
    return jnp.einsum("tk,tkn->tn", x, w[group],
                      preferred_element_type=jnp.float32).astype(x.dtype)

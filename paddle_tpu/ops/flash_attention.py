"""Flash attention — Pallas TPU kernel with custom VJP.

ref: python/paddle/nn/functional/flash_attention.py:198 +
paddle/phi/kernels/gpu/flash_attn_kernel.cu (which bind the external
FlashAttention-2 CUDA library). TPU-native redesign, not a port: the
online-softmax recurrence is tiled onto the MXU with VMEM scratch
carries, following the standard flash-attention schedule:

  forward:  grid (B, H, nq, nk) — innermost k-dimension is ARBITRARY
            (sequential), carrying (m, l, acc) in f32 VMEM scratch;
            logsumexp L = m + log(l) is written as a residual.
  backward: recompute p = exp(s - L) blockwise; two kernels, one
            accumulating dq over k-blocks, one accumulating (dk, dv)
            over q-blocks — no S×S materialization anywhere.

Each kernel holds a RESIDENT block of one sequence axis (q rows in the
forward and dq, k rows in dk/dv) while blocks of the other axis are
FETCHED through VMEM and pass the vector units a SUB-tile at a time
(``Sweep``). Under a causal mask ``_spans`` — the one place that knows
the rule — says which sub-tiles see every pair (no mask is built),
which straddle the diagonal (masked) and which are dead: no dead
sub-tile is visited and no dead block fetched. A sliding ``window`` is
a second dead boundary of the same rule, behind the diagonal: sub-tiles
wholly older than the window are dead, those its edge crosses masked.
Window calls run under kernel names of their own (``flash_window_*``),
so a device trace tells them from the causal ones. A sequence short
enough is held whole, and the schedule is then settled when the kernel
is traced: its tiles unroll into straight-line code (``_sweep``).

Layouts: public API is paddle's [B, S, H, D]; kernels run [B, H, S, D].
GQA: the forward indexes kv-heads via h // group — no repeat; the
backward expands kv then reduces group-wise (dk/dv peak at q-head size,
same as the fallback's repeat).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES, fit, interpret_default

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() exact zero
                 # without inf-inf = nan hazards in the masked rows

_SEM = pltpu.GridDimensionSemantics


# ---------------------------------------------------------------------------
# the tile schedule
# ---------------------------------------------------------------------------


class Sweep(NamedTuple):
    """One kernel's tiling. A grid step holds ``held`` rows of the
    resident axis and works on ``rows`` of them at a time, while blocks
    of ``fetch`` rows of the swept axis come through VMEM, ``sub`` rows
    of them at a time through the vector units."""
    held: int
    rows: int
    fetch: int
    sub: int


_FETCH_BYTES = 512 * 1024  # of one fetched block; K and V of a
                            # 2048 x 128 bf16 head each fit whole
_UNROLL = 16                # tiles a kernel body may hold unrolled


def _sweep(resident: int, swept: int, d: int, itemsize: int) -> Sweep:
    """The tiling of one kernel — ``_block``'s successor, chosen on a
    v5e (PERF.md §6, PR 25). Tiles are 512 x 512: smaller ones pay the
    softmax state once a tile and lose. The swept axis is fetched as far
    as VMEM takes it, so a causal row of tiles fetches no dead block.
    A sequence short enough is held whole — one grid step a head: every
    bound is static then, and the tiles unroll into one basic block
    where the scheduler runs one tile's matmuls under the next one's
    vector work (a loop runs them in turn)."""
    cap = max(LANES, _FETCH_BYTES // (d * itemsize))
    rows = fit(resident, 512)
    fetch = fit(swept, cap)
    sub = fit(fetch, 512)
    whole = (fetch == swept and resident <= cap
             and (resident // rows) * (swept // sub) <= _UNROLL)
    return Sweep(resident if whole else rows, rows, fetch, sub)


def _sweeps(sq: int, sk: int, d: int, itemsize: int):
    """(forward, dq, dk/dv): the first two hold queries and sweep keys,
    the third holds keys and sweeps queries."""
    over_k = _sweep(sq, sk, d, itemsize)
    return over_k, over_k, _sweep(sk, sq, d, itemsize)


def _static(*xs) -> bool:
    return all(isinstance(x, int) for x in xs)


def _min(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _max(a, b):
    return max(a, b) if _static(a, b) else jnp.maximum(a, b)


def _clamp(x, lo, hi):
    return _min(_max(x, lo), hi)


def _spans(first, rows: int, off: int, sub: int, n: int, over_k: bool,
           window: Optional[int] = None):
    """THE rule: query i sees key j iff j <= i + off and, under a
    ``window``, i + off - j < window.

    A resident block covers ``rows`` positions from ``first`` on one
    axis; the other axis is cut into ``n`` sub-tiles of ``sub``. Returns
    ``(m_lo, f_lo, f_hi, m_hi)``: sub-tiles [f_lo, f_hi) see every pair
    of the block, [m_lo, f_lo) and [f_hi, m_hi) straddle an edge of the
    visible band and need the mask, all others are dead. ``over_k``: the
    block is of queries and keys are swept (the window's edge comes
    first, the diagonal last); else the block is of keys and queries are
    swept (the diagonal comes first). ``first`` is an int or a traced
    int32 (from a program id) and the result follows it; numerators are
    held non-negative so ``//`` floors either way.
    """
    last = first + rows - 1
    if over_k:
        full = _min(_max(first + off + 1, 0) // sub, n)
        live = _min(_max(last + off + sub, 0) // sub, n)
        if window is None:
            return 0, 0, full, live
        # keys older than every query's window / inside every query's
        dead = _min(_max(first + off - window + 1, 0) // sub, live)
        inside = (_max(last + off - window + 1, 0) + sub - 1) // sub
        f_hi = _clamp(full, dead, live)
        return dead, _clamp(inside, dead, f_hi), f_hi, live
    live = _min(_max(first - off, 0) // sub, n)
    full = _min((_max(last - off, 0) + sub - 1) // sub, n)
    if window is None:
        return live, full, n, n
    # queries whose window still holds every key of the block / any key
    inside = _max(first - off + window, 0) // sub
    end = _clamp(_max(last - off + window - 1 + sub, 0) // sub, live, n)
    f_hi = _clamp(inside, live, end)
    return live, _clamp(full, live, f_hi), f_hi, end


def kernel_names(window: Optional[int] = None):
    """The ``name=`` of the three kernels (forward, dq, dk/dv): what a
    device trace shows each call as."""
    tag = "flash" if window is None else "flash_window"
    return f"{tag}_fwd", f"{tag}_bwd_dq", f"{tag}_bwd_dkv"


class TileCounts(NamedTuple):
    full: int    # sub-tiles run without a mask
    masked: int  # sub-tiles on the diagonal, run with the mask
    dead: int    # sub-tiles never visited


def tile_plan(sq: int, sk: int, d: int, causal: bool, itemsize: int = 2,
              window: Optional[int] = None):
    """How often the schedule engages, per head, for a shape:
    ``{kernel name: (Sweep, TileCounts)}``. Static: which sub-tiles are
    masked is fixed when a kernel is traced."""
    plan = {}
    for name, t, over_k in zip(kernel_names(window),
                               _sweeps(sq, sk, d, itemsize),
                               (True, True, False)):
        resident, swept = (sq, sk) if over_k else (sk, sq)
        n = swept // t.sub
        full = masked = 0
        for first in range(0, resident, t.rows):
            m0, f0, f1, m1 = (_spans(first, t.rows, sk - sq, t.sub, n,
                                     over_k, window)
                              if causal else (0, 0, n, n))
            full, masked = full + f1 - f0, masked + (m1 - m0) - (f1 - f0)
        total = (resident // t.rows) * n
        plan[name] = (t, TileCounts(full, masked, total - full - masked))
    return plan


def _step(axis: int, steps):
    """The grid position along ``axis``: the int 0 where the axis has one
    step, so that everything derived from it is static too."""
    return 0 if steps[axis] == 1 else pl.program_id(axis)


def _when(cond, fn) -> None:
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _for(lo, hi, body) -> None:
    """``body(j)`` for j in [lo, hi): unrolled when both bounds are
    static, else a loop the size of which only the grid step knows."""
    if _static(lo, hi):
        for j in range(lo, hi):
            body(j)
    else:
        jax.lax.fori_loop(lo, hi, lambda j, c: (body(j), c)[1], 0)


def _rows_at(j, sub: int):
    """Rows [j*sub, (j+1)*sub) of a block in VMEM."""
    if _static(j):
        return pl.ds(j * sub, sub)
    return pl.ds(pl.multiple_of(j * sub, sub), sub)


def _schedule(t: Sweep, steps, causal: bool, off: int, over_k: bool,
              block, window: Optional[int]) -> None:
    """One grid step of a kernel whose grid is ``steps`` = (batch, heads,
    held blocks, fetched blocks). For each ``t.rows`` of the held block,
    ``block(r, first)`` — ``r`` their slice of the block, ``first`` their
    position on the axis — gives ``(init, tile, finish)``: ``init`` runs
    on the first fetched block, ``finish`` on the last, and between them
    ``tile(at, start, masked)`` on every live sub-tile of this step's
    fetched block (``at`` its slice of the block, ``start`` its position
    on the swept axis): the full ones bare, those on an edge of the
    visible band masked, in the order the sweep meets them."""
    ih, ib = _step(2, steps), _step(3, steps)
    per_block = t.fetch // t.sub
    n = steps[3] * per_block

    def inside(x):  # an index of the whole axis, seen from block ib
        return _min(_max(x - ib * per_block, 0), per_block)

    for a in range(t.held // t.rows):
        first = ih * t.held + a * t.rows
        init, tile, finish = block(pl.ds(a * t.rows, t.rows), first)

        def run(lo, hi, masked):
            _for(lo, hi, lambda j: tile(
                _rows_at(j, t.sub), (ib * per_block + j) * t.sub, masked))

        _when(ib == 0, init)
        if causal:
            m0, f0, f1, m1 = _spans(first, t.rows, off, t.sub, n, over_k,
                                    window)
            for lo, hi, masked in ((m0, f0, True), (f0, f1, False),
                                   (f1, m1, True)):
                if not (_static(lo, hi) and lo == hi):
                    run(inside(lo), inside(hi), masked)
        else:
            run(0, per_block, False)
        _when(ib == steps[3] - 1, finish)


def _live_block(t: Sweep, steps, off: int, window: Optional[int], held,
                swept, over_k: bool):
    """The fetched block grid step (``held``, ``swept``) reads: its own
    where the held block has a live pair in it, else the nearest that
    has — the block already in VMEM, so a dead step fetches nothing."""
    per_block = t.fetch // t.sub
    n = steps[3] * per_block
    m_lo, _, _, m_hi = _spans(held * t.held, t.held, off, t.sub, n, over_k,
                              window)
    if over_k or window is not None:
        swept = jnp.minimum(swept, _max(m_hi - 1, 0) // per_block)
    if not over_k or window is not None:
        swept = jnp.maximum(swept, _min(m_lo, n - 1) // per_block)
    return swept


def _visible(q_first, k_first, shape, off: int, q_axis: int,
             window: Optional[int]):
    """Mask of a sub-tile whose queries start at ``q_first`` along axis
    ``q_axis`` and whose keys start at ``k_first`` along the other."""
    q_abs = q_first + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_abs = k_first + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window is None:
        return q_abs + off >= k_abs
    behind = q_abs + off - k_abs
    return (behind >= 0) & (behind < window)


def _across(x, width: int):
    """A lane-replicated [rows, 128] statistic at ``width`` lanes."""
    if width == LANES:
        return x
    if width % LANES == 0:
        return jnp.tile(x, (1, width // LANES))
    if width < LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


_PARALLEL_BUT_LAST = pltpu.CompilerParams(
    dimension_semantics=(
        _SEM.PARALLEL, _SEM.PARALLEL, _SEM.PARALLEL, _SEM.ARBITRARY,
    ),
)

# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, off: int, t: Sweep, steps,
                window: Optional[int]):
    d = q_ref.shape[3]

    def block(r, first):  # t.rows queries from ``first``
        def init():
            m_scr[r] = jnp.full((t.rows, LANES), NEG_INF, jnp.float32)
            l_scr[r] = jnp.zeros((t.rows, LANES), jnp.float32)
            acc_scr[r] = jnp.zeros((t.rows, d), jnp.float32)

        def tile(at, start, masked: bool):
            s = _dot(q_ref[0, 0, r], k_ref[0, 0, at, :], _NT) * scale
            if masked:
                mask = _visible(first, start, s.shape, off, 0, window)
                s = jnp.where(mask, s, NEG_INF)          # [rows, sub] f32
            # m and l stay lane-replicated [rows, 128] from tile to tile
            m_prev = m_scr[r]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _across(m_new, t.sub))
            if masked and off < 0:
                # a fully-masked row (sq > sk only): m_new == NEG_INF
                # makes exp(s-m) == 1; zero it so the row emits 0 and l
                # stays 0. (A row that a window's edge masks in its
                # first tiles meets a live key later: alpha == 0 then
                # wipes what those tiles added.)
                p = jnp.where(mask, p, 0.0)
            l_scr[r] = l_scr[r] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = _dot(p.astype(v_ref.dtype), v_ref[0, 0, at, :], _NN)
            acc_scr[r] = acc_scr[r] * _across(alpha, d) + pv
            m_scr[r] = m_new

        def finish():
            l = l_scr[r]
            # fully-masked rows (sq > sk under a causal mask) emit zeros
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, 0, r] = (acc_scr[r] / _across(safe_l, d)).astype(o_ref.dtype)
            lse_ref[0, 0, :, r] = (m_scr[r] + jnp.log(safe_l))[:, 0][None, :]

        return init, tile, finish

    _schedule(t, steps, causal, off, True, block, window)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "interpret",
                                             "window"))
def _flash_fwd(q, k, v, scale: float, causal: bool, interpret: bool,
               window: Optional[int]):
    """q: [B, Hq, Sq, D], k/v: [B, Hkv, Sk, D] → (out [B,Hq,Sq,D],
    lse [B,Hq,Sq] in f32). Jitted so that a model's layers share one
    trace and one lowering of the kernel."""
    batch, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    off = sk - sq
    t = _sweeps(sq, sk, d, q.dtype.itemsize)[0]
    grid = (batch, hq, sq // t.held, sk // t.fetch)

    def q_map(b, h, iq, ik):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ik):
        if causal:  # a dead step re-uses the block already in VMEM
            ik = _live_block(t, grid, off, window, iq, ik, True)
        return (b, h // group, ik, 0)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               off=off, t=t, steps=grid, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, t.held, d), q_map),
            pl.BlockSpec((1, 1, t.fetch, d), kv_map),
            pl.BlockSpec((1, 1, t.fetch, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, t.held, d), q_map),
            pl.BlockSpec((1, 1, 1, t.held), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((batch, hq, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.held, LANES), jnp.float32),  # running max
            pltpu.VMEM((t.held, LANES), jnp.float32),  # running denom
            pltpu.VMEM((t.held, d), jnp.float32),       # output accumulator
        ],
        compiler_params=_PARALLEL_BUT_LAST,
        interpret=interpret,
        name=kernel_names(window)[0],
    )(q, k, v)
    return out, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale: float, causal: bool, off: int,
                   t: Sweep, steps, window: Optional[int]):
    def block(r, first):  # t.rows queries from ``first``
        def init():
            acc_scr[r] = jnp.zeros((t.rows, acc_scr.shape[1]), jnp.float32)

        def tile(at, start, masked: bool):
            k = k_ref[0, 0, at, :]
            s = _dot(q_ref[0, 0, r], k, _NT) * scale      # [rows, sub]
            if masked:
                mask = _visible(first, start, s.shape, off, 0, window)
                s = jnp.where(mask, s, NEG_INF)
            # the residual rows become columns inside the tile, where
            # the scheduler hides the relayout under the matmuls (hoisted
            # out of the loop it was exposed: 0.3 us each, PERF.md §6)
            p = jnp.exp(s - lse_ref[0, 0, 0, r][:, None])
            if masked and off < 0:
                # fully-masked rows have lse == NEG_INF -> exp(0) == 1
                p = jnp.where(mask, p, 0.0)
            dp = _dot(do_ref[0, 0, r], v_ref[0, 0, at, :], _NT)
            ds = p * (dp - delta_ref[0, 0, 0, r][:, None]) * scale
            acc_scr[r] += _dot(ds.astype(k.dtype), k, _NN)

        def finish():
            dq_ref[0, 0, r] = acc_scr[r].astype(dq_ref.dtype)

        return init, tile, finish

    _schedule(t, steps, causal, off, True, block, window)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale: float, causal: bool, off: int, t: Sweep, steps,
                    window: Optional[int]):
    """Scores are computed TRANSPOSED, [k rows, q rows]: ``lse`` and
    ``delta`` broadcast along sublanes as the rows they arrive as, and
    dv += p^T do, dk += ds^T q are plain contractions."""
    d = k_ref.shape[3]

    def block(r, first):  # t.rows keys from ``first``
        def init():
            dk_scr[r] = jnp.zeros((t.rows, d), jnp.float32)
            dv_scr[r] = jnp.zeros((t.rows, d), jnp.float32)

        def tile(at, start, masked: bool):
            q = q_ref[0, 0, at, :]                        # [sub, d]
            do = do_ref[0, 0, at, :]
            s = _dot(k_ref[0, 0, r], q, _NT) * scale      # [rows, sub] = s^T
            if masked:
                mask = _visible(start, first, s.shape, off, 1, window)
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, 0, :, at])         # lse: [1, sub]
            if masked and off < 0:
                p = jnp.where(mask, p, 0.0)
            dv_scr[r] += _dot(p.astype(do.dtype), do, _NN)
            dp = _dot(v_ref[0, 0, r], do, _NT)            # [rows, sub] = dp^T
            ds = p * (dp - delta_ref[0, 0, :, at]) * scale
            dk_scr[r] += _dot(ds.astype(q.dtype), q, _NN)

        def finish():
            dk_ref[0, 0, r] = dk_scr[r].astype(dk_ref.dtype)
            dv_ref[0, 0, r] = dv_scr[r].astype(dv_ref.dtype)

        return init, tile, finish

    _schedule(t, steps, causal, off, False, block, window)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "interpret",
                                             "window"))
def _flash_bwd(q, k, v, out, lse, do, scale: float, causal: bool,
               interpret: bool, window: Optional[int]):
    """All operands [B, H, S, D] (kv already head-expanded)."""
    batch, h, sq, d = q.shape
    sk = k.shape[2]
    off = sk - sq
    _, tq, tk = _sweeps(sq, sk, d, q.dtype.itemsize)
    _, name_dq, name_dkv = kernel_names(window)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    lse3 = lse[:, :, None, :]      # [B, H, 1, Sq]
    delta3 = delta[:, :, None, :]  # [B, H, 1, Sq]

    grid = (batch, h, sq // tq.held, sk // tq.fetch)

    def at_q(b, hh, iq, ik):
        return (b, hh, iq, 0)

    def at_k(b, hh, iq, ik):
        if causal:
            ik = _live_block(tq, grid, off, window, iq, ik, True)
        return (b, hh, ik, 0)

    def row_q(b, hh, iq, ik):
        return (b, hh, 0, iq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, off=off,
                          t=tq, steps=grid, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tq.held, d), at_q),
            pl.BlockSpec((1, 1, tq.fetch, d), at_k),
            pl.BlockSpec((1, 1, tq.fetch, d), at_k),
            pl.BlockSpec((1, 1, tq.held, d), at_q),
            pl.BlockSpec((1, 1, 1, tq.held), row_q),
            pl.BlockSpec((1, 1, 1, tq.held), row_q),
        ],
        out_specs=pl.BlockSpec((1, 1, tq.held, d), at_q),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((tq.held, d), jnp.float32)],
        compiler_params=_PARALLEL_BUT_LAST,
        interpret=interpret,
        name=name_dq,
    )(q, k, v, do, lse3, delta3)

    grid = (batch, h, sk // tk.held, sq // tk.fetch)

    def live_q(ik, iq):
        if causal:
            iq = _live_block(tk, grid, off, window, ik, iq, False)
        return iq

    def swept_q(b, hh, ik, iq):
        return (b, hh, live_q(ik, iq), 0)

    def swept_row(b, hh, ik, iq):
        return (b, hh, 0, live_q(ik, iq))

    def held_k(b, hh, ik, iq):
        return (b, hh, ik, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          off=off, t=tk, steps=grid, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tk.fetch, d), swept_q),
            pl.BlockSpec((1, 1, tk.held, d), held_k),
            pl.BlockSpec((1, 1, tk.held, d), held_k),
            pl.BlockSpec((1, 1, tk.fetch, d), swept_q),
            pl.BlockSpec((1, 1, 1, tk.fetch), swept_row),
            pl.BlockSpec((1, 1, 1, tk.fetch), swept_row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tk.held, d), held_k),
            pl.BlockSpec((1, 1, tk.held, d), held_k),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tk.held, d), jnp.float32),
            pltpu.VMEM((tk.held, d), jnp.float32),
        ],
        compiler_params=_PARALLEL_BUT_LAST,
        interpret=interpret,
        name=name_dkv,
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op: custom VJP over [B, S, H, D] layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Fused attention, paddle layout [B, S, H, D]; supports GQA
    (kv heads dividing q heads), causal masking and, under it, a sliding
    ``window``: a query sees the ``window`` newest keys up to its own."""
    out, _ = _fa_fwd(q, k, v, causal, scale, interpret, window)
    return out


def _fa_fwd(q, k, v, causal, scale, interpret, window):
    if window is not None and (not causal or window < 1):
        raise ValueError("a window counts back from the causal diagonal: "
                         f"causal={causal}, window={window}")
    if interpret is None:
        interpret = interpret_default()
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t, lse = _flash_fwd(qt, kt, vt, s, causal, interpret, window)
    return jnp.swapaxes(out_t, 1, 2), (q, k, v, out_t, lse)


def _fa_bwd(causal, scale, interpret, window, res, g):
    if interpret is None:
        interpret = interpret_default()
    q, k, v, out_t, lse = res
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    hq, hkv = q.shape[2], k.shape[2]
    group = hq // hkv
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if group > 1:
        kt = jnp.repeat(kt, group, axis=1)
        vt = jnp.repeat(vt, group, axis=1)
    do_t = jnp.swapaxes(g, 1, 2)
    dq_t, dk_t, dv_t = _flash_bwd(qt, kt, vt, out_t, lse, do_t, s, causal,
                                  interpret, window)
    if group > 1:
        b, _, sk, d = dk_t.shape
        dk_t = dk_t.reshape(b, hkv, group, sk, d).sum(axis=2)
        dv_t = dv_t.reshape(b, hkv, group, sk, d).sum(axis=2)
    return (
        jnp.swapaxes(dq_t, 1, 2),
        jnp.swapaxes(dk_t, 1, 2).astype(k.dtype),
        jnp.swapaxes(dv_t, 1, 2).astype(v.dtype),
    )


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_fwd(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """Alias used by nn.functional.scaled_dot_product_attention."""
    return flash_attention(q, k, v, causal, scale, interpret)

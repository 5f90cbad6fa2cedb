"""What Gated DeltaNet's recurrence takes, from the projection: a causal
depthwise convolution, SiLU and the per-head l2 norms as ONE pass over
HBM forward and one backward — Pallas TPU kernels with a custom VJP.

``qkv`` [B, S, C] holds ``[q | k | v]`` side by side, heads contiguous
(``C = 2 Hk d_k + Hv d_v``); ``w`` [taps, C], tap 0 the oldest position,
zeros before the sequence. For every channel, in float32::

    p_t = sum_j w_j x_(t - (taps - 1 - j));      a = p sigmoid(p)
    q = a rsqrt(sum_head a^2 + eps) / sqrt(d_k)      per head of d_k
    k = a rsqrt(sum_head a^2 + eps);                 v = a

-> q, k [B, S, Hk, d_k], v [B, S, Hv, d_v] in ``qkv``'s type. Only the
read of ``qkv`` and the writes of q, k, v are in that type: nothing of
[S, C] in float32 reaches HBM.

``gdn_inputs_fwd`` (grid: batch, blocks of the sequence, blocks of the
columns) reads a block [rows, columns] of ``qkv`` and, as a second view
of the same array, the ``HALO`` rows before it (a block's first
``taps - 1`` rows are convolved with them). A column block is whole
heads, so a head's norm is a lane reduction inside it; it lies in q, in
k or in v, and the kernel writes the one of its three outputs it lies
in (the other two keep the block they hold: their index does not move).
Inside a block the kernels walk strips of ``STRIP`` rows of one head,
carrying a strip's last rows to the next, so that a strip's float32
stays in vector registers (a whole block at a time, every step of the
chain stores and loads the block).

``gdn_inputs_bwd`` keeps ``qkv`` and ``w`` alone from the forward pass
and computes p, a and the norms again. ``d qkv`` at row t takes ``dp``
at rows t .. t + taps - 1, so it walks the sequence's blocks in reverse
and carries each column block's first rows of ``dp`` in scratch; inside
a block ``dp`` goes to VMEM scratch strip by strip and a second walk
reads it. ``d w`` adds up in float32 in an output block that stays
resident over the whole grid.

A head that is no multiple of 128 lanes, or a sequence no block divides,
is a ``ValueError``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import (COLUMNS, HALO, IN_ORDER, PARALLEL, ROWS, STRIP,
                            TILE, advanced, interpret_default, strips)

__all__ = ["conv_silu_l2norm", "KERNELS"]

# neither name holds ``gdn_fwd`` / ``gdn_bwd``: the recurrence's roofline
# readers time every kernel whose name does
KERNELS = ("gdn_inputs_fwd", "gdn_inputs_bwd")
EPS = 1e-6                       # inside the norm's root


def _unit(a, scale):
    """-> (``scale a r``, ``scale r``) with r = rsqrt(sum a^2 + eps) over
    the lanes: ``a`` is one head's."""
    r = scale * jax.lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + EPS)
    return a * r, r


def _in_its_part(j, nq, dk, refs, run):
    """Column block ``j`` lies in q (the first ``nq`` blocks), in k (the
    next ``nq``) or in v: ``run(ref, scale)`` with that part's ref of
    ``refs`` and its norm's scale (None: v has no norm)."""
    q, k, v = refs
    pl.when(j < nq)(lambda: run(q, 1.0 / math.sqrt(dk)))
    pl.when((j >= nq) & (j < 2 * nq))(lambda: run(k, 1.0))
    pl.when(j >= 2 * nq)(lambda: run(v, None))


def _fwd_kernel(x_ref, before_ref, w_ref, q_ref, k_ref, v_ref, *, nq, dk):
    j = pl.program_id(2)
    first = pl.program_id(1) == 0

    def write(ref, scale):
        def strip(rows, lanes, xs, p, kept):
            a = p * jax.nn.sigmoid(p)
            if scale is not None:
                a = _unit(a, scale)[0]
            ref[0, rows, lanes] = a.astype(ref.dtype)
            return kept
        strips(x_ref, before_ref, w_ref, first, dk, strip)

    _in_its_part(j, nq, dk, (q_ref, k_ref, v_ref), write)


def _bwd_kernel(x_ref, before_ref, w_ref, dq_ref, dk_ref, dv_ref,
                dx_ref, dw_ref, dp_scr, after_scr, *, nq, dk):
    i, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(1) - 1            # blocks are walked in reverse
    rows_in_block, taps = x_ref.shape[1], w_ref.shape[0]

    @pl.when(t == 0)
    def _():
        after_scr[j] = jnp.zeros(after_scr.shape[1:], jnp.float32)

    @pl.when((i == 0) & (t == 0))
    def _():
        dw_ref[j] = jnp.zeros(dw_ref.shape[1:], jnp.float32)

    def through(ref, scale):
        """``dp`` of the block into ``dp_scr``; -> the taps' sums of
        ``dp`` x input over the block's rows, eight partial rows each."""
        def strip(rows, lanes, xs, p, sums):
            sig = jax.nn.sigmoid(p)
            a = p * sig
            da = ref[0, rows, lanes].astype(jnp.float32)
            if scale is not None:
                # y = a r, r = scale rsqrt(sum a^2 + eps):
                # da = r dy - y r sum(dy y) / scale^2
                y, r = _unit(a, scale)
                along = jnp.sum(da * y, axis=1, keepdims=True)
                da = r * da - y * (along * r * (1.0 / scale) ** 2)
            dp = da * (sig * (1.0 + p * (1.0 - sig)))
            dp_scr[rows, lanes] = dp
            out = []
            for tap, acc in enumerate(sums):
                part = dp * xs[taps - 1 - tap]
                for at in range(0, STRIP, TILE):
                    acc = acc + part[at:at + TILE]
                out.append(acc)
            return out

        zero = jnp.zeros((TILE, dk), jnp.float32)
        return strips(x_ref, before_ref, w_ref, t == last, dk, strip,
                      [zero] * taps)

    def add_up(sums):
        for h, head in enumerate(sums):
            for tap, acc in enumerate(head):
                dw_ref[j, tap:tap + 1, h * dk:(h + 1) * dk] += jnp.sum(
                    acc, axis=0, keepdims=True)

    _in_its_part(j, nq, dk, (dq_ref, dk_ref, dv_ref),
                 lambda ref, scale: add_up(through(ref, scale)))

    # d x_t = sum_d w_(taps-1-d) dp_(t+d): the rows past the block are
    # the first of the block after it, visited one step ago
    dp_scr[rows_in_block:, :] = after_scr[j]
    after_scr[j] = dp_scr[:TILE, :]

    def to_dx(s, _):
        rows = pl.ds(pl.multiple_of(s * STRIP, STRIP), STRIP)
        after = pl.ds(pl.multiple_of((s + 1) * STRIP, STRIP), TILE)
        for h in range(x_ref.shape[2] // dk):
            lanes = slice(h * dk, (h + 1) * dk)
            dp, w = dp_scr[rows, lanes], w_ref[:, lanes]
            dx = dp * w[taps - 1:taps]
            for d in range(1, taps):
                dx = dx + advanced(dp, dp_scr[after, lanes], d) \
                    * w[taps - 1 - d:taps - d]
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
        return 0

    jax.lax.fori_loop(0, rows_in_block // STRIP, to_dx, 0)


def _blocks(s: int, c: int, key: int, dk: int, taps: int):
    """(rows, columns) of a grid step: rows dividing ``s``, columns whole
    heads dividing the width of q (= k's) and v's."""
    if dk % 128:
        raise ValueError(f"a head of {dk} is no multiple of 128 lanes")
    if taps - 1 > TILE:
        raise ValueError(f"{taps} taps reach past the {TILE} rows kept")
    rows = next((b for b in (ROWS, ROWS // 2, ROWS // 4) if s % b == 0),
                None)
    if rows is None:
        raise ValueError(f"sequence {s} is no multiple of a block of "
                         f"{ROWS // 4} rows")
    heads = max(COLUMNS // dk, 1)
    while key % (heads * dk) or (c - 2 * key) % (heads * dk):
        heads -= 1
        if not heads:
            raise ValueError(f"v's {c - 2 * key} columns are not whole "
                             f"heads of {dk}")
    return rows, heads * dk


def _specs(rows, cols, nq, nv, taps, block_of):
    """The blocks of a grid step (batch i, step t, column block j):
    ``qkv``'s, the rows before it, the taps' weights, and q's, k's, v's
    (a column block lies in one of the three; the other two stay where
    they are); ``block_of(t)``: the sequence block."""
    def clipped(first, n):
        return lambda i, t, j: (i, block_of(t),
                                jnp.clip(j - first, 0, n - 1))

    return (pl.BlockSpec((1, rows, cols), lambda i, t, j: (i, block_of(t), j)),
            pl.BlockSpec((1, HALO, cols), lambda i, t, j: (
                i, jnp.maximum(block_of(t) * (rows // HALO) - 1, 0), j)),
            pl.BlockSpec((taps, cols), lambda i, t, j: (0, j)),
            pl.BlockSpec((1, rows, cols), clipped(0, nq)),
            pl.BlockSpec((1, rows, cols), clipped(nq, nq)),
            pl.BlockSpec((1, rows, cols), clipped(2 * nq, nv)))


@functools.partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv",
                                             "interpret"))
def _gdn_inputs_fwd(qkv, w, hk, hv, dk, dv, interpret: bool):
    """-> q, k [B, S, Hk dk], v [B, S, Hv dv]. Jitted so that a model's
    layers share one trace and lowering."""
    b, s, c = qkv.shape
    taps, key = w.shape[0], hk * dk
    rows, cols = _blocks(s, c, key, dk, taps)
    nq, nv = key // cols, hv * dv // cols
    x, before, weights, q, k, v = _specs(rows, cols, nq, nv, taps,
                                         lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nq=nq, dk=dk),
        grid=(b, s // rows, 2 * nq + nv),
        in_specs=[x, before, weights],
        out_specs=[q, k, v],
        out_shape=[jax.ShapeDtypeStruct((b, s, key), qkv.dtype),
                   jax.ShapeDtypeStruct((b, s, key), qkv.dtype),
                   jax.ShapeDtypeStruct((b, s, hv * dv), qkv.dtype)],
        compiler_params=PARALLEL,
        interpret=interpret,
        name=KERNELS[0],
    )(qkv, qkv, w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv",
                                             "interpret"))
def _gdn_inputs_bwd(qkv, w, dq, dk_, dv_, hk, hv, dk, dv, interpret: bool):
    """-> d qkv [B, S, C] in ``qkv``'s type, d w [taps, C] float32."""
    b, s, c = qkv.shape
    taps, key = w.shape[0], hk * dk
    rows, cols = _blocks(s, c, key, dk, taps)
    nq, nv = key // cols, hv * dv // cols
    n, last = 2 * nq + nv, s // rows - 1
    x, before, weights, q, k, v = _specs(rows, cols, nq, nv, taps,
                                         lambda t: last - t)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, nq=nq, dk=dk),
        grid=(b, s // rows, n),
        in_specs=[x, before, weights, q, k, v],
        out_specs=[x, pl.BlockSpec((n, taps, cols), lambda i, t, j: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, c), qkv.dtype),
                   jax.ShapeDtypeStruct((n, taps, cols), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows + TILE, cols), jnp.float32),
                        pltpu.VMEM((n, TILE, cols), jnp.float32)],
        compiler_params=IN_ORDER,
        interpret=interpret,
        name=KERNELS[1],
    )(qkv, qkv, w.astype(jnp.float32), dq, dk_, dv_)
    return dx, dw.transpose(1, 0, 2).reshape(taps, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def conv_silu_l2norm(qkv, w, hk: int, hv: int, dk: int, dv: int,
                     interpret: Optional[bool] = None):
    """The equations of the module docstring: ``qkv`` [B, S, 2 hk dk +
    hv dv], ``w`` [taps, the same] -> q, k [B, S, hk, dk], v [B, S, hv,
    dv]."""
    return _inputs_fwd(qkv, w, hk, hv, dk, dv, interpret)[0]


def _inputs_fwd(qkv, w, hk, hv, dk, dv, interpret):
    b, s, c = qkv.shape
    if c != 2 * hk * dk + hv * dv or w.shape[1] != c:
        raise ValueError(f"{c} columns (the taps' {w.shape[1]}) for "
                         f"{hk} + {hk} heads of {dk} and {hv} of {dv}")
    if interpret is None:
        interpret = interpret_default()
    q, k, v = _gdn_inputs_fwd(qkv, w, hk, hv, dk, dv, interpret)
    return ((q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
             v.reshape(b, s, hv, dv)), (qkv, w))


def _inputs_bwd(hk, hv, dk, dv, interpret, res, cotangents):
    if interpret is None:
        interpret = interpret_default()
    qkv, w = res
    b, s, _ = qkv.shape
    dq, dk_, dv_ = (d.reshape(b, s, -1) for d in cotangents)
    dx, dw = _gdn_inputs_bwd(qkv, w, dq, dk_, dv_, hk, hv, dk, dv, interpret)
    return dx, dw.astype(w.dtype)


conv_silu_l2norm.defvjp(_inputs_fwd, _inputs_bwd)

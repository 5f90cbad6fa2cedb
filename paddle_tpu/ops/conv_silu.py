"""A causal depthwise convolution with a bias, then SiLU, as ONE pass over
HBM forward and one backward — Pallas TPU kernels with a custom VJP (what
a Mamba-2 layer does to ``[x | B | C]`` before its scan).

``x`` [B, S, C]; ``w`` [taps, C], tap 0 the oldest position, zeros before
the sequence; ``b`` [C]. For every channel, in float32::

    p_t = b + sum_j w_j x_(t - (taps - 1 - j));      y = p sigmoid(p)

-> ``y`` [B, S, C] in ``x``'s type. Only the read of ``x`` and the write
of ``y`` are in that type: nothing of [S, C] in float32 reaches HBM.

The kernels are ``ops/gdn_inputs.py``'s without the split into q, k, v
and without the l2 norms, and with the bias that file has no place for
(its columns are whole heads of one of three outputs; here a column block
is any ``_LANES`` lanes of one output): the two share their walk
(``pallas_common.strips``: strips of 64 rows of 128 lanes, so that a
strip's float32 stays in vector registers; the ``taps - 1`` rows before a
block as a second view of the same array) and its constants.
``conv_silu_bwd`` keeps ``x``, ``w`` and
``b`` alone from the forward pass and computes ``p`` again; it walks the
sequence's blocks in reverse, carries each column block's first rows of
``dp`` in scratch, and adds ``d w`` and ``d b`` up in float32 in an
output block that stays resident over the whole grid.

Channels that are no multiple of 128 lanes, or a sequence no block
divides, are a ``ValueError``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import (COLUMNS, HALO, IN_ORDER, PARALLEL, ROWS, STRIP,
                            TILE, advanced, interpret_default, strips)

__all__ = ["conv_silu", "KERNELS"]

KERNELS = ("conv_silu_fwd", "conv_silu_bwd")
_LANES = 128                     # lanes a strip holds: a vector register's


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, y_ref):
    def strip(rows, lanes, xs, p, kept):
        p = p + b_ref[:, lanes]
        y_ref[0, rows, lanes] = (p * jax.nn.sigmoid(p)).astype(y_ref.dtype)
        return kept

    strips(x_ref, before_ref, w_ref, pl.program_id(1) == 0, _LANES, strip)


def _bwd_kernel(x_ref, before_ref, w_ref, b_ref, dy_ref, dx_ref, dwb_ref,
                dp_scr, after_scr):
    i, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(1) - 1            # blocks are walked in reverse
    rows_in_block, taps = x_ref.shape[1], w_ref.shape[0]

    @pl.when(t == 0)
    def _():
        after_scr[j] = jnp.zeros(after_scr.shape[1:], jnp.float32)

    @pl.when((i == 0) & (t == 0))
    def _():
        dwb_ref[j] = jnp.zeros(dwb_ref.shape[1:], jnp.float32)

    def strip(rows, lanes, xs, p, sums):
        """``dp`` of the strip into ``dp_scr``; -> the taps' sums of
        ``dp`` x input and the bias's sum of ``dp``, eight partial rows
        each."""
        p = p + b_ref[:, lanes]
        sig = jax.nn.sigmoid(p)
        dp = dy_ref[0, rows, lanes].astype(jnp.float32) * (
            sig * (1.0 + p * (1.0 - sig)))
        dp_scr[rows, lanes] = dp
        out = []
        for tap, acc in enumerate(sums):
            part = dp * xs[taps - 1 - tap] if tap < taps else dp
            for at in range(0, STRIP, TILE):
                acc = acc + part[at:at + TILE]
            out.append(acc)
        return out

    zero = jnp.zeros((TILE, _LANES), jnp.float32)
    sums = strips(x_ref, before_ref, w_ref, t == last, _LANES, strip,
                  [zero] * (taps + 1))
    for h, group in enumerate(sums):
        for row, acc in enumerate(group):
            dwb_ref[j, row:row + 1, h * _LANES:(h + 1) * _LANES] += jnp.sum(
                acc, axis=0, keepdims=True)

    # d x_t = sum_d w_(taps-1-d) dp_(t+d): the rows past the block are
    # the first of the block after it, visited one step ago
    dp_scr[rows_in_block:, :] = after_scr[j]
    after_scr[j] = dp_scr[:TILE, :]

    def to_dx(s, _):
        rows = pl.ds(pl.multiple_of(s * STRIP, STRIP), STRIP)
        after = pl.ds(pl.multiple_of((s + 1) * STRIP, STRIP), TILE)
        for h in range(x_ref.shape[2] // _LANES):
            lanes = slice(h * _LANES, (h + 1) * _LANES)
            dp, w = dp_scr[rows, lanes], w_ref[:, lanes]
            dx = dp * w[taps - 1:taps]
            for d in range(1, taps):
                dx = dx + advanced(dp, dp_scr[after, lanes], d) \
                    * w[taps - 1 - d:taps - d]
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
        return 0

    jax.lax.fori_loop(0, rows_in_block // STRIP, to_dx, 0)


def _blocks(s: int, c: int, taps: int):
    """(rows, columns) of a grid step: rows dividing ``s``, columns whole
    registers dividing ``c``."""
    if c % _LANES:
        raise ValueError(f"{c} channels are no multiple of {_LANES} lanes")
    if taps - 1 > TILE:
        raise ValueError(f"{taps} taps reach past the {TILE} rows kept")
    rows = next((b for b in (ROWS, ROWS // 2, ROWS // 4) if s % b == 0),
                None)
    if rows is None:
        raise ValueError(f"sequence {s} is no multiple of a block of "
                         f"{ROWS // 4} rows")
    cols = next(n * _LANES for n in range(COLUMNS // _LANES, 0, -1)
                if c % (n * _LANES) == 0)
    return rows, cols


def _specs(rows, cols, taps, block_of):
    """The blocks of a grid step (batch i, step t, column block j): the
    input's (and the output's), the rows before it, the taps' weights and
    the bias; ``block_of(t)``: the sequence block."""
    return (pl.BlockSpec((1, rows, cols), lambda i, t, j: (i, block_of(t), j)),
            pl.BlockSpec((1, HALO, cols), lambda i, t, j: (
                i, jnp.maximum(block_of(t) * (rows // HALO) - 1, 0), j)),
            pl.BlockSpec((taps, cols), lambda i, t, j: (0, j)),
            pl.BlockSpec((1, cols), lambda i, t, j: (0, j)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_silu_fwd(x, w, b, interpret: bool):
    """-> y [B, S, C]. Jitted so that a model's layers share one trace and
    lowering."""
    bsz, s, c = x.shape
    taps = w.shape[0]
    rows, cols = _blocks(s, c, taps)
    block, before, weights, bias = _specs(rows, cols, taps, lambda t: t)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, s // rows, c // cols),
        in_specs=[block, before, weights, bias],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=PARALLEL,
        interpret=interpret,
        name=KERNELS[0],
    )(x, x, w.astype(jnp.float32), b.astype(jnp.float32)[None, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _conv_silu_bwd(x, w, b, dy, interpret: bool):
    """-> d x [B, S, C] in ``x``'s type, d w [taps, C] and d b [C]
    float32."""
    bsz, s, c = x.shape
    taps = w.shape[0]
    rows, cols = _blocks(s, c, taps)
    n, last = c // cols, s // rows - 1
    block, before, weights, bias = _specs(rows, cols, taps,
                                          lambda t: last - t)
    dx, dwb = pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, s // rows, n),
        in_specs=[block, before, weights, bias, block],
        out_specs=[block, pl.BlockSpec((n, taps + 1, cols),
                                       lambda i, t, j: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, taps + 1, cols), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows + TILE, cols), jnp.float32),
                        pltpu.VMEM((n, TILE, cols), jnp.float32)],
        compiler_params=IN_ORDER,
        interpret=interpret,
        name=KERNELS[1],
    )(x, x, w.astype(jnp.float32), b.astype(jnp.float32)[None, :], dy)
    dwb = dwb.transpose(1, 0, 2).reshape(taps + 1, c)
    return dx, dwb[:taps], dwb[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv_silu(x, w, b, interpret: Optional[bool] = None):
    """The equations of the module docstring: ``x`` [B, S, C], ``w``
    [taps, C], ``b`` [C] -> y [B, S, C]."""
    return _rule_fwd(x, w, b, interpret)[0]


def _rule_fwd(x, w, b, interpret):
    if w.shape[1] != x.shape[2] or b.shape != (x.shape[2],):
        raise ValueError(f"taps {w.shape} and bias {b.shape} for "
                         f"{x.shape[2]} channels")
    if interpret is None:
        interpret = interpret_default()
    return _conv_silu_fwd(x, w, b, interpret), (x, w, b)


def _rule_bwd(interpret, res, dy):
    if interpret is None:
        interpret = interpret_default()
    x, w, b = res
    dx, dw, db = _conv_silu_bwd(x, w, b, dy, interpret)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)


conv_silu.defvjp(_rule_fwd, _rule_bwd)

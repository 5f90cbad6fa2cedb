"""Gated DeltaNet's output gate: the per-head RMSNorm, its gain and the
SiLU gate as ONE pass over HBM forward and one backward — Pallas TPU
kernels with a custom VJP.

``o``, ``z`` [B, S, H d] hold H heads of ``d`` lanes side by side; ``w``
[d] is the gain every head shares. For every head, in float32::

    n   = o rsqrt(mean_head(o^2) + eps)
    out = n w z sigmoid(z)

-> ``out`` [B, S, H d] in ``o``'s type. Only the reads of ``o``, ``z``
and the write of ``out`` are in that type: nothing of [S, H d] in
float32 reaches HBM.

``gated_norm_fwd`` (grid: batch, blocks of the sequence, blocks of the
columns) reads a block [rows, columns] of ``o`` and of ``z``. A column
block is whole heads, so a head's mean is a lane reduction inside it.
Inside a block the kernels walk strips of ``STRIP`` rows of one head,
as ``ops/gdn_inputs.py``'s do, so that a strip's float32 stays in vector
registers.

``gated_norm_bwd`` keeps ``o``, ``z`` and ``w`` alone from the forward
pass and computes the statistics again; it writes ``d o`` and ``d z`` in
their inputs' type. ``d w`` adds up in float32 in an output block that
stays resident over the whole grid.

A head that is no multiple of 128 lanes, or a sequence no block divides,
is a ``ValueError``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_common import (COLUMNS, IN_ORDER, PARALLEL, ROWS, STRIP, TILE,
                            interpret_default)

__all__ = ["gated_rms_norm", "KERNELS"]

# neither name holds ``gdn_fwd`` / ``gdn_bwd``: the recurrence's roofline
# readers time every kernel whose name does
KERNELS = ("gated_norm_fwd", "gated_norm_bwd")


def _strips(ref, d, strip, carried):
    """Walk the block a strip of ``STRIP`` rows and a head of ``d`` lanes
    at a time: ``strip(rows, lanes, carried)`` returns what to carry to
    the next call. -> ``carried`` after the last strip."""
    heads = [slice(h * d, (h + 1) * d) for h in range(ref.shape[2] // d)]

    def body(s, kept):
        rows = pl.ds(pl.multiple_of(s * STRIP, STRIP), STRIP)
        for lanes in heads:
            kept = strip(rows, lanes, kept)
        return kept

    return jax.lax.fori_loop(0, ref.shape[1] // STRIP, body, carried)


def _normed(ref, rows, lanes, eps):
    """-> (``n``, ``r``) of one head's strip: ``n = o r``, ``r`` the
    reciprocal root of the head's mean square."""
    f = ref[0, rows, lanes].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(f * f, axis=1, keepdims=True) + eps)
    return f * r, r


def _fwd_kernel(o_ref, z_ref, w_ref, out_ref, *, eps):
    w = w_ref[...]

    def strip(rows, lanes, kept):
        n, _ = _normed(o_ref, rows, lanes, eps)
        z = z_ref[0, rows, lanes].astype(jnp.float32)
        out_ref[0, rows, lanes] = (n * w * (z * jax.nn.sigmoid(z))).astype(
            out_ref.dtype)
        return kept

    _strips(o_ref, w.shape[1], strip, 0)


def _bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, eps):
    first = [pl.program_id(axis) == 0 for axis in range(3)]

    @pl.when(first[0] & first[1] & first[2])
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    w = w_ref[...]

    def strip(rows, lanes, acc):
        """``d o`` and ``d z`` of the strip; -> the sum of ``dy n g`` over
        the rows so far, eight partial rows."""
        n, r = _normed(o_ref, rows, lanes, eps)
        z = z_ref[0, rows, lanes].astype(jnp.float32)
        sig = jax.nn.sigmoid(z)
        g = z * sig
        dy = dy_ref[0, rows, lanes].astype(jnp.float32)
        # out = n w g with n = o r, r = rsqrt(mean o^2 + eps):
        # do = r (dn - n mean(dn n))
        dn = dy * (w * g)
        do_ref[0, rows, lanes] = (
            r * (dn - n * jnp.mean(dn * n, axis=1, keepdims=True))).astype(
                do_ref.dtype)
        along = dy * n
        dz_ref[0, rows, lanes] = (
            along * w * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)
        part = along * g
        for at in range(0, STRIP, TILE):
            acc = acc + part[at:at + TILE]
        return acc

    zero = jnp.zeros((TILE, w.shape[1]), jnp.float32)
    dw_ref[...] += _strips(o_ref, w.shape[1], strip, zero)


def _blocks(s: int, c: int, d: int):
    """(rows, columns) of a grid step: rows dividing ``s``, columns whole
    heads dividing ``c``."""
    if d % 128:
        raise ValueError(f"a head of {d} is no multiple of 128 lanes")
    if c % d:
        raise ValueError(f"{c} columns are not whole heads of {d}")
    rows = next((b for b in (ROWS, ROWS // 2, ROWS // 4) if s % b == 0),
                None)
    if rows is None:
        raise ValueError(f"sequence {s} is no multiple of a block of "
                         f"{ROWS // 4} rows")
    heads = next(n for n in range(max(COLUMNS // d, 1), 0, -1)
                 if (c // d) % n == 0)
    return rows, heads * d


def _specs(rows, cols, d):
    """The blocks of a grid step (batch i, step t, column block j): a
    block of ``o`` (of ``z``, of a result) and the gain."""
    return (pl.BlockSpec((1, rows, cols), lambda i, t, j: (i, t, j)),
            pl.BlockSpec((1, d), lambda i, t, j: (0, 0)))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _gated_norm_fwd(o, z, w, eps: float, interpret: bool):
    """-> out [B, S, H d]. Jitted so that a model's layers share one trace
    and lowering."""
    b, s, c = o.shape
    d = w.shape[0]
    rows, cols = _blocks(s, c, d)
    block, gain = _specs(rows, cols, d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(b, s // rows, c // cols),
        in_specs=[block, block, gain],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=PARALLEL,
        interpret=interpret,
        name=KERNELS[0],
    )(o, z, w.astype(jnp.float32)[None, :])


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _gated_norm_bwd(o, z, w, dy, eps: float, interpret: bool):
    """-> d o, d z [B, S, H d] in ``o``'s and ``z``'s type, d w [d]
    float32."""
    b, s, c = o.shape
    d = w.shape[0]
    rows, cols = _blocks(s, c, d)
    block, gain = _specs(rows, cols, d)
    do, dz, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(b, s // rows, c // cols),
        in_specs=[block, block, gain, block],
        out_specs=[block, block,
                   pl.BlockSpec((TILE, d), lambda i, t, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((TILE, d), jnp.float32)],
        compiler_params=IN_ORDER,
        interpret=interpret,
        name=KERNELS[1],
    )(o, z, w.astype(jnp.float32)[None, :], dy)
    return do, dz, dw.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_rms_norm(o, z, w, eps: float, interpret: Optional[bool] = None):
    """The equations of the module docstring: ``o``, ``z`` [B, S, H d],
    ``w`` [d] -> out [B, S, H d]."""
    return _rule_fwd(o, z, w, eps, interpret)[0]


def _rule_fwd(o, z, w, eps, interpret):
    if z.shape != o.shape or o.ndim != 3 or w.ndim != 1:
        raise ValueError(f"o {o.shape}, z {z.shape} and a gain {w.shape}")
    # a ValueError where the kernels cannot take the shape
    _blocks(o.shape[1], o.shape[2], w.shape[0])
    if interpret is None:
        interpret = interpret_default()
    return _gated_norm_fwd(o, z, w, float(eps), interpret), (o, z, w)


def _rule_bwd(eps, interpret, res, dy):
    if interpret is None:
        interpret = interpret_default()
    o, z, w = res
    do, dz, dw = _gated_norm_bwd(o, z, w, dy, float(eps), interpret)
    return do, dz, dw.astype(w.dtype)


gated_rms_norm.defvjp(_rule_fwd, _rule_bwd)

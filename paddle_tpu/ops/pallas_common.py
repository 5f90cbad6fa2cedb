"""What more than one Pallas kernel file of this directory uses, in ONE
place: no kernel file imports another kernel file, every one imports
this (``tests/test_layering.py``). Four decisions live here:

* how a kernel runs off the chip: ``interpret_default``;
* how a block size is fitted to an axis: ``fit`` over ``LANES``;
* how a product is issued to the MXU: the dimension numbers ``NN``,
  ``NT``, ``TN`` and ``mm`` (operands in one type, float32 sums), and
  ``column`` / ``put_column`` for a head's column of a [rows, heads]
  block;
* how an elementwise kernel over [tokens, channels] walks its block:
  ``ROWS`` x ``COLUMNS`` a grid step, ``strips`` of ``STRIP`` rows with a
  ``HALO`` before the block for a causal convolution's taps,
  ``advanced`` for the transposed taps, and the two ``CompilerParams``
  (``PARALLEL``: a block is its own; ``IN_ORDER``: a block carries to
  the next).

Edit it rarely: a Mosaic kernel's payload holds the source lines of
what it inlines, so an edit here makes every step that holds a kernel
compile afresh once (ROADMAP trap 2). What ONE kernel file uses stays
private in that file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


def fit(size: int, cap: int) -> int:
    """Largest of cap, cap/2, ... 128 dividing ``size``; else the whole
    axis (a block equal to the array's dimension is always legal)."""
    b = cap
    while b >= LANES:
        if size % b == 0:
            return b
        b //= 2
    return size


# ---------------------------------------------------------------------------
# products on the MXU
# ---------------------------------------------------------------------------

NN = (((1,), (0,)), ((), ()))   # a @ b
NT = (((1,), (1,)), ((), ()))   # a @ b.T
TN = (((0,), (0,)), ((), ()))   # a.T @ b


def mm(a, b, dims, dtype):
    """A matmul with its operands in ``dtype``, added up in float32."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=(jax.lax.Precision.HIGHEST if dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32)


def column(block, head):
    """Column ``head`` (a grid index) of block [rows, heads] as [rows, 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1, keepdims=True)


def put_column(ref, head, columns, first):
    """Write ``columns`` (each [rows, 1]) into the columns from ``head`` on
    of the resident block ``ref`` [1, rows, heads]; the first grid step to
    visit clears it."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape[1:], 1)
    held = jnp.where(first, 0.0, ref[0])
    for j, column in enumerate(columns):
        held = jnp.where(lane == head + j, column, held)
    ref[0] = held


# ---------------------------------------------------------------------------
# the strip walk of the elementwise kernels
# ---------------------------------------------------------------------------

ROWS = 256                      # tokens a grid step holds
COLUMNS = 512                   # channels a grid step holds, at most
HALO = 16                       # rows of the view before a block: one
#                                 tile of a 16-bit type, >= taps - 1
TILE = 8                        # float32 rows a vector register holds
STRIP = 64                      # rows a kernel's inner step computes

_SEM = pltpu.GridDimensionSemantics
PARALLEL = pltpu.CompilerParams(
    dimension_semantics=(_SEM.PARALLEL, _SEM.PARALLEL, _SEM.ARBITRARY))
IN_ORDER = pltpu.CompilerParams(
    dimension_semantics=(_SEM.ARBITRARY, _SEM.ARBITRARY, _SEM.ARBITRARY))


def _delayed(x, before, d):
    """Row r of the result is row r - d of ``x`` [strip, lanes], and of
    ``before`` (the ``TILE`` rows before ``x``) where r < d."""
    if d == 0:
        return x
    whole = jnp.concatenate([before, x], axis=0)
    return pltpu.roll(whole, d, 0)[TILE:]


def advanced(x, after, d):
    """Row r of the result is row r + d of ``x`` [strip, lanes], and of
    ``after`` (the ``TILE`` rows after ``x``) where that is past it."""
    if d == 0:
        return x
    whole = jnp.concatenate([x, after], axis=0)
    return pltpu.roll(whole, whole.shape[0] - d, 0)[:x.shape[0]]


def strips(x_ref, before_ref, w_ref, first, dk, strip, carried=()):
    """Walk the block a strip of ``STRIP`` rows and a head of ``dk``
    lanes at a time, in the sequence's order, so that what a strip
    computes stays in vector registers: ``strip(rows, lanes, xs, p,
    carried)`` gets the taps' inputs ``xs`` (newest first) and ``p``
    (float32 [STRIP, dk]) and returns what to carry to the next strip's
    call for the same head. -> ``carried`` after the last strip, a list
    over the block's heads."""
    cols = x_ref.shape[2]
    taps = w_ref.shape[0]
    heads = [slice(h * dk, (h + 1) * dk) for h in range(cols // dk)]
    halo = before_ref[0].astype(jnp.float32)[HALO - TILE:]
    halo = jnp.where(first, 0.0, halo)

    def body(s, state):
        rows = pl.ds(pl.multiple_of(s * STRIP, STRIP), STRIP)
        out = []
        for lanes, (before, kept) in zip(heads, state):
            x = x_ref[0, rows, lanes].astype(jnp.float32)
            w = w_ref[:, lanes]
            xs = [_delayed(x, before, d) for d in range(taps)]
            p = xs[0] * w[taps - 1:taps]
            for d in range(1, taps):
                p = p + xs[d] * w[taps - 1 - d:taps - d]
            out.append((x[STRIP - TILE:], strip(rows, lanes, xs, p, kept)))
        return out

    state = [(halo[:, lanes], carried) for lanes in heads]
    state = jax.lax.fori_loop(0, x_ref.shape[1] // STRIP, body, state)
    return [kept for _, kept in state]

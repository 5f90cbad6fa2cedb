"""Mamba-2's selective state-space scan in its chunked, "state-space
duality" form (Dao & Gu, arXiv:2405.21060) — Pallas TPU kernels with a
custom VJP.

For every head ``h``, from a zero state ``S`` [P, N] (float32)::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t + D_h x_t

``x`` [B, S, H P], the heads side by side along the last axis as the
projection leaves them (a [B, S, H, 64] array would be laid out for its
last two axes and copied on its way in and out), ``dt`` [B, S, H] (> 0:
the model's softplus is outside), ``A`` [H] (< 0), ``B``, ``C`` [B, S, N]
— ONE group: every head reads the same B and C —, ``D`` [H]; returns
``y`` [B, S, H P]. The decay
``exp(dt_t A_h)`` is a function of the INPUT, by token and head:
``ops/lightning_attention.py`` (a constant a head) and
``ops/gated_delta_rule.py`` (the delta rule's write, q and k a head)
cannot stand in.

The sequence is walked a CHUNK of 128 tokens at a time, in the form that
never DIVIDES by a decay. With ``cs_i`` the running sum of ``dt_t A_h``
from the chunk's first token to ``i`` (float32, made outside the kernels:
[S, H] numbers), ``Xd = dt * X`` and ``T`` the state entering the chunk,
transposed [N, P]::

    Y  = ((C B^T) * L) Xd + e * (C T) + D X     L_ij = exp(cs_i - cs_j), i >= j
    T' = exp(cs_last) T + B^T (f * Xd)          e_i = exp(cs_i)
                                                f_j = exp(cs_last - cs_j)

Every exponent is a difference that is <= 0: a fast head's
``exp(cs_last)`` underflows to zero and nothing is divided by it. ``C
B^T`` is ONE [128, 128] product for all heads of a grid step, and so are
the products against the state (``C T``, ``B^T (f * Xd)``): the states of
a step's heads lie side by side along the lanes, [N, heads P]. ``L``
alone is a head's. P = 64 is half a vector register's lanes, so a step
takes ``x`` [S, H P] as it lies, heads along the lanes, and works on
PAIRS of heads: a pair's two masked products run against the pair's 128
lanes and a select keeps each head's half. The matmuls take their
operands in ``x``'s type and add up in float32; the state and every
elementwise step are float32.

``ssd_fwd`` (grid: batch, blocks of the sequence in order, blocks of
``_HEADS`` heads) carries every head block's state in float32 scratch
from block to block and writes, beside ``y``, the state ENTERING each
block of 512 tokens. ``ssd_bwd`` walks the blocks in reverse carrying
``dT`` the same way; inside a block it computes the chunks' entering
states again from the block's and then walks the chunks in reverse. It
writes ``dx``; ``d dt`` and ``d cs`` as columns [S, H] (``d cs`` in two
parts, one by rows of ``L`` and one by its columns, which come out as
rows [H, S]); ``dB``, ``dC`` added up over the head blocks in float32;
and ``dD`` as partial sums. The wrapper turns ``d cs`` into ``d dt`` and
``dA`` (a reversed running sum a chunk: [S, H] numbers).

``S`` has to be a multiple of the chunk, and a head 64 wide: anything
else is a ``ValueError`` (pad the sequence outside).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import (NN, NT, TN, column, interpret_default, mm,
                            put_column)

__all__ = ["ssd", "CHUNK", "KERNELS"]

CHUNK = 128                      # the kernels' own constant, not a knob
KERNELS = ("ssd_fwd", "ssd_bwd")
_BLOCK = 512                     # tokens a grid step holds (whole chunks)
_HEADS = 8                       # heads a grid step holds, at most
_P = 64                          # the head size built: a pair fills 128 lanes
_TILE = 8                        # float32 rows a vector register holds

_SEM = pltpu.GridDimensionSemantics
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=(_SEM.PARALLEL, _SEM.ARBITRARY, _SEM.ARBITRARY),
    vmem_limit_bytes=64 * 1024 * 1024)


def _first_half(rows: int):
    """[rows, 2 P] bool: the lanes of a pair's first head."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, 2 * _P), 1) < _P


def _lanes(columns):
    """A head's number a row, ``columns[h]`` [rows, 1], on each of the
    head's P lanes -> [rows, heads P]."""
    half = _first_half(columns[0].shape[0])
    pairs = [jnp.where(half, columns[j], columns[j + 1])
             for j in range(0, len(columns), 2)]
    return pairs[0] if len(pairs) == 1 else jnp.concatenate(pairs, axis=1)


def _head_sums(v):
    """v [rows, heads P] -> each head's sum over its P lanes, [rows, 1]."""
    half = _first_half(v.shape[0])
    out = []
    for at in range(0, v.shape[1], 2 * _P):
        pair = v[:, at:at + 2 * _P]
        out += [jnp.sum(jnp.where(half, pair, 0.0), axis=1, keepdims=True),
                jnp.sum(jnp.where(half, 0.0, pair), axis=1, keepdims=True)]
    return out


class _Chunk:
    """What both kernels need of one chunk (rows ``at`` of the block) and
    the ``heads`` heads from ``first`` on: the inputs, ``C B^T`` under the
    causal mask, and every head's decays, all float32."""

    def __init__(self, at, first, heads, x_ref, dt_ref, cs_ref, cst_ref,
                 b_ref, c_ref):
        q = CHUNK
        self.dtype = x_ref.dtype
        self.x = x_ref[0, at, :].astype(jnp.float32)             # [Q, L]
        self.b, self.c = b_ref[0, at, :], c_ref[0, at, :]        # [Q, N]
        dts, css = dt_ref[0, at, :], cs_ref[0, at, :]            # [Q, H]
        self.dt = [column(dts, first + j) for j in range(heads)]
        self.cs = [column(css, first + j) for j in range(heads)]
        self.rows = [cst_ref[0, 0, j:j + 1, at] for j in range(heads)]
        self.lower = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                      >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        self.g = jnp.where(self.lower,
                           mm(self.c, self.b, NT, self.dtype), 0.0)
        last = [cs[q - 1:q, :] for cs in self.cs]                # [1, 1]
        self.e = _lanes([jnp.exp(cs) for cs in self.cs])
        self.f = _lanes([jnp.exp(l - cs) for l, cs in zip(last, self.cs)])
        self.whole = _lanes([jnp.exp(l) for l in last])          # [1, L]
        self.xd = self.x * _lanes(self.dt)

    def decay(self, j):
        """Head ``j``'s ``L`` [Q, Q]: zero above the diagonal."""
        gap = jnp.minimum(self.cs[j] - self.rows[j], 0.0)
        return jnp.where(self.lower, jnp.exp(gap), 0.0)

    def next_state(self, state):
        return self.whole * state + mm(self.b, self.f * self.xd, TN,
                                       self.dtype)


def _pairs(width: int):
    return [(p, slice(p * 2 * _P, (p + 1) * 2 * _P))
            for p in range(width // (2 * _P))]


def _chunks(ref):
    return [slice(c * CHUNK, (c + 1) * CHUNK)
            for c in range(ref.shape[1] // CHUNK)]


def _fwd_kernel(x_ref, dt_ref, cs_ref, cst_ref, b_ref, c_ref, d_ref,
                y_ref, s_ref, s_scr, *, heads):
    hb = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[hb] = jnp.zeros(s_scr.shape[1:], jnp.float32)

    half = _first_half(CHUNK)
    state = s_scr[hb]
    s_ref[0, 0, 0] = state
    for at in _chunks(x_ref):
        ch = _Chunk(at, hb * heads, heads, x_ref, dt_ref, cs_ref, cst_ref,
                    b_ref, c_ref)
        within = []
        for p, lanes in _pairs(x_ref.shape[2]):
            xd = ch.xd[:, lanes]
            within.append(jnp.where(
                half,
                mm(ch.decay(2 * p) * ch.g, xd, NN, ch.dtype),
                mm(ch.decay(2 * p + 1) * ch.g, xd, NN, ch.dtype)))
        y = (jnp.concatenate(within, axis=1)
             + ch.e * mm(ch.c, state, NN, ch.dtype) + d_ref[...] * ch.x)
        y_ref[0, at, :] = y.astype(y_ref.dtype)
        state = ch.next_state(state)
    s_scr[hb] = state


def _bwd_kernel(x_ref, dt_ref, cs_ref, cst_ref, b_ref, c_ref, d_ref, s_ref,
                dy_ref, dx_ref, ddt_ref, dcs_ref, dcst_ref, db_ref, dc_ref,
                dd_ref, ds_scr, *, heads):
    hb = pl.program_id(2)
    first = hb * heads

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[hb] = jnp.zeros(ds_scr.shape[1:], jnp.float32)
        dd_ref[0, hb] = jnp.zeros(dd_ref.shape[2:], jnp.float32)

    q = CHUNK
    half = _first_half(q)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    chunks = [(at, _Chunk(at, first, heads, x_ref, dt_ref, cs_ref, cst_ref,
                          b_ref, c_ref)) for at in _chunks(x_ref)]
    states = [s_ref[0, 0, 0]]          # the state entering each chunk
    for _, ch in chunks[:-1]:
        states.append(ch.next_state(states[-1]))
    d_state = ds_scr[hb]               # d of the state leaving the chunk
    d_dt = [[] for _ in range(heads)]
    d_cs = [[] for _ in range(heads)]
    skipped = jnp.zeros((_TILE, x_ref.shape[2]), jnp.float32)
    for (at, ch), state in zip(reversed(chunks), reversed(states)):
        dtype = ch.dtype
        dy = dy_ref[0, at, :].astype(jnp.float32)
        edy = ch.e * dy
        from_next = ch.f * mm(ch.b, d_state, NN, dtype)       # f (B dT')
        d_g = jnp.zeros((q, q), jnp.float32)
        within, by_row = [], []
        for p, lanes in _pairs(x_ref.shape[2]):
            dyp, xd = dy[:, lanes], ch.xd[:, lanes]
            both = []
            for k, mine in enumerate((half, ~half)):
                j = 2 * p + k
                decay = ch.decay(j)
                d_m = jnp.where(ch.lower, mm(jnp.where(mine, dyp, 0.0), xd,
                                             NT, dtype), 0.0)
                d_g = d_g + decay * d_m
                m = decay * ch.g
                z = m * d_m
                by_row.append(jnp.sum(z, axis=1, keepdims=True))
                dcst_ref[0, 0, j:j + 1, at] = -jnp.sum(z, axis=0,
                                                       keepdims=True)
                both.append(mm(m, dyp, TN, dtype))
            within.append(jnp.where(half, *both))
        d_xd = jnp.concatenate(within, axis=1) + from_next
        dx_ref[0, at, :] = (d_xd * _lanes(ch.dt)
                            + d_ref[...] * dy).astype(dx_ref.dtype)
        # d cs: a row of L, the carry's e, the next state's f; the chunk's
        # last row also takes every f of the chunk and exp(cs_last)
        carried = _head_sums(edy * mm(ch.c, state, NN, dtype))
        passed = _head_sums(from_next * ch.xd)
        kept = _head_sums(jnp.sum(ch.whole * d_state * state, axis=0,
                                  keepdims=True))
        for j, d in enumerate(_head_sums(d_xd * ch.x)):
            d_dt[j].append(d)
            d_cs[j].append(
                by_row[j] + carried[j] - passed[j]
                + jnp.where(at_last, jnp.sum(passed[j], axis=0,
                                             keepdims=True) + kept[j], 0.0))
        d_c = mm(d_g, ch.b, NN, dtype) + mm(edy, state, NT, dtype)
        d_b = (mm(d_g, ch.c, TN, dtype)
               + mm(ch.f * ch.xd, d_state, NT, dtype))
        for ref, d in ((dc_ref, d_c), (db_ref, d_b)):
            ref[0, at, :] = jnp.where(hb == 0, 0.0, ref[0, at, :]) + d
        part = dy * ch.x
        for r in range(0, q, _TILE):
            skipped = skipped + part[r:r + _TILE]
        d_state = ch.whole * d_state + mm(ch.c, edy, TN, dtype)
    ds_scr[hb] = d_state
    dd_ref[0, hb] += skipped
    clear = hb == 0
    put_column(ddt_ref, first,
               [jnp.concatenate(d[::-1], axis=0) for d in d_dt], clear)
    put_column(dcs_ref, first,
               [jnp.concatenate(d[::-1], axis=0) for d in d_cs], clear)


def _block(s: int) -> int:
    """Tokens a grid step holds: whole chunks, dividing ``s``."""
    for b in (_BLOCK, _BLOCK // 2, CHUNK):
        if s % b == 0:
            return b
    raise ValueError(f"sequence {s} is no multiple of the chunk {CHUNK}")


def _heads(h: int, p: int) -> int:
    """Heads a grid step holds: whole pairs, dividing ``h``."""
    if p != _P:
        raise ValueError(f"a head of {p} is not built (the kernels pair "
                         f"heads of {_P} on a register's 128 lanes)")
    for n in (_HEADS, _HEADS // 2, _HEADS // 4):
        if n % 2 == 0 and h % n == 0:
            return n
    raise ValueError(f"{h} heads are no whole pairs")


def _running(dt, a):
    """-> cs [B, S, H] float32: the running sum of ``dt A`` from each
    chunk's first token on."""
    b, s, h = dt.shape
    rate = dt.astype(jnp.float32) * a.astype(jnp.float32)
    return jnp.cumsum(rate.reshape(b, s // CHUNK, CHUNK, h),
                      axis=2).reshape(b, s, h)


def _by_head_block(cs, heads):
    """cs [B, S, H] -> [B, H / heads, heads, S]: a head's numbers as a
    row."""
    b, s, h = cs.shape
    return jnp.swapaxes(cs, 1, 2).reshape(b, h // heads, heads, s)


def _specs(blk, h, heads, n, block_of):
    """The blocks of a grid step (batch i, step t, head block j) in ``x``
    [B, S, H P], the columns [B, S, H], the rows [B, H / heads, heads,
    S], B and C [B, S, N], D on its lanes [1, H P] and the states [B, H /
    heads, S / blk, N, heads P]; ``block_of(t)``: the sequence block."""
    width = heads * _P
    return (pl.BlockSpec((1, blk, width), lambda i, t, j: (i, block_of(t), j)),
            pl.BlockSpec((1, blk, h), lambda i, t, j: (i, block_of(t), 0)),
            pl.BlockSpec((1, 1, heads, blk),
                         lambda i, t, j: (i, j, 0, block_of(t))),
            pl.BlockSpec((1, blk, n), lambda i, t, j: (i, block_of(t), 0)),
            pl.BlockSpec((1, width), lambda i, t, j: (0, j)),
            pl.BlockSpec((1, 1, 1, n, width),
                         lambda i, t, j: (i, j, block_of(t), 0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_fwd(x, dt, cs, bm, cm, d, interpret: bool):
    """-> (y [B, S, H P], the state entering each block [B, H / heads, S /
    blk, N, heads P] float32); x [B, S, H P]. Jitted so that a model's
    layers share one trace and lowering."""
    b, s, h = dt.shape
    p = x.shape[2] // h
    n, blk, heads = bm.shape[2], _block(s), _heads(h, p)
    wide, col, row, shared, skip, state = _specs(blk, h, heads, n,
                                                 lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads),
        grid=(b, s // blk, h // heads),
        in_specs=[wide, col, col, row, shared, shared, skip],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, h // heads, s // blk, n,
                                         heads * p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h // heads, n, heads * p), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[0],
    )(x, dt.astype(jnp.float32), cs, _by_head_block(cs, heads), bm, cm,
      jnp.repeat(d.astype(jnp.float32), p)[None, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_bwd(x, dt, cs, bm, cm, d, states, dy, interpret: bool):
    """-> dx [B, S, H P]; d dt, d cs [B, S, H] float32 (d cs whole: both
    its parts); dB, dC [B, S, N] float32; dD [H] float32."""
    b, s, h = dt.shape
    p = x.shape[2] // h
    n, blk, heads = bm.shape[2], _block(s), _heads(h, p)
    last, blocks = s // blk - 1, h // heads
    wide, col, row, shared, skip, state = _specs(blk, h, heads, n,
                                                 lambda t: last - t)
    partial = pl.BlockSpec((1, blocks, _TILE, heads * p),
                           lambda i, t, j: (i, 0, 0, 0))
    f32 = jnp.float32
    dx, ddt, dcs, dcst, db, dc, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads),
        grid=(b, s // blk, blocks),
        in_specs=[wide, col, col, row, shared, shared, skip, state, wide],
        out_specs=[wide, col, col, row, shared, shared, partial],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, s, h), f32),
                   jax.ShapeDtypeStruct((b, s, h), f32),
                   jax.ShapeDtypeStruct((b, blocks, heads, s), f32),
                   jax.ShapeDtypeStruct((b, s, n), f32),
                   jax.ShapeDtypeStruct((b, s, n), f32),
                   jax.ShapeDtypeStruct((b, blocks, _TILE, heads * p), f32)],
        scratch_shapes=[pltpu.VMEM((blocks, n, heads * p), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[1],
    )(x, dt.astype(f32), cs, _by_head_block(cs, heads), bm, cm,
      jnp.repeat(d.astype(f32), p)[None, :], states, dy)
    dcs = dcs + jnp.swapaxes(dcst.reshape(b, h, s), 1, 2)
    dd = jnp.sum(dd, axis=(0, 2)).reshape(h, p).sum(axis=1)
    return dx, ddt, dcs, db, dc, dd


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd(x, dt, a, b, c, d, interpret: Optional[bool] = None):
    """The recurrence of the module docstring: x [B, S, H P], dt [B, S,
    H], a [H], b, c [B, S, N], d [H] -> y [B, S, H P]."""
    return _rule_fwd(x, dt, a, b, c, d, interpret)[0]


def _rule_fwd(x, dt, a, b, c, d, interpret):
    bsz, s, width = x.shape
    h = a.shape[0]
    if (dt.shape != (bsz, s, h) or d.shape != (h,) or a.ndim != 1
            or width % h):
        raise ValueError(f"dt {dt.shape}, a {a.shape}, d {d.shape} for x "
                         f"{x.shape}: heads differ")
    _block(s)             # a ValueError where S is no multiple of the chunk
    _heads(h, width // h)
    if b.shape != c.shape or b.shape[:2] != (bsz, s) or b.ndim != 3:
        raise ValueError(f"b {b.shape} and c {c.shape}: one group, [B, S, N]")
    if interpret is None:
        interpret = interpret_default()
    y, states = _ssd_fwd(x, dt, _running(dt, a), b, c, d, interpret)
    return y, (x, dt, a, b, c, d, states)


def _rule_bwd(interpret, res, dy):
    x, dt, a, b, c, d, states = res
    if interpret is None:
        interpret = interpret_default()
    bsz, s, h = dt.shape
    dx, ddt, dcs, db, dc, dd = _ssd_bwd(x, dt, _running(dt, a), b, c, d,
                                        states, dy, interpret)
    # cs_i sums the rates of its chunk up to i: a rate's gradient is the
    # sum of d cs from its own token to the chunk's last
    rate = jnp.flip(jnp.cumsum(jnp.flip(
        dcs.reshape(bsz, s // CHUNK, CHUNK, h), 2), axis=2), 2).reshape(
            bsz, s, h)
    dt32, a32 = dt.astype(jnp.float32), a.astype(jnp.float32)
    return (dx, (ddt + rate * a32).astype(dt.dtype),
            jnp.sum(rate * dt32, axis=(0, 1)).astype(a.dtype),
            db.astype(b.dtype), dc.astype(c.dtype), dd.astype(d.dtype))


ssd.defvjp(_rule_fwd, _rule_bwd)

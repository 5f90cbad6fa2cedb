"""Lightning attention (a linear attention whose state decays at a fixed
rate a head) — Pallas TPU kernels with a custom VJP.

For every head ``h``, from a zero state ``S`` [d_k, d_v] (float32)::

    S_t = lam_h S_{t-1} + k_t v_t^T;     o_t = scale S_t^T q_t

``lam_h = exp(-slopes[h])``: a constant of the head, no gate, no delta
rule (``ops/gated_delta_rule.py`` with ``beta = 0`` writes nothing: it
cannot stand in). ``q``, ``k`` [B, S, H, d_k], ``v`` [B, S, H, d_v],
``slopes`` [H] float32 (> 0); returns ``o`` [B, S, H, d_v].

The sequence is walked a CHUNK of 128 tokens at a time (Qin et al.,
"Lightning Attention-2", arXiv:2401.04658), in the form that never
DIVIDES by a decay. With ``i``, ``j`` the positions inside a chunk and
``S`` the state entering it::

    O  = scale (((Q K^T) * D) V + (Q * lam^(i+1)) S)    D_ij = lam^(i-j), i >= j
    S' = lam^C S + (K * lam^(C-1-j))^T V

Every power that is formed has an exponent >= 0: the fast heads'
``lam^127`` underflows to zero and nothing is divided by it. The matmuls
take their operands in the inputs' type and add up in float32; the state
and every elementwise step are float32.

``lightning_fwd`` (grid: batch, heads, blocks of the sequence in order)
carries the head's state in float32 scratch from block to block and
writes, beside ``o``, the state ENTERING each block (float32).
``lightning_bwd`` walks the blocks in reverse carrying ``dS`` the same
way; inside a block it computes the chunks' entering states again from
the block's and then walks the chunks in reverse. ``slopes`` gets no
gradient (a zero: the decays are no parameters).

``S`` has to be a multiple of the chunk: anything else is a
``ValueError`` (pad the sequence outside).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import NN, NT, TN, interpret_default, mm

__all__ = ["lightning_attention", "alibi_slopes", "CHUNK", "KERNELS"]

CHUNK = 128                      # the kernels' own constant, not a knob
KERNELS = ("lightning_fwd", "lightning_bwd")
_BLOCK = 512                     # tokens a grid step holds (whole chunks)

_SEM = pltpu.GridDimensionSemantics
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=(_SEM.PARALLEL, _SEM.PARALLEL, _SEM.ARBITRARY))


def alibi_slopes(heads: int):
    """ALiBi's slopes for ``heads`` heads, ``2^(-8 (h + 1) / heads)``:
    the decay rates Lightning Attention gives its heads."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)


class _Decay:
    """The powers of one head's ``lam = exp(-slope)`` a chunk needs, all
    with exponents >= 0 (float32)."""

    def __init__(self, slope):
        c = CHUNK
        row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.lower = row >= col
        gap = jnp.maximum(row - col, 0).astype(jnp.float32)
        self.d = jnp.where(self.lower, jnp.exp(-slope * gap), 0.0)
        at = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0).astype(jnp.float32)
        self.a = jnp.exp(-slope * (at + 1.0))            # lam^(i+1)   [C, 1]
        self.b = jnp.exp(-slope * (c - 1.0 - at))        # lam^(C-1-j) [C, 1]
        self.whole = jnp.exp(-slope * c * jnp.ones((1, 1), jnp.float32))

    def next_state(self, state, k, v, dt):
        return self.whole * state + mm(k.astype(jnp.float32) * self.b, v,
                                       TN, dt)


def _chunks(ref):
    return [slice(c * CHUNK, (c + 1) * CHUNK)
            for c in range(ref.shape[1] // CHUNK)]


def _fwd_kernel(slopes_ref, q_ref, k_ref, v_ref, o_ref, s_ref, s_scr, *,
                scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)

    dt = q_ref.dtype
    dec = _Decay(slopes_ref[pl.program_id(1)])
    state = s_scr[...]
    s_ref[0, 0, 0] = state
    for at in _chunks(q_ref):
        q, k, v = q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :]
        p = jnp.where(dec.lower, mm(q, k, NT, dt) * dec.d, 0.0)
        o = (mm(p, v, NN, dt)
             + mm(q.astype(jnp.float32) * dec.a, state, NN, dt))
        o_ref[0, at, :] = (scale * o).astype(o_ref.dtype)
        state = dec.next_state(state, k, v, dt)
    s_scr[...] = state


def _bwd_kernel(slopes_ref, q_ref, k_ref, v_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, ds_scr, *, scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, jnp.float32)

    dt = q_ref.dtype
    dec = _Decay(slopes_ref[pl.program_id(1)])
    chunks = _chunks(q_ref)
    states = [s_ref[0, 0, 0]]          # the state entering each chunk
    for at in chunks[:-1]:
        states.append(dec.next_state(states[-1], k_ref[0, at, :],
                                     v_ref[0, at, :], dt))
    d_state = ds_scr[...]
    for at, state in zip(reversed(chunks), reversed(states)):
        q, k, v = q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :]
        do = do_ref[0, at, :]
        qa = q.astype(jnp.float32) * dec.a
        kb = k.astype(jnp.float32) * dec.b
        p = jnp.where(dec.lower, mm(q, k, NT, dt) * dec.d, 0.0)
        d_qk = jnp.where(dec.lower, scale * mm(do, v, NT, dt) * dec.d, 0.0)
        dv = scale * mm(p, do, TN, dt) + mm(kb, d_state, NN, dt)
        dq = (scale * dec.a * mm(do, state, NT, dt)
              + mm(d_qk, k, NN, dt))
        dk = mm(d_qk, q, TN, dt) + dec.b * mm(v, d_state, NT, dt)
        d_state = scale * mm(qa, do, TN, dt) + dec.whole * d_state
        dq_ref[0, at, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, at, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, at, :] = dv.astype(dv_ref.dtype)
    ds_scr[...] = d_state


def _block(s: int) -> int:
    """Tokens a grid step holds: whole chunks, dividing ``s``."""
    for b in (_BLOCK, _BLOCK // 2, CHUNK):
        if s % b == 0:
            return b
    raise ValueError(f"sequence {s} is no multiple of the chunk {CHUNK}")


def _specs(blk, dk, dv, block_of):
    """The blocks of a grid step (batch i, head h, step t; then the
    prefetched slopes) in the arrays [B, S, heads * d] and the states
    [B, H, S / blk, dk, dv]; ``block_of(t)``: the sequence block."""
    return (pl.BlockSpec((1, blk, dk), lambda i, h, t, _: (i, block_of(t), h)),
            pl.BlockSpec((1, blk, dv), lambda i, h, t, _: (i, block_of(t), h)),
            pl.BlockSpec((1, 1, 1, dk, dv),
                         lambda i, h, t, _: (i, h, block_of(t), 0, 0)))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _lightning_fwd(q, k, v, slopes, scale: float, interpret: bool):
    """-> (o [B, S, H, dv], the state entering each block [B, H, S / blk,
    dk, dv] float32). Jitted so that a model's layers share one trace and
    lowering."""
    b, s, h, dk = q.shape
    dv = v.shape[3]
    blk = _block(s)
    key, value, state = _specs(blk, dk, dv, lambda t: t)
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h, s // blk),
            in_specs=[key, key, value], out_specs=[value, state],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, h, s // blk, dk, dv),
                                        jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[0],
    )(slopes.astype(jnp.float32), q.reshape(b, s, h * dk),
      k.reshape(b, s, h * dk), v.reshape(b, s, h * dv))
    return o.reshape(b, s, h, dv), states


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _lightning_bwd(q, k, v, slopes, states, do, scale: float,
                   interpret: bool):
    b, s, h, dk = q.shape
    dv = v.shape[3]
    blk = _block(s)
    last = s // blk - 1
    key, value, state = _specs(blk, dk, dv, lambda t: last - t)
    dq, dk_, dv_ = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h, s // blk),
            in_specs=[key, key, value, state, value],
            out_specs=[key, key, value],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dk), q.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dk), k.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dv), v.dtype)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[1],
    )(slopes.astype(jnp.float32), q.reshape(b, s, h * dk),
      k.reshape(b, s, h * dk), v.reshape(b, s, h * dv), states,
      do.reshape(b, s, h * dv))
    return dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def lightning_attention(q, k, v, slopes, scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """The recurrence of the module docstring: q, k [B, S, H, dk], v [B,
    S, H, dv], slopes [H] -> o [B, S, H, dv]; ``scale`` defaults to
    ``1 / sqrt(dk)``."""
    return _rule_fwd(q, k, v, slopes, scale, interpret)[0]


def _settled(q, scale, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = interpret_default()
    return float(scale), interpret


def _rule_fwd(q, k, v, slopes, scale, interpret):
    _block(q.shape[1])        # a ValueError where S is no multiple of the chunk
    if not q.shape[2] == k.shape[2] == v.shape[2] == slopes.shape[0]:
        raise ValueError(
            f"heads of q {q.shape[2]}, k {k.shape[2]}, v {v.shape[2]} and "
            f"slopes {slopes.shape[0]} differ")
    o, states = _lightning_fwd(q, k, v, slopes, *_settled(q, scale, interpret))
    return o, (q, k, v, slopes, states)


def _rule_bwd(scale, interpret, res, do):
    q, k, v, slopes, states = res
    dq, dk, dv = _lightning_bwd(q, k, v, slopes, states, do,
                                *_settled(q, scale, interpret))
    return dq, dk, dv, jnp.zeros_like(slopes)


lightning_attention.defvjp(_rule_fwd, _rule_bwd)

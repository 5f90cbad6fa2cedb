"""The gated delta rule (Gated DeltaNet's recurrence) — Pallas TPU kernels
with a custom VJP.

For every value head, from a zero state ``S`` [d_k, d_v] (float32)::

    S <- exp(g_t) S;   u = S^T k_t;   delta = beta_t (v_t - u)
    S <- S + k_t delta^T;             o_t = S^T q_t

``q``, ``k`` [B, S, Hk, d_k] (already normalised and scaled by the caller),
``v`` [B, S, Hv, d_v], ``g`` (log decay, <= 0) and ``beta`` [B, S, Hv]
float32; value head ``h`` reads key head ``h // (Hv / Hk)``. Returns
``o`` [B, S, Hv, d_v].

The sequence is walked a CHUNK of 64 tokens at a time (the chunked form
of Yang et al., "Gated Delta Networks", arXiv:2412.06464 section 3.3).
With ``gam_i`` the decays summed inside the chunk up to token i and
``Gam_ij = exp(gam_i - gam_j)`` for i >= j::

    A  = strict_lower(diag(beta) (K K^T * Gam))
    T  = (I + A)^-1        A is nilpotent: six factors (I + (-A)^(2^n))
    W  = T diag(beta exp(gam)) K;        U = T diag(beta) V
    V' = U - W S                         S: the state entering the chunk
    O  = (Q * exp(gam)) S + lower(Q K^T * Gam) V'
    S' = exp(gam_C) S + (K * exp(gam_C - gam))^T V'

Every decay that is formed is a product of ``exp(g)`` over a run of
tokens, so none exceeds one whatever ``g`` is. The matmuls take their
operands in the inputs' type and add up in float32; the state and every
elementwise step are float32.

``gdn_fwd`` (grid: batch, blocks of the sequence in order, value heads)
carries every head's state in float32 scratch from block to block and
writes, beside ``o``, the state ENTERING each chunk in the inputs' type.
``gdn_bwd`` walks the blocks and the chunks in them in reverse, carries
``dS`` the same way, and computes everything else of a chunk again from
the inputs and that state: A, T, W, U and V' are kept nowhere. The two
value heads of a key head run in consecutive grid steps, so ``q`` and
``k`` are fetched once for both and their gradients are added up in the
output block while it is resident. The decays' sums inside a chunk and
their transpose (a reverse sum) are two small XLA ops around the
kernels.

``S`` has to be a multiple of the chunk: anything else is a
``ValueError`` (pad the sequence, and ``g`` with zeros, outside).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default

__all__ = ["gated_delta_rule", "CHUNK", "KERNELS"]

CHUNK = 64                       # the kernels' own constant, not a knob
KERNELS = ("gdn_fwd", "gdn_bwd")
_BLOCK = 256                     # tokens a grid step holds (whole chunks)

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b

_SEM = pltpu.GridDimensionSemantics
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=(_SEM.PARALLEL, _SEM.ARBITRARY, _SEM.ARBITRARY))


def _mm(a, b, dims, dtype):
    """A matmul with its operands in ``dtype``, added up in float32."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=(jax.lax.Precision.HIGHEST if dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32)


def _column(block, head):
    """Column ``head`` (a grid index) of block [rows, heads] as [rows, 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1, keepdims=True)


def _put_column(ref, head, column, first):
    """Write ``column`` [rows, 1] into column ``head`` of the resident
    block ``ref`` [1, rows, heads]; the first head to visit clears it."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape[1:], 1)
    held = jnp.where(first, 0.0, ref[0])
    ref[0] = jnp.where(lane == head, column, held)


def _inverse(a, dtype):
    """(I + a)^-1 for a strictly lower triangular [C, C]: with b = -a,
    (I + b)(I + b^2)(I + b^4) ... up to the power that is zero."""
    n = a.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
           == jax.lax.broadcasted_iota(jnp.int32, a.shape, 1))
    power = -a
    out = jnp.where(eye, 1.0, 0.0) + power
    reach = 2
    while reach < n:
        power = _mm(power, power, _NN, dtype)
        out = out + _mm(out, power, _NN, dtype)
        reach *= 2
    return out


class _Chunk:
    """What both kernels compute of one chunk from its inputs alone
    (float32 unless said): the masks, the decays, A, T, W and U."""

    def __init__(self, q, k, v, gam, beta):
        dt, c = q.dtype, q.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.eye, self.lower, self.strict = row == col, row >= col, row > col
        self.q, self.k, self.beta = q, k, beta             # beta [C, 1]
        self.kf, self.vf = k.astype(jnp.float32), v.astype(jnp.float32)
        across = self.to_row(gam)                          # [1, C]
        # exp(gam_i - gam_j) where i >= j: a decay, never above one
        self.decay = jnp.where(
            self.lower, jnp.exp(jnp.minimum(gam - across, 0.0)), 0.0)
        self.e = jnp.exp(gam)                              # [C, 1]
        last = gam[c - 1:c, :]
        self.e_last = jnp.exp(last)                        # [1, 1]
        self.f = jnp.exp(last - gam)                       # [C, 1]
        self.kk = _mm(k, k, _NT, dt)
        self.a = jnp.where(self.strict, beta * self.kk * self.decay, 0.0)
        self.t = _inverse(self.a, dt)
        self.w = _mm(self.t, self.kf * (beta * self.e), _NN, dt)   # [C, dk]
        self.u = _mm(self.t, self.vf * beta, _NN, dt)              # [C, dv]
        self.p = jnp.where(self.lower, _mm(q, k, _NT, dt) * self.decay, 0.0)

    def to_row(self, column):
        """[C, 1] -> [1, C] (a sum down the diagonal: no relayout)."""
        return jnp.sum(jnp.where(self.eye, column, 0.0), axis=0,
                       keepdims=True)

    def to_column(self, across):
        return jnp.sum(jnp.where(self.eye, across, 0.0), axis=1,
                       keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, h_ref, s_scr):
    head = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[head] = jnp.zeros(s_scr.shape[1:], jnp.float32)

    dt = q_ref.dtype
    gam_all, beta_all = _column(g_ref[0], head), _column(b_ref[0], head)
    state = s_scr[head]
    for c in range(q_ref.shape[1] // CHUNK):
        at = slice(c * CHUNK, (c + 1) * CHUNK)
        ch = _Chunk(q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :],
                    gam_all[at], beta_all[at])
        h_ref[0, 0, c] = state.astype(h_ref.dtype)
        new = ch.u - _mm(ch.w, state, _NN, dt)                     # V'
        o = (_mm(ch.q.astype(jnp.float32) * ch.e, state, _NN, dt)
             + _mm(ch.p, new, _NN, dt))
        o_ref[0, at, :] = o.astype(o_ref.dtype)
        state = ch.e_last * state + _mm(ch.kf * ch.f, new, _TN, dt)
    s_scr[head] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, h_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, group):
    head = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[head] = jnp.zeros(ds_scr.shape[1:], jnp.float32)

    dt = q_ref.dtype
    gam_all, beta_all = _column(g_ref[0], head), _column(b_ref[0], head)
    d_state = ds_scr[head]
    d_gam, d_beta = [], []
    first_of_group = head % group == 0
    for c in reversed(range(q_ref.shape[1] // CHUNK)):
        at = slice(c * CHUNK, (c + 1) * CHUNK)
        ch = _Chunk(q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :],
                    gam_all[at], beta_all[at])
        state, do = h_ref[0, 0, c], do_ref[0, at, :]
        qf, beta, e, f = ch.q.astype(jnp.float32), ch.beta, ch.e, ch.f
        new = ch.u - _mm(ch.w, state, _NN, dt)                     # V'
        # O = (Q e) S + P V';  S' = e_last S + (K f)^T V'
        d_new = (_mm(ch.p, do, _TN, dt)
                 + _mm(ch.kf * f, d_state, _NN, dt))               # [C, dv]
        d_p = jnp.where(ch.lower, _mm(do, new, _NT, dt), 0.0)
        d_qe = _mm(do, state, _NT, dt)                             # [C, dk]
        d_kf = _mm(new, d_state, _NT, dt)                          # [C, dk]
        d_last = jnp.sum(d_state * state.astype(jnp.float32), keepdims=True)
        # V' = U - W S
        d_w = -_mm(d_new, state, _NT, dt)                          # [C, dk]
        d_state = (_mm(qf * e, do, _TN, dt) + ch.e_last * d_state
                   - _mm(ch.w, d_new, _TN, dt))
        # W = T (K beta e), U = T (V beta), T = (I + A)^-1
        d_kb = _mm(ch.t, d_w, _TN, dt)                             # [C, dk]
        d_vb = _mm(ch.t, d_new, _TN, dt)                           # [C, dv]
        d_a = jnp.where(ch.strict, -(_mm(d_kb, ch.w, _NT, dt)
                                     + _mm(d_vb, ch.u, _NT, dt)), 0.0)
        # A = beta KK Gam (strict), P = QK Gam (lower)
        d_kk = d_a * beta * ch.decay
        d_qk = d_p * ch.decay
        through = d_a * ch.a + d_p * ch.p        # dGam * Gam
        kb_k = jnp.sum(d_kb * ch.kf, axis=1, keepdims=True)
        kf_k = f * jnp.sum(d_kf * ch.kf, axis=1, keepdims=True)
        d_beta.append(
            jnp.sum(d_a * ch.kk * ch.decay, axis=1, keepdims=True)
            + e * kb_k + jnp.sum(d_vb * ch.vf, axis=1, keepdims=True))
        dg = (jnp.sum(through, axis=1, keepdims=True)
              - ch.to_column(jnp.sum(through, axis=0, keepdims=True))
              + beta * e * kb_k
              + e * jnp.sum(d_qe * qf, axis=1, keepdims=True) - kf_k)
        is_last = jax.lax.broadcasted_iota(jnp.int32, dg.shape, 0) \
            == CHUNK - 1
        d_gam.append(dg + jnp.where(
            is_last, jnp.sum(kf_k, keepdims=True) + ch.e_last * d_last, 0.0))
        dq = e * d_qe + _mm(d_qk, ch.k, _NN, dt)
        dk = (_mm(d_kk, ch.k, _NN, dt) + _mm(d_kk, ch.k, _TN, dt)
              + _mm(d_qk, ch.q, _TN, dt) + beta * e * d_kb + f * d_kf)
        dv_ref[0, at, :] = (beta * d_vb).astype(dv_ref.dtype)
        # a key head's value heads come one after the other: the first
        # writes, the others add
        for ref, val in ((dq_ref, dq), (dk_ref, dk)):
            held = jnp.where(first_of_group, 0.0,
                             ref[0, at, :].astype(jnp.float32))
            ref[0, at, :] = (held + val).astype(ref.dtype)
    ds_scr[head] = d_state
    _put_column(dg_ref, head, jnp.concatenate(d_gam[::-1], axis=0), head == 0)
    _put_column(db_ref, head, jnp.concatenate(d_beta[::-1], axis=0),
                head == 0)


def _block(s: int) -> int:
    """Tokens a grid step holds: whole chunks, dividing ``s``."""
    for b in (_BLOCK, _BLOCK // 2, CHUNK):
        if s % b == 0:
            return b
    raise ValueError(f"sequence {s} is no multiple of the chunk {CHUNK}")


def _specs(blk, dk, dv, hv, group, block_of):
    """The blocks of a grid step (batch i, step t, value head h) in the
    arrays [B, S, heads * d]: a key head's, a value head's, and all the
    heads' columns of g / beta; ``block_of(t)``: the sequence block."""
    return (pl.BlockSpec((1, blk, dk),
                         lambda i, t, h: (i, block_of(t), h // group)),
            pl.BlockSpec((1, blk, dv), lambda i, t, h: (i, block_of(t), h)),
            pl.BlockSpec((1, blk, hv), lambda i, t, h: (i, block_of(t), 0)))


def _within_chunks(x, reverse=False):
    """x [B, S, H]: running sums inside each chunk of the sequence."""
    b, s, h = x.shape
    x = x.reshape(b, s // CHUNK, CHUNK, h)
    return jax.lax.cumsum(x, axis=2, reverse=reverse).reshape(b, s, h)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_fwd(q, k, v, g, beta, interpret: bool):
    """-> (o [B, S, Hv, dv], the state entering each chunk [B, Hv, S/C,
    dk, dv], the decays summed inside the chunks [B, S, Hv]). Jitted so
    that a model's layers share one trace and lowering."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    group, blk = hv // hk, _block(s)
    gam = _within_chunks(g.astype(jnp.float32))
    beta = beta.astype(jnp.float32)
    steps = blk // CHUNK

    key, value, heads = _specs(blk, dk, dv, hv, group, lambda t: t)

    o, states = pl.pallas_call(
        _fwd_kernel,
        grid=(b, s // blk, hv),
        in_specs=[key, key, value, heads, heads],
        out_specs=[
            value,
            pl.BlockSpec((1, 1, steps, dk, dv),
                         lambda i, t, h: (i, h, t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hv * dv), v.dtype),
            jax.ShapeDtypeStruct((b, hv, s // CHUNK, dk, dv), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hv, dk, dv), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[0],
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), gam, beta)
    return o.reshape(b, s, hv, dv), states, gam


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_bwd(q, k, v, gam, beta, states, do, interpret: bool):
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    group, blk = hv // hk, _block(s)
    last, steps = s // blk - 1, blk // CHUNK
    beta = beta.astype(jnp.float32)

    key, value, heads = _specs(blk, dk, dv, hv, group, lambda t: last - t)
    dq, dk_, dv_, d_gam, d_beta = pl.pallas_call(
        functools.partial(_bwd_kernel, group=group),
        grid=(b, s // blk, hv),
        in_specs=[
            key, key, value, heads, heads,
            pl.BlockSpec((1, 1, steps, dk, dv),
                         lambda i, t, h: (i, h, last - t, 0, 0)),
            value,
        ],
        out_specs=[key, key, value, heads, heads],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hk * dk), q.dtype),
            jax.ShapeDtypeStruct((b, s, hk * dk), k.dtype),
            jax.ShapeDtypeStruct((b, s, hv * dv), v.dtype),
            jax.ShapeDtypeStruct((b, s, hv), jnp.float32),
            jax.ShapeDtypeStruct((b, s, hv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hv, dk, dv), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[1],
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), gam, beta, states,
      do.reshape(b, s, hv * dv))
    # gam is a running sum of g inside a chunk: its transpose runs back
    d_g = _within_chunks(d_gam, reverse=True)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            d_g, d_beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule(q, k, v, g, beta, interpret: Optional[bool] = None):
    """The recurrence of the module docstring: q, k [B, S, Hk, dk], v
    [B, S, Hv, dv], g, beta [B, S, Hv] -> o [B, S, Hv, dv]."""
    return _rule_fwd(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    _block(q.shape[1])        # a ValueError where S is no multiple of the chunk
    if v.shape[2] % q.shape[2]:
        raise ValueError(f"{v.shape[2]} value heads over {q.shape[2]} key "
                         "heads")
    if interpret is None:
        interpret = _interpret_default()
    o, states, gam = _gdn_fwd(q, k, v, g, beta, interpret)
    return o, (q, k, v, g, gam, beta, states)


def _rule_bwd(interpret, res, do):
    if interpret is None:
        interpret = _interpret_default()
    q, k, v, g, gam, beta, states = res
    dq, dk, dv, dg, db = _gdn_bwd(q, k, v, gam, beta, states, do, interpret)
    return dq, dk, dv, dg.astype(g.dtype), db.astype(beta.dtype)


gated_delta_rule.defvjp(_rule_fwd, _rule_bwd)

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

The chip's compiler keeps a product's result close behind its operands
in the program, so a dependent product waits the MXU's whole round trip out
(~230 cycles a level of the inverse, measured) whatever its shape, and a
chunk is a chain of such products: the six levels of the inverse, then W
and U, then what needs the state. Both kernels therefore walk a grid step
STAGE BY STAGE over all of its chunks and value heads at once (``_chunks``:
``_BLOCK`` tokens of a key head's ``Hv / Hk`` heads, two chunks of two heads
= 4 bodies where there are two heads to a key head): every body's decays,
then every inverse level by level (``_inverses``), then every W and U, so
that products next to each other in the program are independent and
pipeline. What carries the state is the only chain left, two products a
chunk: ``W S`` -> V' -> ``S'`` in the forward, ``(K f) dS`` -> dV' -> ``dS'``
in the backward, whose other products sit in stages before and after it.

Beside that, products that share an operand are issued as ONE, their
other operands side by side: ``[q; k] k^T`` (once for all the value heads of
a key head: neither depends on the head), the inverse with its running
product one factor behind the power (``[out; p] p`` gives ``out (I + p)``
and ``p p`` at once: six products for ten), ``T [k beta e | v beta]``, and in
the backward ``[do; d_new] S^T``, ``T^T [d_w | d_new]``, ``[d_kb | d_vb] [w |
u]^T``, ``[q e; w]^T [do; -d_new]`` and, summed over the key head's value
heads first, ``[d_qk; d_kk] k`` and ``[d_qk; d_kk]^T [q; k]``. The forward's
chain keeps its product as small as it can be: ``(Q e) S`` runs beside
``W S``, not in it (merged it cost 7% of the kernel).

``gdn_fwd`` (grid: batch, blocks of the sequence in order, key heads)
holds a key head's value heads in one grid step, carries every head's state
in float32 scratch from block to block and writes, beside ``o``, the state
ENTERING each chunk in the inputs' type. ``gdn_bwd`` walks the blocks and
the chunks in them in reverse, carries ``dS`` the same way, and computes
everything else of a chunk again from the inputs and that state: A, T, W, U
and V' are kept nowhere. ``dq`` and ``dk`` are summed over the key head's
value heads in registers and written once. The decays' sums inside a chunk
and their transpose (a reverse sum) are two small XLA ops around the
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

from .pallas_common import (NN, NT, TN, column, interpret_default, mm,
                            put_column)

__all__ = ["gated_delta_rule", "CHUNK", "KERNELS"]

CHUNK = 64                       # the kernels' own constant, not a knob
KERNELS = ("gdn_fwd", "gdn_bwd")
# Tokens a grid step holds (whole chunks), of at most ``_BODIES`` (chunk,
# head) bodies. Every body is written out in the trace: 256 tokens (8 bodies
# at two heads a key head) read 25% less kernel time than 128 and 3.4 s more
# set-up, so 128 it is until the bodies are a batched dimension (PERF.md §6).
_BLOCK = 128
_BODIES = 8

_SEM = pltpu.GridDimensionSemantics
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=(_SEM.PARALLEL, _SEM.ARBITRARY, _SEM.ARBITRARY))


def _inverses(mats, dtype):
    """(I + a)^-1 for every strictly lower triangular a [C, C] of the list:
    with b = -a, (I + b)(I + b^2)(I + b^4) ... up to the power that is
    zero. The factors commute, so the running product stays one factor
    behind the power and both advance in one product, ``[out; p] @ p``;
    the list advances level by level, so that neighbours in the program
    are independent products."""
    n = mats[0].shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    powers = [-a for a in mats]
    outs = [jnp.where(eye, 1.0, 0.0) + p for p in powers]
    if n <= 2:
        return outs
    powers, reach = [mm(p, p, NN, dtype) for p in powers], 2
    while 2 * reach < n:            # outs lack the factor (I + b^reach)
        both = [mm(jnp.concatenate([o, p], axis=0), p, NN, dtype)
                for o, p in zip(outs, powers)]
        outs = [o + x[:n] for o, x in zip(outs, both)]
        powers, reach = [x[n:] for x in both], 2 * reach
    return [o + mm(o, p, NN, dtype) for o, p in zip(outs, powers)]


def _inverse(a, dtype):
    """(I + a)^-1 of one matrix: six products at 64."""
    return _inverses([a], dtype)[0]


class _Keys:
    """What the value heads of a key head share in one chunk: the masks,
    q and k, and ``[q; k] k^T``."""

    def __init__(self, q, k):
        c = q.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        self.eye, self.lower, self.strict = row == col, row >= col, row > col
        self.k = k
        self.qf, self.kf = q.astype(jnp.float32), k.astype(jnp.float32)
        self.qk = jnp.concatenate([q, k], axis=0)                  # [2C, dk]
        both = mm(self.qk, k, NT, q.dtype)                       # [2C, C]
        self.qkt, self.kk = both[:c], both[c:]

    def to_row(self, column):
        """[C, 1] -> [1, C] (a sum down the diagonal: no relayout)."""
        return jnp.sum(jnp.where(self.eye, column, 0.0), axis=0,
                       keepdims=True)

    def to_column(self, across):
        return jnp.sum(jnp.where(self.eye, across, 0.0), axis=1,
                       keepdims=True)


class _Chunk:
    """What both kernels compute of one chunk and value head from its
    inputs alone (float32 unless said), in two stages: the decays and A;
    then, given T = (I + A)^-1, W, U and P."""

    def __init__(self, keys, v, gam, beta, at, lanes):
        c = keys.k.shape[0]
        self.keys, self.beta = keys, beta                  # beta [C, 1]
        self.at, self.lanes = at, lanes    # its rows and lanes in a block
        self.vf = v.astype(jnp.float32)
        across = keys.to_row(gam)                          # [1, C]
        # exp(gam_i - gam_j) where i >= j: a decay, never above one
        self.decay = jnp.where(
            keys.lower, jnp.exp(jnp.minimum(gam - across, 0.0)), 0.0)
        self.e = jnp.exp(gam)                              # [C, 1]
        last = gam[c - 1:c, :]
        self.e_last = jnp.exp(last)                        # [1, 1]
        self.f = jnp.exp(last - gam)                       # [C, 1]
        self.a = jnp.where(keys.strict, beta * keys.kk * self.decay, 0.0)

    def finish(self, t):
        keys, dk = self.keys, self.keys.k.shape[1]
        self.t = t
        self.wu = mm(t, jnp.concatenate(
            [keys.kf * (self.beta * self.e), self.vf * self.beta], axis=1),
            NN, keys.k.dtype)
        self.w, self.u = self.wu[:, :dk], self.wu[:, dk:]  # [C, dk], [C, dv]
        self.p = jnp.where(keys.lower, keys.qkt * self.decay, 0.0)
        self.qe = keys.qf * self.e
        self.qe_w = jnp.concatenate([self.qe, self.w], axis=0)
        self.kf_f = keys.kf * self.f


def _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, group):
    """{(chunk, head of the group): _Chunk} of a grid step, every body's
    first stage, then every inverse, then every second stage."""
    first = pl.program_id(2) * group               # the step's value heads
    dv = v_ref.shape[2] // group
    out = {}
    for c in range(q_ref.shape[1] // CHUNK):
        at = slice(c * CHUNK, (c + 1) * CHUNK)
        keys = _Keys(q_ref[0, at, :], k_ref[0, at, :])
        for j in range(group):
            lanes = slice(j * dv, (j + 1) * dv)
            out[c, j] = _Chunk(keys, v_ref[0, at, lanes],
                               column(g_ref[0, at, :], first + j),
                               column(b_ref[0, at, :], first + j), at, lanes)
    for ch, t in zip(out.values(), _inverses(
            [ch.a for ch in out.values()], q_ref.dtype)):
        ch.finish(t)
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, h_ref, s_scr, *,
                group):
    first = pl.program_id(2) * group               # the step's value heads
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        for j in range(group):
            s_scr[first + j] = jnp.zeros(s_scr.shape[1:], jnp.float32)

    state = [s_scr[first + j] for j in range(group)]
    for (c, j), ch in _chunks(q_ref, k_ref, v_ref, g_ref, b_ref,
                              group).items():
        h_ref[0, j, c] = state[j].astype(h_ref.dtype)
        new = ch.u - mm(ch.w, state[j], NN, dt)                      # V'
        o = mm(ch.qe, state[j], NN, dt) + mm(ch.p, new, NN, dt)
        o_ref[0, ch.at, ch.lanes] = o.astype(o_ref.dtype)
        state[j] = ch.e_last * state[j] + mm(ch.kf_f, new, TN, dt)
    for j in range(group):
        s_scr[first + j] = state[j]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, h_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, group):
    first = pl.program_id(2) * group
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        for j in range(group):
            ds_scr[first + j] = jnp.zeros(ds_scr.shape[1:], jnp.float32)

    chunks = _chunks(q_ref, k_ref, v_ref, g_ref, b_ref, group)
    ids = list(chunks)[::-1]                       # the chunks in reverse
    state = {(c, j): h_ref[0, j, c] for c, j in ids}
    do = {i: do_ref[0, chunks[i].at, chunks[i].lanes].astype(jnp.float32)
          for i in ids}
    # what needs no dS.  O = (Q e) S + P V';  V' = U - W S
    new = {i: chunks[i].u - mm(chunks[i].w, state[i], NN, dt) for i in ids}
    p_do = {i: mm(chunks[i].p, do[i], TN, dt) for i in ids}
    d_p = {i: jnp.where(chunks[i].keys.lower, mm(do[i], new[i], NT, dt),
                        0.0) for i in ids}
    # the chain.  S' = e_last S + (K f)^T V'
    d_state = [ds_scr[first + j] for j in range(group)]
    d_new, d_kf, d_last = {}, {}, {}
    for i in ids:
        ch, j = chunks[i], i[1]
        d_new[i] = p_do[i] + mm(ch.kf_f, d_state[j], NN, dt)     # [C, dv]
        d_kf[i] = mm(new[i], d_state[j], NT, dt)                 # [C, dk]
        d_last[i] = jnp.sum(d_state[j] * state[i].astype(jnp.float32),
                            keepdims=True)
        d_state[j] = ch.e_last * d_state[j] + mm(
            ch.qe_w, jnp.concatenate([do[i], -d_new[i]], axis=0), TN, dt)
    for j in range(group):
        ds_scr[first + j] = d_state[j]
    # what hangs off it.  W = T (K beta e), U = T (V beta), T = (I + A)^-1
    both = {i: mm(jnp.concatenate([do[i], d_new[i]], axis=0), state[i], NT,
                  dt) for i in ids}
    d_qe = {i: both[i][:CHUNK] for i in ids}                       # [C, dk]
    d_w = {i: -both[i][CHUNK:] for i in ids}
    d_kb_vb = {i: mm(chunks[i].t, jnp.concatenate([d_w[i], d_new[i]],
                                                  axis=1), TN, dt)
               for i in ids}
    d_a = {i: jnp.where(chunks[i].keys.strict,
                        -mm(d_kb_vb[i], chunks[i].wu, NT, dt), 0.0)
           for i in ids}
    d_gam, d_beta = [[] for _ in range(group)], [[] for _ in range(group)]
    # summed over a key head's value heads: dq, dk without their products
    # against q and k, and [d_qk; d_kk] for those
    sums = {}
    for i in ids:
        (c, j), ch, keys = i, chunks[i], chunks[i].keys
        beta, e, f = ch.beta, ch.e, ch.f
        dk_ = keys.k.shape[1]
        d_kb, d_vb = d_kb_vb[i][:, :dk_], d_kb_vb[i][:, dk_:]
        # A = beta KK Gam (strict), P = QK Gam (lower)
        d_kk = d_a[i] * beta * ch.decay
        d_qk = d_p[i] * ch.decay
        through = d_a[i] * ch.a + d_p[i] * ch.p      # dGam * Gam
        kb_k = jnp.sum(d_kb * keys.kf, axis=1, keepdims=True)
        kf_k = f * jnp.sum(d_kf[i] * keys.kf, axis=1, keepdims=True)
        d_beta[j].append(
            jnp.sum(d_a[i] * keys.kk * ch.decay, axis=1, keepdims=True)
            + e * kb_k + jnp.sum(d_vb * ch.vf, axis=1, keepdims=True))
        dg = (jnp.sum(through, axis=1, keepdims=True)
              - keys.to_column(jnp.sum(through, axis=0, keepdims=True))
              + beta * e * kb_k
              + e * jnp.sum(d_qe[i] * keys.qf, axis=1, keepdims=True) - kf_k)
        is_last = jax.lax.broadcasted_iota(jnp.int32, dg.shape, 0) \
            == CHUNK - 1
        d_gam[j].append(dg + jnp.where(
            is_last, jnp.sum(kf_k, keepdims=True) + ch.e_last * d_last[i],
            0.0))
        dv_ref[0, ch.at, ch.lanes] = (beta * d_vb).astype(dv_ref.dtype)
        dq, dk, d_both = sums.get(c, (0.0, 0.0, 0.0))
        sums[c] = (dq + e * d_qe[i], dk + beta * e * d_kb + f * d_kf[i],
                   d_both + jnp.concatenate([d_qk, d_kk], axis=0))
    # dq += d_qk k;  dk += d_kk k + d_kk^T k + d_qk^T q
    both = {c: mm(d_both, chunks[c, 0].keys.k, NN, dt)           # [2C, dk]
            for c, (_, _, d_both) in sums.items()}
    across = {c: mm(d_both, chunks[c, 0].keys.qk, TN, dt)
              for c, (_, _, d_both) in sums.items()}
    for c, (dq, dk, _) in sums.items():
        at = chunks[c, 0].at
        dq_ref[0, at, :] = (dq + both[c][:CHUNK]).astype(dq_ref.dtype)
        dk_ref[0, at, :] = (dk + both[c][CHUNK:] + across[c]).astype(
            dk_ref.dtype)
    clear = pl.program_id(2) == 0
    put_column(dg_ref, first,
               [jnp.concatenate(d[::-1], axis=0) for d in d_gam], clear)
    put_column(db_ref, first,
               [jnp.concatenate(d[::-1], axis=0) for d in d_beta], clear)


def _block(s: int, group: int) -> int:
    """Tokens a grid step holds: whole chunks, dividing ``s``, and no more
    (chunk, value head) bodies than ``_BODIES`` where fewer chunks do."""
    for b in (_BLOCK, _BLOCK // 2, CHUNK):
        if s % b == 0 and (b // CHUNK * group <= _BODIES or b == CHUNK):
            return b
    raise ValueError(f"sequence {s} is no multiple of the chunk {CHUNK}")


def _specs(blk, dk, dv, hv, group, block_of):
    """The blocks of a grid step (batch i, step t, key head h) in the
    arrays [B, S, heads * d]: the key head's, its ``group`` value heads',
    and all the heads' columns of g / beta; ``block_of(t)``: the sequence
    block."""
    return (pl.BlockSpec((1, blk, dk), lambda i, t, h: (i, block_of(t), h)),
            pl.BlockSpec((1, blk, group * dv),
                         lambda i, t, h: (i, block_of(t), h)),
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
    group = hv // hk
    blk = _block(s, group)
    gam = _within_chunks(g.astype(jnp.float32))
    beta = beta.astype(jnp.float32)
    steps = blk // CHUNK

    key, value, heads = _specs(blk, dk, dv, hv, group, lambda t: t)

    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, group=group),
        grid=(b, s // blk, hk),
        in_specs=[key, key, value, heads, heads],
        out_specs=[
            value,
            pl.BlockSpec((1, group, steps, dk, dv),
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
    group = hv // hk
    blk = _block(s, group)
    last, steps = s // blk - 1, blk // CHUNK
    beta = beta.astype(jnp.float32)

    key, value, heads = _specs(blk, dk, dv, hv, group, lambda t: last - t)
    dq, dk_, dv_, d_gam, d_beta = pl.pallas_call(
        functools.partial(_bwd_kernel, group=group),
        grid=(b, s // blk, hk),
        in_specs=[
            key, key, value, heads, heads,
            pl.BlockSpec((1, group, steps, dk, dv),
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
    if v.shape[2] % q.shape[2]:
        raise ValueError(f"{v.shape[2]} value heads over {q.shape[2]} key "
                         "heads")
    # a ValueError where S is no multiple of the chunk
    _block(q.shape[1], v.shape[2] // q.shape[2])
    if interpret is None:
        interpret = interpret_default()
    o, states, gam = _gdn_fwd(q, k, v, g, beta, interpret)
    return o, (q, k, v, g, gam, beta, states)


def _rule_bwd(interpret, res, do):
    if interpret is None:
        interpret = interpret_default()
    q, k, v, g, gam, beta, states = res
    dq, dk, dv, dg, db = _gdn_bwd(q, k, v, gam, beta, states, do, interpret)
    return dq, dk, dv, dg.astype(g.dtype), db.astype(beta.dtype)


gated_delta_rule.defvjp(_rule_fwd, _rule_bwd)

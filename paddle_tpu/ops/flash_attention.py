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

Layouts: public API is paddle's [B, S, H, D]; kernels run [B, H, S, D].
GQA: the forward indexes kv-heads via h // group — no repeat; the
backward expands kv then reduces group-wise (dk/dv peak at q-head size,
same as the fallback's repeat).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() exact zero
                 # without inf-inf = nan hazards in the masked rows

_SEM = pltpu.GridDimensionSemantics


def _block(size: int) -> int:
    """Largest MXU-friendly block dividing ``size``."""
    for b in (512, 256, 128):
        if size % b == 0:
            return b
    return size


def _interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, bq: int, bk: int, off: int):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip k-blocks strictly above the diagonal — ~2x on long seq
    iq = pl.program_id(2)
    live = (iq * bq + bq - 1 + off >= ik * bk) if causal else (ik >= 0)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]

        if causal:
            q_abs = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_abs = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = q_abs + off >= k_abs
            s_masked = jnp.where(mask, s, NEG_INF)
        else:
            mask = None
            s_masked = s

        m_prev = m_scr[:, :1]                             # [bq, 1]
        m_cur = jnp.max(s_masked, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_masked - m_new)                     # [bq, bk] f32
        if mask is not None:
            # fully-masked rows: m_new == NEG_INF makes exp(s-m) == 1;
            # zero them so such rows emit 0 (and l stays 0)
            p = jnp.where(mask, p, 0.0)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        # fully-masked rows (possible only off the causal diagonal when
        # sq > sk never happens here; guard anyway) -> emit zeros
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(safe_l))[:, 0][None, :]


def _flash_fwd(q, k, v, scale: float, causal: bool, interpret: bool):
    """q: [B, Hq, Sq, D], k/v: [B, Hkv, Sk, D] → (out [B,Hq,Sq,D],
    lse [B,Hq,Sq] in f32)."""
    batch, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    bq, bk = _block(sq), _block(sk)
    grid = (batch, hq, sq // bq, sk // bk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, off=sk - sq
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((batch, hq, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max
            pltpu.VMEM((bq, 128), jnp.float32),  # running denom
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                _SEM.PARALLEL, _SEM.PARALLEL, _SEM.PARALLEL, _SEM.ARBITRARY,
            ),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale: float, causal: bool, bq: int, bk: int, off: int):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    iq = pl.program_id(2)
    live = (iq * bq + bq - 1 + off >= ik * bk) if causal else (ik >= 0)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_abs = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_abs = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = q_abs + off >= k_abs
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, 0][:, None])          # [bq, bk]
        if causal:
            # fully-masked rows have lse == NEG_INF -> exp(0) == 1
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0, 0][:, None]) * scale  # [bq, bk] f32
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale: float, causal: bool, bq: int, bk: int, off: int):
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    ik = pl.program_id(2)
    live = (iq * bq + bq - 1 + off >= ik * bk) if causal else (iq >= 0)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_abs = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_abs = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = q_abs + off >= k_abs
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, 0][:, None])           # [bq, bk]
        if causal:
            p = jnp.where(mask, p, 0.0)
        do = do_ref[0, 0]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0, 0][:, None]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, scale: float, causal: bool, interpret: bool):
    """All operands [B, H, S, D] (kv already head-expanded)."""
    batch, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block(sq), _block(sk)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    lse3 = lse[:, :, None, :]      # [B, H, 1, Sq]
    delta3 = delta[:, :, None, :]  # [B, H, 1, Sq]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, off=sk - sq),
        grid=(batch, h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, hh, iq, ik: (b, hh, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, iq, ik: (b, hh, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, iq, ik: (b, hh, ik, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b, hh, iq, ik: (b, hh, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, hh, iq, ik: (b, hh, 0, iq)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, hh, iq, ik: (b, hh, 0, iq)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, hh, iq, ik: (b, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                _SEM.PARALLEL, _SEM.PARALLEL, _SEM.PARALLEL, _SEM.ARBITRARY,
            ),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, off=sk - sq),
        grid=(batch, h, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, hh, ik, iq: (b, hh, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, ik, iq: (b, hh, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, ik, iq: (b, hh, ik, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b, hh, ik, iq: (b, hh, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, hh, ik, iq: (b, hh, 0, iq)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, hh, ik, iq: (b, hh, 0, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, ik, iq: (b, hh, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hh, ik, iq: (b, hh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                _SEM.PARALLEL, _SEM.PARALLEL, _SEM.PARALLEL, _SEM.ARBITRARY,
            ),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op: custom VJP over [B, S, H, D] layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """Fused attention, paddle layout [B, S, H, D]; supports GQA
    (kv heads dividing q heads) and causal masking."""
    out, _ = _fa_fwd(q, k, v, causal, scale, interpret)
    return out


def _fa_fwd(q, k, v, causal, scale, interpret):
    if interpret is None:
        interpret = _interpret_default()
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t, lse = _flash_fwd(qt, kt, vt, s, causal, interpret)
    return jnp.swapaxes(out_t, 1, 2), (q, k, v, out_t, lse)


def _fa_bwd(causal, scale, interpret, res, g):
    if interpret is None:
        interpret = _interpret_default()
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
    dq_t, dk_t, dv_t = _flash_bwd(qt, kt, vt, out_t, lse, do_t, s, causal, interpret)
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

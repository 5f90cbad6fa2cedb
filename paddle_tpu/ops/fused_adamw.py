"""Fused AdamW — single-pass Pallas TPU optimizer kernel.

ref: paddle/phi/kernels/gpu/adamw_kernel.cu (the reference's fused
multi-tensor CUDA path, adamw.py:493 ``_C_ops.adamw_``). TPU-native
redesign: the AdamW tail is HBM-bound, and XLA cannot fuse the update
chain across the backward scan boundary — each of m/v/p lands in its
own fusion with its own round-trip over the optimizer state. This
kernel streams param+grad+m+v tiles through VMEM exactly once per
step: bias-corrected update, decoupled weight decay, and the
stochastic-rounding bf16 writeback all computed in-register, so the
per-element HBM traffic is one read of p/g/m/v and one write of p/m/v.

Numerics contract (tests/test_fused_adamw.py): with stochastic
rounding off the kernel computes the reference
``AdamW._update_param`` expressions — the reference's op order and f32
compute dtype (``_moments`` / ``_adam_delta``), with the scalar prologue
(``lr_t``, the effective epsilon, the decay factor) computed OUTSIDE the
kernel by the exact reference expressions — and every output agrees
with the jitted reference to within one f32 rounding of an intermediate
term (``update_error_bounds``). Not bit-equal: whether
``b1*m + (1-b1)*g`` is contracted into a fused multiply-add is each
compiler's choice (XLA:CPU, the interpreter, Mosaic), one rounding of
the larger product apart — which, where the two products cancel, is
many ulps of the small result. With SR on, the writeback uses the same
lowbias32 hash over (flat element index, two threefry salts) as
``_stochastic_round_bf16`` — same salts, same draws.

Layout: arrays are flattened C-order, zero-padded to a (rows, 128)
lane grid, and tiled over ``bt`` sublanes per program (multiple of 16:
legal for both f32 (8,128) and bf16 (16,128) tiles). The flat index
the SR hash sees is ``tile*bt*128 + row*128 + lane`` — identical to
the reference's ``lax.iota`` over the unflattened array, so SR parity
holds element-for-element.

The ``skip`` operand is the GradScaler found-inf veto: a scalar read
from SMEM before any tile math — when set, every output tile is a
bitwise copy of its input (params, m, v all untouched), which is what
lets the scaler drive interleaved fused updates safely (see
amp.GradScaler).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


# ---------------------------------------------------------------------------
# HBM traffic models
# ---------------------------------------------------------------------------


def fused_adamw_hbm_bytes(size: int, p_dtype, g_dtype, m_dtype) -> int:
    """The kernel's HBM traffic: ONE streamed pass — read p/g/m/v,
    write p/m/v. This is the number handed to the compiler as
    ``pl.CostEstimate`` and asserted in tests against
    ``cost_analysis``."""
    pb = jnp.dtype(p_dtype).itemsize
    gb = jnp.dtype(g_dtype).itemsize
    mb = jnp.dtype(m_dtype).itemsize
    read = size * (pb + gb + 2 * mb)
    write = size * (pb + 2 * mb)
    return read + write


def unfused_adamw_hbm_bytes(size: int, p_dtype, g_dtype, m_dtype) -> int:
    """Op-boundary HBM traffic of the reference (unfused) AdamW tail.

    Accounting: each jnp op in ``_moments``/``_adam_delta``/``_apply``
    reads its operands and materializes its result — the schedule XLA
    actually emits for the optimizer tail after the backward scan,
    where the m/v moment fusion and the p update fusion cannot share a
    loop (the moments are both carried outputs of the step and inputs
    to the delta). Counted per element:

      moment pass:  read g, m, v; write m', v'      (intermediates in
                    f32 compute dtype round-trip once each: b1*m,
                    (1-b1)*g, b2*v, (1-b2)*g*g)
      update pass:  read p, m', v'; write p'        (delta chain
                    lr_t*m, sqrt(v), denom each materialize once)
    """
    f32 = jnp.dtype(jnp.float32).itemsize
    pb = jnp.dtype(p_dtype).itemsize
    gb = jnp.dtype(g_dtype).itemsize
    mb = jnp.dtype(m_dtype).itemsize
    # moment pass: read g+m+v, write m'+v', plus four f32 intermediates
    # (each written then read back: 2x traffic)
    moment = size * (gb + 2 * mb + 2 * mb + 4 * 2 * f32)
    # update pass: read p+m'+v', write p', plus three f32 intermediates
    update = size * (pb + 2 * mb + pb + 3 * 2 * f32)
    return moment + update


# ---------------------------------------------------------------------------
# numerics contract
# ---------------------------------------------------------------------------


def reference_update(p, g, m, v, *, lr, beta1, beta2, epsilon,
                     beta1_pow, beta2_pow, weight_decay=0.0,
                     sr_salts=None):
    """Plain ``jax.numpy`` reference the kernel is compared against (CPU
    tests, chip smoke): ``AdamW._update_param``'s expressions verbatim
    (``_moments`` / ``_adam_delta`` / the decoupled decay), beta powers
    already advanced, and with ``sr_salts`` the writeback of
    ``optimizer._stochastic_round_bf16`` under those pinned salts.
    Returns ``(p', m', v')`` in the storage dtypes of the inputs."""
    g32 = g.astype(jnp.float32)
    m_new = beta1 * m.astype(jnp.float32) + (1 - beta1) * g32
    v_new = beta2 * v.astype(jnp.float32) + (1 - beta2) * g32 * g32
    lr_t = lr * jnp.sqrt(1 - beta2_pow) / (1 - beta1_pow)
    delta = lr_t * m_new / (
        jnp.sqrt(v_new) + epsilon * jnp.sqrt(1 - beta2_pow))
    new = p.astype(jnp.float32) * (1.0 - lr * weight_decay) - delta
    if sr_salts is None:
        p_new = new.astype(p.dtype)
    else:
        from ..optimizer.optimizer import _stochastic_round_bf16

        p_new = _stochastic_round_bf16(new, sr_salts)
    return p_new, m_new.astype(m.dtype), v_new.astype(v.dtype)


def update_error_bounds(p, g, m, v, *, lr, beta1, beta2, epsilon,
                        beta1_pow, beta2_pow, weight_decay=0.0,
                        roundings: int = 1):
    """Elementwise ``|kernel - reference|`` bounds ``(dp, dm, dv)`` in
    f32, as float64 numpy arrays: what ``roundings`` extra f32
    roundings per intermediate term can put into each output.

    One rounding is the difference between ``a*b + c`` evaluated as a
    fused multiply-add and as a rounded product then a sum — a relative
    2**-23 of the LARGER term, not of the (possibly cancelled) result.
    The parameter bound adds the moments' own error carried through
    ``lr_t * m / (sqrt(v) + eps)``. Storage narrower than f32 rounds
    once more on top of this; callers compare such outputs after
    allowing one ulp of the storage dtype."""
    import numpy as np

    def f64(a):
        return np.asarray(jnp.asarray(a).astype(jnp.float32), np.float64)

    p, g, m, v = f64(p), f64(g), f64(m), f64(v)
    b1p, b2p = float(beta1_pow), float(beta2_pow)
    eps32 = roundings * 2.0 ** -23
    m_new = beta1 * m + (1 - beta1) * g
    v_new = beta2 * v + (1 - beta2) * g * g
    dm = eps32 * (np.abs(beta1 * m) + np.abs((1 - beta1) * g))
    dv = 2 * eps32 * (np.abs(beta2 * v) + (1 - beta2) * g * g)
    lr_t = lr * np.sqrt(1 - b2p) / (1 - b1p)
    denom = np.sqrt(v_new) + epsilon * np.sqrt(1 - b2p)
    delta = lr_t * m_new / denom
    d_denom = dv / (2 * np.sqrt(np.maximum(v_new, 1e-300))) + eps32 * denom
    d_delta = lr_t * dm / denom + np.abs(delta) * (
        d_denom / denom + 2 * eps32)
    dp = eps32 * (np.abs(p * (1.0 - lr * weight_decay))
                  + np.abs(delta)) + d_delta
    return dp, dm, dv


def assert_matches_reference(got, ref, inputs, *, roundings: int = 1,
                             **hyper) -> float:
    """The contract as a check: each of ``got = (p', m', v')`` against
    ``ref`` (``reference_update`` on the same ``inputs = (p, g, m, v)``
    and ``hyper``). Every output must sit within
    ``update_error_bounds``; one stored narrower than f32 (bf16
    moments, a bf16 or stochastically rounded parameter) gets one ulp
    of its storage dtype on top, for where the f32 value straddles a
    rounding boundary — and that may happen on under 1% of the elements
    (independent SR draws would disagree on about half). Returns the
    largest error found, as a fraction of its bound."""
    import numpy as np

    worst = 0.0
    bounds = update_error_bounds(*inputs, roundings=roundings, **hyper)
    for a, b, bound, name in zip(got, ref, bounds, "pmv"):
        if a.dtype != b.dtype:
            raise AssertionError(f"{name}: dtype {a.dtype} != {b.dtype}")
        a64 = np.asarray(a.astype(jnp.float32), np.float64)
        b64 = np.asarray(b.astype(jnp.float32), np.float64)
        if a.dtype != jnp.float32:
            if (a64 == b64).mean() <= 0.99:
                raise AssertionError(
                    f"{name}: only {(a64 == b64).mean():.4f} of the "
                    f"{a.dtype} elements equal the reference")
            exponent = np.floor(np.log2(np.maximum(np.abs(b64), 1e-300)))
            bound = bound + 2.0 ** (exponent - jnp.finfo(a.dtype).nmant)
        ratio = float(np.max(np.abs(a64 - b64) / bound))
        if ratio > 1:
            raise AssertionError(
                f"{name}: error is {ratio:.2f}x the {roundings}-rounding "
                "bound")
        worst = max(worst, ratio)
    return worst


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _adamw_kernel(scal_ref, salt_ref, p_ref, g_ref, m_ref, v_ref,
                  po_ref, mo_ref, vo_ref, *,
                  beta1: float, beta2: float, use_sr: bool, bt: int):
    # scalar prologue lives in SMEM: (lr_t, eps_eff, decay_f, skip)
    lr_t = scal_ref[0]
    eps_eff = scal_ref[1]
    decay_f = scal_ref[2]
    sk = scal_ref[3] != 0.0

    p = p_ref[...]
    m_old = m_ref[...]
    v_old = v_ref[...]
    # reference compute dtype: arithmetic in f32 regardless of storage
    g32 = g_ref[...].astype(jnp.float32)
    m32 = m_old.astype(jnp.float32)
    v32 = v_old.astype(jnp.float32)

    # _AdamBase._moments op order
    m_new = beta1 * m32 + (1 - beta1) * g32
    v_new = beta2 * v32 + (1 - beta2) * g32 * g32
    # AdamW._update_param + _adam_delta: decay factor and lr_t/eps_eff
    # precomputed outside with the reference scalar expressions
    new = p.astype(jnp.float32) * decay_f \
        - lr_t * m_new / (jnp.sqrt(v_new) + eps_eff)

    if use_sr:
        # _stochastic_round_bf16's lowbias32 hash over the GLOBAL flat
        # element index (tile offset + local C-order index): identical
        # bits to the reference's iota over the unflattened array
        tile = pl.program_id(0)
        row = jax.lax.broadcasted_iota(jnp.uint32, (bt, _LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.uint32, (bt, _LANES), 1)
        i = row * jnp.uint32(_LANES) + lane \
            + tile.astype(jnp.uint32) * jnp.uint32(bt * _LANES)
        u = jax.lax.bitcast_convert_type(new, jnp.uint32)
        b = i * jnp.uint32(0x9E3779B9) + salt_ref[0]
        b = (b ^ (b >> 16)) * jnp.uint32(0x7FEB352D)
        b = (b ^ (b >> 15)) * jnp.uint32(0x846CA68B)
        b = (b ^ (b >> 16)) + salt_ref[1]
        r = jax.lax.bitcast_convert_type(
            (u + (b & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000),
            jnp.float32,
        )
        out = jnp.where(jnp.isfinite(new), r, new).astype(jnp.bfloat16)
    else:
        out = new.astype(po_ref.dtype)

    # found-inf veto: select the ORIGINAL bits before any write lands
    po_ref[...] = jnp.where(sk, p, out)
    mo_ref[...] = jnp.where(sk, m_old, m_new.astype(mo_ref.dtype))
    vo_ref[...] = jnp.where(sk, v_old, v_new.astype(vo_ref.dtype))


def _tile_rows(total: int) -> Tuple[int, int]:
    """(rows per program, padded row count) for a C-order (rows, 128)
    view; bt is a multiple of 16 so both f32 and bf16 tiles are legal."""
    rows = -(-total // _LANES)
    bt = min(256, -(-rows // 16) * 16)
    return bt, -(-rows // bt) * bt


def _pad2d(a, rows_padded: int):
    flat = a.reshape(-1)
    pad = rows_padded * _LANES - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), a.dtype)])
    return flat.reshape(rows_padded, _LANES)


def fused_adamw_update(
    p, g, m, v, *,
    lr, beta1: float, beta2: float, epsilon: float,
    beta1_pow, beta2_pow, weight_decay=0.0,
    sr_salts=None, skip=None, interpret: Optional[bool] = None,
):
    """One fused AdamW step over a single parameter.

    p/g/m/v: arrays of one shape (any rank; m/v may store a narrower
    dtype). ``beta1_pow``/``beta2_pow`` are the ALREADY-ADVANCED beta
    powers (f32 scalars) for this step. ``sr_salts`` — a (2,) uint32
    array — switches on the in-kernel stochastic-rounding bf16
    writeback (requires a bf16 param). ``skip`` is an optional traced
    bool: when true every output equals its input bitwise (the
    GradScaler found-inf veto). Returns ``(p_new, m_new, v_new)`` in
    the storage dtypes of the inputs.
    """
    if interpret is None:
        interpret = _interpret_default()
    total = p.size
    if total == 0:
        return p, m, v
    use_sr = sr_salts is not None
    if use_sr and p.dtype != jnp.bfloat16:
        raise ValueError(
            "stochastic-rounding writeback requires a bf16 param "
            f"(got {p.dtype})")

    # scalar prologue: the exact reference expressions (_adam_delta /
    # the AdamW decay factor), computed once per step outside the grid
    b1p = jnp.asarray(beta1_pow, jnp.float32)
    b2p = jnp.asarray(beta2_pow, jnp.float32)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    eps_eff = epsilon * jnp.sqrt(1 - b2p)
    decay_f = jnp.asarray(1.0 - lr * weight_decay, jnp.float32)
    skip_f = (jnp.asarray(skip).astype(jnp.float32)
              if skip is not None else jnp.zeros((), jnp.float32))
    scalars = jnp.stack([
        lr_t.astype(jnp.float32), eps_eff.astype(jnp.float32),
        decay_f, skip_f,
    ])
    salts = (jnp.asarray(sr_salts, jnp.uint32) if use_sr
             else jnp.zeros((2,), jnp.uint32))

    bt, rows_padded = _tile_rows(total)
    grid = (rows_padded // bt,)
    p2, g2 = _pad2d(p, rows_padded), _pad2d(g, rows_padded)
    m2, v2 = _pad2d(m, rows_padded), _pad2d(v, rows_padded)
    out_p_dtype = jnp.bfloat16 if use_sr else p.dtype

    kernel = functools.partial(
        _adamw_kernel, beta1=float(beta1), beta2=float(beta2),
        use_sr=use_sr, bt=bt)
    tile_spec = pl.BlockSpec((bt, _LANES), lambda i: (i, 0))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    p_new, m_new, v_new = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[smem_spec, smem_spec,
                  tile_spec, tile_spec, tile_spec, tile_spec],
        out_specs=[tile_spec, tile_spec, tile_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_padded, _LANES), out_p_dtype),
            jax.ShapeDtypeStruct((rows_padded, _LANES), m.dtype),
            jax.ShapeDtypeStruct((rows_padded, _LANES), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,),
        ),
        cost_estimate=pl.CostEstimate(
            flops=10 * total,
            bytes_accessed=fused_adamw_hbm_bytes(
                total, p.dtype, g.dtype, m.dtype),
            transcendentals=total,
        ),
        interpret=interpret,
        name="fused_adamw",
    )(scalars, salts, p2, g2, m2, v2)

    unflat = lambda a: a.reshape(-1)[:total].reshape(p.shape)  # noqa: E731
    return unflat(p_new), unflat(m_new), unflat(v_new)

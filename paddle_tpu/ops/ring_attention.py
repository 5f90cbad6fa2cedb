"""Ring attention — sequence/context-parallel attention over a mesh axis.

ref: the reference's SEP (sequence-expert-parallel) context parallelism
(SURVEY §2.7, §5.7: fleet sep utilities + the RingFlashAttention used
by PaddleNLP long-context training). The reference moves K/V around an
NCCL ring with explicit send/recv; here the ring is ``lax.ppermute``
over a named mesh axis inside ``shard_map``, so the schedule is visible
to the XLA latency-hiding scheduler (compute of chunk i overlaps the
permute bringing chunk i+1).

Math: per-device q block attends to every kv block as it passes by;
blocks merge with the streaming log-sum-exp recurrence (same as flash
attention's inter-block merge):

    m' = max(m, lse_i);  l' = l·e^{m-m'} + e^{lse_i-m'}
    acc' = acc·e^{m-m'} + out_i·e^{lse_i-m'}

Causal uses the block-triangular schedule: ring step t brings the kv
block of rank (r - t) mod P — skip if it is ahead of our q block,
full-attend if behind, diagonal-mask if equal.

Everything is jnp + lax (differentiable through ppermute/scan); on TPU
the within-block math hits the MXU and XLA fuses the merge.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["ring_attention", "sep_parallel_attention"]

_NEG = -1e30


def _manual_axes() -> tuple:
    """Axis names bound manually in the current trace context (empty
    outside any shard_map). Single point of contact with the abstract-
    mesh introspection API."""
    from jax.sharding import AxisType

    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return ()
    return tuple(
        n for n, t in zip(am.axis_names, am.axis_types)
        if t == AxisType.Manual
    )


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   vary_axes: Optional[tuple] = None):
    """Sequence-sharded attention; call inside shard_map/pjit over a
    mesh with ``axis_name``. q/k/v: [B, S_local, H, D] (paddle layout).
    Returns [B, S_local, H, D].

    ``vary_axes``: manual axes the scan carries must be marked varying
    over. Defaults to (axis_name,) — correct when this ring owns the
    only manual region; a caller composing inside an outer manual
    shard_map (the pipelined dp x sep x pp path) passes the outer
    manual set so the carry variance matches the k/v entries."""
    p_size = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(d)

    q_off = rank * s_local

    # carry (m, l, acc) in the "unnormalized" space: per block,
    # out_t = sum_k exp(s - m_t)·v and l_t = sum_k exp(s - m_t). Merge:
    #   m' = max(m, m_t); acc' = acc·e^{m-m'} + out_t·e^{m_t-m'}
    #   l'  = l·e^{m-m'} + l_t·e^{m_t-m'}
    # framework policy (tensor/linalg.py matmul, nn/functional/conv.py):
    # f32 inputs get HIGHEST precision — the TPU default truncates
    # einsum operands to bf16
    _prec = (
        jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    )

    def block(q, k_t, v_t, src_rank):
        kv_off = src_rank * s_local
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k_t, 1, 2)
        vh = jnp.swapaxes(v_t, 1, 2)
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qh, kh, preferred_element_type=jnp.float32,
            precision=_prec,
        ) * sc
        if causal:
            q_abs = q_off + jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 0)
            k_abs = kv_off + jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 1)
            s = jnp.where(q_abs >= k_abs, s, _NEG)
        m_t = jnp.max(s, axis=-1)  # [B, H, Sq]
        p = jnp.exp(s - m_t[..., None])
        if causal:
            p = jnp.where(s <= _NEG / 2, 0.0, p)
        l_t = jnp.sum(p, axis=-1)
        out_t = jnp.einsum(
            "bhqk,bhkd->bhqd", p, vh.astype(jnp.float32), precision=_prec
        )
        return out_t, m_t, l_t

    def merge(state, k_t, v_t, t):
        m, l, acc = state
        src_rank = (rank - t) % p_size
        out_t, m_t, l_t = block(q, k_t, v_t, src_rank)
        if causal:
            live = (src_rank <= rank).astype(jnp.float32)
            l_t = l_t * live
            out_t = out_t * live
            m_t = jnp.where(live > 0, m_t, _NEG)

        m_new = jnp.maximum(m, m_t)
        a = jnp.where(m > _NEG / 2, jnp.exp(m - m_new), 0.0)
        b_ = jnp.where(m_t > _NEG / 2, jnp.exp(m_t - m_new), 0.0)
        l = l * a + l_t * b_
        acc = acc * a[..., None] + out_t * b_[..., None]
        return m_new, l, acc

    def scan_step(carry, t):
        k_t, v_t, m, l, acc = carry
        m, l, acc = merge((m, l, acc), k_t, v_t, t)
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        return (k_t, v_t, m, l, acc), None

    def _varying(x):
        # shard_map scans need device-varying carries
        return jax.lax.pcast(x, vary_axes or (axis_name,), to="varying")

    m0 = _varying(jnp.full((b, h, s_local), _NEG, jnp.float32))
    l0 = _varying(jnp.zeros((b, h, s_local), jnp.float32))
    acc0 = _varying(jnp.zeros((b, h, s_local, d), jnp.float32))
    # scan the first P-1 ring steps (each permutes kv onward), then fold
    # in the final block without the wasted last permute
    if p_size > 1:
        (k_t, v_t, m, l, acc), _ = jax.lax.scan(
            scan_step, (k, v, m0, l0, acc0), jnp.arange(p_size - 1)
        )
    else:
        k_t, v_t, m, l, acc = k, v, m0, l0, acc0
    m, l, acc = merge((m, l, acc), k_t, v_t, p_size - 1)
    safe_l = jnp.where(l > 0, l, 1.0)
    out = acc / safe_l[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [B, S_local, H, D]


def _axis_already_manual(axis_name: str) -> bool:
    """True when the current trace is inside a shard_map that bound
    ``axis_name`` manually — the caller's arrays are already local
    shards and a nested shard_map over the axis would be rejected."""
    return axis_name in _manual_axes()


def sep_parallel_attention(q, k, v, mesh=None, axis_name: str = "sep",
                           causal: bool = False,
                           scale: Optional[float] = None):
    """User entry (ref: the sep_parallel attention path in fleet
    meta_parallel). Two calling contexts:

    - OUTSIDE any manual region (the usual case): q/k/v are GLOBAL
      [B, S, H, D] Tensors/arrays; opens a shard_map over ``mesh``'s
      ``axis_name``, runs ring attention on the sequence shards,
      returns the global result.
    - INSIDE a shard_map that already bound ``axis_name`` (e.g. the
      pipelined region binding sep manually): q/k/v are the LOCAL
      sequence shards; runs the ring body directly on the bound axis —
      this is what lets sep compose inside dp x sep x pp pipelines.
    """
    from jax.sharding import PartitionSpec as P

    from ..base.tape import apply

    if _axis_already_manual(axis_name):
        return apply(
            partial(ring_attention, axis_name=axis_name, causal=causal,
                    scale=scale, vary_axes=_manual_axes()),
            q, k, v, op_name="sep_parallel_attention_local",
        )

    if mesh is None:
        raise ValueError(
            "sep_parallel_attention needs `mesh` when called outside a "
            f"manual region binding axis {axis_name!r}"
        )
    spec = P(None, axis_name, None, None)

    def f(qq, kk, vv):
        fn = jax.shard_map(
            partial(ring_attention, axis_name=axis_name, causal=causal,
                    scale=scale),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
        return fn(qq, kk, vv)

    return apply(f, q, k, v, op_name="sep_parallel_attention")

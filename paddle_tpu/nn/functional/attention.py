"""Attention functionals.

ref: python/paddle/nn/functional/flash_attention.py:198
(flash_attention / scaled_dot_product_attention wrapping the external
FlashAttention-2 CUDA library via phi flash_attn kernels).

TPU-native design: one public entry, ``scaled_dot_product_attention``,
that dispatches to
- a **Pallas flash-attention kernel** (paddle_tpu.ops.flash_attention)
  when running on TPU with supported shapes/dtypes, and
- a reference jnp implementation otherwise (CPU tests, odd shapes).
Layout follows the reference: [batch, seq, num_heads, head_dim].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...base.tape import apply
from ...base.tensor import Tensor

__all__ = ["scaled_dot_product_attention", "flash_attention", "sdp_kernel", "flash_attn_qkvpacked", "flash_attention_with_sparse_mask", "flash_attn_varlen_qkvpacked"]


def _naive_attention(q, k, v, mask, dropout_p, causal, scale, key):
    """Reference jnp path; q/k/v: [B, S, H, D] (paddle flash-attn layout)."""
    qh = jnp.swapaxes(q, 1, 2)  # [B, H, S, D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    # GQA: broadcast kv heads over query-head groups
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    logits = logits.astype(jnp.float32)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), jnp.zeros((), probs.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # back to [B, S, H, D]


def _use_pallas(q_shape, dtype, mask, dropout_p) -> bool:
    if mask is not None or dropout_p > 0:
        return False
    if jax.devices()[0].platform != "tpu":
        return False
    head_dim = q_shape[-1]
    return head_dim in (64, 128, 256) and q_shape[1] % 128 == 0


def _flash_over_mesh(mesh, q, k, v, causal):
    """The flash kernel under a multi-device mesh. GSPMD cannot
    partition a Mosaic kernel (jax refuses to lower one inside a
    multi-device jit: "wrap the call in a shard_map"), so the kernel is
    mapped by hand: heads over ``mp`` — where tensor parallelism's
    column-parallel qkv already leaves them — and batch over the data
    axes, each only when it divides; what does not divide is replicated
    and every chip then runs that part whole. The sequence stays whole
    per shard (sequence parallelism is ``sep_parallel_attention``)."""
    from jax.sharding import PartitionSpec as P

    from ...ops.flash_attention import flash_attention_fwd

    sizes = dict(mesh.shape)
    data_axes = tuple(a for a in ("dp", "sharding") if sizes.get(a, 1) > 1)
    n_data = int(np.prod([sizes[a] for a in data_axes])) if data_axes else 1
    batch = data_axes if data_axes and q.shape[0] % n_data == 0 else None
    mp = sizes.get("mp", 1)
    heads = "mp" if mp > 1 and q.shape[2] % mp == 0 \
        and k.shape[2] % mp == 0 else None
    spec = P(batch, None, heads, None)
    return jax.shard_map(
        lambda qq, kk, vv: flash_attention_fwd(qq, kk, vv, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _fleet_mesh():
    """The hybrid mesh ``fleet.init`` built when it spans several
    devices, else None (one device: the kernel is called directly)."""
    from ...distributed.fleet.base.topology import (
        get_hybrid_communicate_group,
    )

    hcg = get_hybrid_communicate_group()
    return hcg.mesh if hcg is not None and hcg.mesh.size > 1 else None


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p=0.0,
    is_causal=False,
    training=True,
    name=None,
):
    """ref: python/paddle/nn/functional/flash_attention.py
    scaled_dot_product_attention. Input layout [B, S, H, D]."""
    from ...base import random as _random

    if not training:
        dropout_p = 0.0
    rng_key = _random.next_key() if dropout_p > 0 else None

    if _use_pallas(tuple(query.shape), query.dtype, attn_mask, dropout_p):
        # no fallback: a kernel the compiler refuses must surface, not
        # silently become the S x S jnp path
        from ...ops.flash_attention import flash_attention_fwd

        mesh = _fleet_mesh()

        def _pallas(qq, kk, vv):
            if mesh is not None:
                return _flash_over_mesh(mesh, qq, kk, vv, is_causal)
            return flash_attention_fwd(qq, kk, vv, causal=is_causal)

        return apply(_pallas, query, key, value, op_name="flash_attention")

    def _f(qq, kk, vv, *maybe_mask):
        m = maybe_mask[0] if maybe_mask else None
        return _naive_attention(qq, kk, vv, m, dropout_p, is_causal, None, rng_key)

    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    return apply(_f, *args, op_name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False, fixed_seed_offset=None, rng_name="", training=True, name=None):
    """ref: flash_attention.py:198 — same output tuple (out, softmax)."""
    out = scaled_dot_product_attention(
        query, key, value, None, dropout, causal, training
    )
    return out, None


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False, return_softmax=False, fixed_seed_offset=None, rng_name="", training=True, name=None):
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    return flash_attention(q, k, v, dropout, causal, return_softmax, fixed_seed_offset, rng_name, training, name)


class sdp_kernel:
    """Context selecting the attention backend (parity shim; TPU picks
    automatically between Pallas and jnp)."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def flash_attention_with_sparse_mask(query, key, value, attn_mask_start_row_indices=None,
                                     attn_mask_start_row=0, dropout_p=0.0,
                                     is_causal=True, training=True, name=None):
    """ref: flash_attention.py flash_attention_with_sparse_mask — causal
    attention where row i additionally masks keys before
    start_row_indices[i]. Lowered to SDPA with the composed mask."""
    if attn_mask_start_row_indices is None:
        return scaled_dot_product_attention(query, key, value, None, dropout_p, is_causal, training)

    def _f(q, k, v, start_rows):
        b, s, h, d = q.shape
        r = jnp.arange(s)
        causal = r[None, :] <= r[:, None]
        # start_rows: [B, H, S] or [B, S]; key j masked for rows >= start_rows[j]
        sr = start_rows if start_rows.ndim == 3 else start_rows[:, None, :]
        # row i attends key j iff j <= i AND i < start_rows[..., j]
        mask = causal[None, None] & (r[None, None, :, None] < sr[:, :, None, :])
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d)
        logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, -1).astype(q.dtype)
        return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", probs, vh), 1, 2)

    return apply(_f, query, key, value, attn_mask_start_row_indices, op_name="flash_attention_with_sparse_mask")


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k, max_seqlen_q, max_seqlen_k,
                                scale=None, dropout=0.0, causal=False, return_softmax=False,
                                fixed_seed_offset=None, rng_name="", varlen_padded=True,
                                training=True, name=None):
    """ref: flash_attention.py flash_attn_varlen_qkvpacked — packed
    variable-length batches. Segment ids from cu_seqlens mask
    cross-sequence attention; one SDPA over the packed [total, ...]."""

    def _f(packed, cu_q):
        # packed: [total, 3, H, D] (varlen_padded packs all seqs)
        total = packed.shape[0]
        q = packed[:, 0]
        k = packed[:, 1]
        v = packed[:, 2]
        pos = jnp.arange(total)
        seg = jnp.searchsorted(cu_q, pos, side="right")  # segment id per token
        same = seg[:, None] == seg[None, :]
        if causal:
            same = same & (pos[None, :] <= pos[:, None])
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / np.sqrt(d)
        logits = jnp.einsum("qhd,khd->hqk", q, k) * s
        logits = jnp.where(same[None], logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, -1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = apply(_f, qkv, cu_seqlens_q, op_name="flash_attn_varlen_qkvpacked")
    return (out, None) if return_softmax else out

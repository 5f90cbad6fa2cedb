"""Block-sparse attention over a per-token LIST of key blocks (the
trainable sparse attention of InfLLM-V2, arXiv:2509.24663, as MiniCPM4 /
MiniCPM-SALA use it) — the selection in plain XLA walked in chunks of
queries, the attention as Pallas TPU kernels with a custom VJP.

``select_blocks(q, k)``: which blocks of 64 keys each token reads. Query
head ``h`` belongs to kv group ``h // (H / G)``; no gradient. With
``Kc_j`` the mean of the keys ``stride j .. stride j + kernel_size - 1``
of a group::

    a[t, h, :]  = softmax_j(q[t, h] . Kc_j / sqrt(d))   over the kernels
                  that END at or before t
    A[t, g, j]  = the sum of a over the group's heads
    s[t, g, b]  = the max of A over the kernels that overlap block b
    forced: the first ``init_blocks`` blocks and the ``window_size /
    block_size`` blocks up to t's own; blocks after t's own are out;
    the set: the ``topk`` best (forced ones first; every valid block
    when fewer are valid), ties to the lower id

-> table [B, G, S, topk] int32, a token's ids ascending, then -1.
Nothing of size [S, H, S / stride] reaches HBM: the queries are walked
``_SELECT_CHUNK`` at a time (``lax.map``), and a chunk's scores, softmax,
group sum and max-pool live and die inside its step.

``block_sparse_attention(q, k, v, table)``: for every token, softmax
attention (scale ``1 / sqrt(d)``) over the keys ``i <= t`` that lie in
the blocks its row of the table names — ANY table: entries < 0 and
blocks after the token's own are no keys, a row's other entries are
distinct, rows may differ from token to token in every entry (a token
with no key at all gets zeros). Work is proportional to the table: a kv
group's K and V are resident in VMEM (``S * d`` elements each: 4 MB at
16,384 tokens of 128 in bfloat16), a grid step holds ``_TOKENS``
tokens, and for each of them the kernel copies its blocks next to each
other in scratch (a copy inside VMEM, no gather from HBM) and runs the
group's heads [H / G, d] against them: scores [H / G, topk * 64],
softmax, the product with the values. No [S, S] array exists anywhere.
The token's heads are the MXU's rows (16 of them in MiniCPM-SALA: an
eighth of its 128): tokens cannot share a pass unless their sets agree,
and a kernel that computed the union of a tile's sets would be exact
only by masking, at dense cost where neighbours' sets differ.

``sparse_attn_fwd`` writes ``o`` and the log-sum-exp a token and head.
``sparse_attn_bwd`` computes a token's probabilities again from them,
writes ``dq``, and ADDS the token's ``dK`` and ``dV`` rows into float32
blocks that stay resident for the whole group (the scatter-add is inside
VMEM too). The table (as made canonical: valid ids ascending first) is a
residual of the forward pass.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default
from .gated_delta_rule import _NN, _NT, _TN, _mm

__all__ = ["select_blocks", "block_sparse_attention", "BLOCK", "KERNELS"]

BLOCK = 64                       # keys a block holds: the kernels' constant
KERNELS = ("sparse_attn_fwd", "sparse_attn_bwd")
_TOKENS = 128                    # tokens a grid step holds
_SELECT_CHUNK = 512              # queries a step of the selection holds
_VMEM_LIMIT = 100 * 2 ** 20      # K, V and (backward) dK, dV of a group
_NEG = -1e30

_SEM = pltpu.GridDimensionSemantics
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=(_SEM.PARALLEL, _SEM.PARALLEL, _SEM.ARBITRARY),
    vmem_limit_bytes=_VMEM_LIMIT)


# -- the selection -----------------------------------------------------------


def pooled_keys(k, kernel_size: int, stride: int):
    """k [B, S, G, d] -> the means of the keys under each kernel
    [B, S / stride - kernel_size / stride + 1, G, d], float32."""
    b, s, g, d = k.shape
    r = kernel_size // stride
    parts = jnp.sum(k.astype(jnp.float32).reshape(b, s // stride, stride, g,
                                                  d), axis=2)
    n = s // stride - r + 1
    return sum(parts[:, i:i + n] for i in range(r)) / kernel_size


@functools.partial(jax.jit, static_argnames=(
    "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
    "window_size"))
def select_blocks(q, k, *, kernel_size: int = 32, kernel_stride: int = 16,
                  block_size: int = BLOCK, topk: int = 64,
                  init_blocks: int = 1, window_size: int = 2048):
    """The rule of the module docstring: q [B, S, H, d], k [B, S, G, d]
    -> table [B, G, S, min(topk, S / block_size)] int32."""
    b, s, h, d = q.shape
    g = k.shape[2]
    if kernel_size % kernel_stride or block_size % kernel_stride \
            or s % block_size or window_size % block_size:
        raise ValueError(
            f"kernel {kernel_size}/{kernel_stride}, block {block_size} and "
            f"window {window_size} do not tile a sequence of {s}")
    kc = pooled_keys(jax.lax.stop_gradient(k), kernel_size,
                     kernel_stride).astype(q.dtype)
    kernels, blocks = kc.shape[1], s // block_size
    picks = min(topk, blocks)
    per = block_size // kernel_stride           # kernels a block's start moves
    wide = (kernel_size + block_size) // kernel_stride - 1
    left = kernel_size // kernel_stride - 1
    ends = kernel_stride * jnp.arange(kernels) + kernel_size - 1
    chunk = min(_SELECT_CHUNK, s)
    if s % chunk:
        raise ValueError(f"sequence {s} is no multiple of {chunk}")
    qs = jax.lax.stop_gradient(q).reshape(b, s // chunk, chunk, g, h // g, d)

    def one(args):
        qc, first = args                                   # [B, T, G, hg, d]
        t = first + jnp.arange(chunk)
        logits = jnp.einsum("btghd,bjgd->btghj", qc, kc,
                            preferred_element_type=jnp.float32) * d ** -0.5
        seen = (ends[None, :] <= t[:, None])[None, :, None, None, :]
        logits = jnp.where(seen, logits, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(logits - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(e, axis=-1, keepdims=True)
        a = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=3)  # [B,T,G,J]
        score = _pooled(a, blocks, per, wide, left)      # [B, T, G, blocks]
        own = (t // block_size)[None, :, None, None]
        at = jnp.arange(blocks)[None, None, None, :]
        forced = (at < init_blocks) | (at > own - window_size // block_size)
        score = jnp.where(forced, jnp.inf, score)
        score = jnp.where(at <= own, score, -jnp.inf)
        return _best_ascending(score, picks)

    table = jax.lax.map(one, (jnp.moveaxis(qs, 1, 0),
                              chunk * jnp.arange(s // chunk)))
    # [chunks, B, T, G, picks] -> [B, G, S, picks]
    return jnp.moveaxis(table, 0, 1).reshape(b, s, g, picks).transpose(
        0, 2, 1, 3)


def _pooled(a, blocks: int, per: int, wide: int, left: int):
    """a [..., J] -> [..., blocks]: the max over the ``wide`` kernels from
    ``per * b - left`` on (a max-pool of stride ``per``), as maxima over
    rows of ``per``: XLA's ``reduce_window`` over the minor axis costs
    seven times as much on the chip (PERF.md section 6)."""
    rows = -(-wide // per)
    total = (blocks + rows) * per
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(left, total - left - a.shape[-1])],
                constant_values=-jnp.inf)
    a = a.reshape(*a.shape[:-1], blocks + rows, per)
    return functools.reduce(jnp.maximum, [
        jnp.max(a[..., i:i + blocks, :min(per, wide - i * per)], axis=-1)
        for i in range(rows)])


def _best_ascending(score, picks: int):
    """score [..., blocks] -> [..., picks] int32: the ids of the ``picks``
    best that are above -inf, ties to the lower id, ASCENDING, then -1. By
    counting who is ahead of whom and not by ``lax.top_k`` and a sort,
    which cost 61 ms of the selection's 63 on the chip where this costs 7
    (PERF.md section 6)."""
    blocks = score.shape[-1]
    at = jnp.arange(blocks)
    mine, other = score[..., :, None], score[..., None, :]
    ahead = (other > mine) | ((other == mine) & (at[None, :] < at[:, None]))
    chosen = (jnp.sum(ahead, axis=-1) < picks) & (score > -jnp.inf)
    place = jnp.cumsum(chosen, axis=-1) - 1
    hit = chosen[..., None, :] & (place[..., None, :]
                                  == jnp.arange(picks)[:, None])
    ids = jnp.sum(jnp.where(hit, at, 0), axis=-1)
    count = jnp.sum(chosen, axis=-1, keepdims=True)
    return jnp.where(jnp.arange(picks) < count, ids, -1).astype(jnp.int32)


# -- the attention -----------------------------------------------------------


def _canonical(table, s: int):
    """table [B, G, S, K] (any) -> [B, G, S, K + 1] int32: the valid
    entries ascending, then zeros; in the last column how many of the
    K * 64 keys so laid out the token sees — they are the first ones,
    because only the last valid block can be the token's own."""
    t = jnp.arange(s, dtype=jnp.int32)[None, None, :, None]
    own = t // BLOCK
    valid = (table >= 0) & (table <= own)
    big = jnp.int32(2 ** 30)
    ids = jnp.sort(jnp.where(valid, table.astype(jnp.int32), big), axis=-1)
    n = jnp.sum(valid, axis=-1, dtype=jnp.int32)
    last = jnp.take_along_axis(ids, jnp.maximum(n - 1, 0)[..., None], -1)
    partial = jnp.where(last == own, t % BLOCK + 1, BLOCK)[..., 0]
    keys = jnp.where(n > 0, (n - 1) * BLOCK + partial, 0)
    return jnp.concatenate([jnp.where(ids < big, ids, 0), keys[..., None]],
                           axis=-1).astype(jnp.int32)


def _gather(tab_ref, t, pairs):
    """Copy the blocks row ``t`` of the table names next to each other:
    ``pairs`` of (resident [1, 1, S, d] ref, scratch [K * 64, d] ref). A
    static loop: unrolled, a token's 64 copies overlap (on the chip the
    forward read 41 ms for the 70 of a ``fori_loop``, PERF.md section 6)."""
    for j in range(tab_ref.shape[3] - 1):
        start = pl.multiple_of(tab_ref[0, 0, t, j] * BLOCK, BLOCK)
        for src, dst in pairs:
            dst[j * BLOCK:(j + 1) * BLOCK, :] = \
                src[0, 0, pl.ds(start, BLOCK), :]


def _lane_is(width, t):
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) == t


def _fwd_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                ksel, vsel, *, scale):
    dt = q_ref.dtype
    tokens = q_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ksel.shape[0]), 1)

    def token(t, stats):
        _gather(tab_ref, t, ((k_ref, ksel), (v_ref, vsel)))
        seen = lane < tab_ref[0, 0, t, tab_ref.shape[3] - 1]
        s = jnp.where(seen, scale * _mm(q_ref[0, 0, t], ksel[...], _NT, dt),
                      _NEG)
        top = jnp.max(s, axis=1, keepdims=True)
        p = jnp.where(seen, jnp.exp(s - top), 0.0)
        total = jnp.sum(p, axis=1, keepdims=True)
        some = total > 0.0
        o = _mm(p, vsel[...], _NN, dt) / jnp.where(some, total, 1.0)
        o_ref[0, 0, t] = o.astype(o_ref.dtype)
        lse = jnp.where(some, top + jnp.log(jnp.where(some, total, 1.0)), 0.0)
        return jnp.where(_lane_is(tokens, t), lse, stats)

    lse_ref[0, 0, 0] = jax.lax.fori_loop(
        0, tokens, token, jnp.zeros(lse_ref.shape[3:], jnp.float32))


def _bwd_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                do_ref, dq_ref, dk_ref, dv_ref, ksel, vsel, dksel, dvsel, *,
                scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    dt = q_ref.dtype
    tokens = q_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ksel.shape[0]), 1)
    stats = lse_ref[0, 0, 0]                               # [heads, tokens]

    def token(t, carry):
        _gather(tab_ref, t, ((k_ref, ksel), (v_ref, vsel)))
        seen = lane < tab_ref[0, 0, t, tab_ref.shape[3] - 1]
        q, do = q_ref[0, 0, t], do_ref[0, 0, t]
        lse = jnp.sum(jnp.where(_lane_is(tokens, t), stats, 0.0), axis=1,
                      keepdims=True)
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, 0, t].astype(jnp.float32), axis=1,
                        keepdims=True)
        s = scale * _mm(q, ksel[...], _NT, dt)
        p = jnp.where(seen, jnp.exp(jnp.where(seen, s - lse, 0.0)), 0.0)
        ds = scale * p * (_mm(do, vsel[...], _NT, dt) - delta)
        dq_ref[0, 0, t] = _mm(ds, ksel[...], _NN, dt).astype(dq_ref.dtype)
        dksel[...] = _mm(ds, q, _TN, dt)
        dvsel[...] = _mm(p, do, _TN, dt)

        for j in range(tab_ref.shape[3] - 1):
            start = pl.multiple_of(tab_ref[0, 0, t, j] * BLOCK, BLOCK)
            at = slice(j * BLOCK, (j + 1) * BLOCK)
            dk_ref[0, 0, pl.ds(start, BLOCK), :] += dksel[at, :]
            dv_ref[0, 0, pl.ds(start, BLOCK), :] += dvsel[at, :]
        return carry

    jax.lax.fori_loop(0, tokens, token, 0)


def _tokens(s: int) -> int:
    for n in (_TOKENS, BLOCK):
        if s % n == 0:
            return n
    raise ValueError(f"sequence {s} is no multiple of the block {BLOCK}")


def _specs(s, n, hg, d, picks):
    """The blocks of a grid step (batch i, group g, step t): the
    canonical table's rows in SMEM, a token tile of the heads-major
    arrays [B, G, S, hg, d], a group's whole K or V [B, G, S, d], the
    tile's log-sum-exps [B, G, S / n, hg, n]."""
    return (pl.BlockSpec((1, 1, n, picks + 1), lambda i, g, t: (i, g, t, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, n, hg, d), lambda i, g, t: (i, g, t, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda i, g, t: (i, g, 0, 0)),
            pl.BlockSpec((1, 1, 1, hg, n), lambda i, g, t: (i, g, t, 0, 0)))


def _by_group(x, g):
    """[B, S, H, d] -> [B, G, S, H / G, d]."""
    b, s, h, d = x.shape
    return x.reshape(b, s, g, h // g, d).transpose(0, 2, 1, 3, 4)


def _by_token(x):
    """``_by_group``'s inverse."""
    b, g, s, hg, d = x.shape
    return x.transpose(0, 2, 1, 3, 4).reshape(b, s, g * hg, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_fwd(q, k, v, ids, interpret: bool):
    """-> (o [B, G, S, hg, d], lse [B, G, S / n, hg, n]) from q as
    ``_by_group`` lays it out, k, v [B, G, S, d] and the canonical
    table. Jitted so that a model's layers share one trace."""
    b, g, s, hg, d = q.shape
    n, picks = _tokens(s), ids.shape[3] - 1
    tab, tile, whole, stats = _specs(s, n, hg, d, picks)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=d ** -0.5),
        grid=(b, g, s // n),
        in_specs=[tab, tile, whole, whole],
        out_specs=[tile, stats],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, g, s // n, hg, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((picks * BLOCK, d), k.dtype),
                        pltpu.VMEM((picks * BLOCK, d), v.dtype)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[0],
    )(ids, q, k, v)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_bwd(q, k, v, ids, o, lse, do, interpret: bool):
    """-> (dq [B, G, S, hg, d], dk, dv [B, G, S, d] float32)."""
    b, g, s, hg, d = q.shape
    n, picks = _tokens(s), ids.shape[3] - 1
    tab, tile, whole, stats = _specs(s, n, hg, d, picks)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=d ** -0.5),
        grid=(b, g, s // n),
        in_specs=[tab, tile, whole, whole, tile, stats, tile],
        out_specs=[tile, whole, whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((picks * BLOCK, d), k.dtype),
                        pltpu.VMEM((picks * BLOCK, d), v.dtype),
                        pltpu.VMEM((picks * BLOCK, d), jnp.float32),
                        pltpu.VMEM((picks * BLOCK, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[1],
    )(ids, q, k, v, o, lse, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def block_sparse_attention(q, k, v, table, interpret: Optional[bool] = None):
    """The attention of the module docstring: q [B, S, H, d], k, v [B, S,
    G, d], table [B, G, S, K] int -> o [B, S, H, d]."""
    return _attn_fwd(q, k, v, table, interpret)[0]


def _attn_fwd(q, k, v, table, interpret):
    b, s, h, d = q.shape
    g = k.shape[2]
    _tokens(s)                # a ValueError where S is no multiple of a block
    if h % g or table.shape[:3] != (b, g, s):
        raise ValueError(f"table {table.shape} for q {q.shape}, k {k.shape}")
    if interpret is None:
        interpret = _interpret_default()
    ids = _canonical(table, s)
    q5, k4, v4 = _by_group(q, g), k.transpose(0, 2, 1, 3), \
        v.transpose(0, 2, 1, 3)
    o5, lse = _sparse_fwd(q5, k4, v4, ids, interpret)
    return _by_token(o5), (q5, k4, v4, ids, o5, lse)


def _attn_bwd(interpret, res, do):
    q5, k4, v4, ids, o5, lse = res
    if interpret is None:
        interpret = _interpret_default()
    dq5, dk4, dv4 = _sparse_bwd(q5, k4, v4, ids, o5, lse,
                                _by_group(do, k4.shape[1]), interpret)
    return (_by_token(dq5), dk4.transpose(0, 2, 1, 3).astype(k4.dtype),
            dv4.transpose(0, 2, 1, 3).astype(v4.dtype),
            np.zeros(ids.shape[:3] + (ids.shape[3] - 1,), jax.dtypes.float0))


block_sparse_attention.defvjp(_attn_fwd, _attn_bwd)

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
tokens, and for each of them the per-token pass copies its blocks next
to each other in scratch (a copy inside VMEM, no gather from HBM) and
runs the group's heads [H / G, d] against them: scores [H / G, K * 64],
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

**The band.** Where a RULE made the table, part of every row is known
ahead: ``select_blocks`` forces the first ``init_blocks`` blocks and the
``window_blocks`` = ``window_size / block_size`` blocks up to a token's
own, and which those are depends on the token's block alone. So the 64
tokens of a query block share them, and they are one contiguous slice of
K (the window, clipped at 0) plus the first blocks where the window does
not hold them. ``block_sparse_attention(..., init_blocks=, window_blocks=)``
is told the rule (static arguments; ``None``: any table, as above) and
attends that band ONCE a query block, as dense tiles: a query block's
64 x H / G rows of ``q`` (1,024 in MiniCPM-SALA; the heads-major layout
read as rows, the same bytes) against tiles of ``_BAND_KEYS`` keys, every
tile whole but the last, whose mask is the tokens' own block's
(``sparse_attn_fwd_band``: flash's arithmetic, float32 scores, running
max and sum; it leaves a float32 partial result and its log-sum-exp a
token and head). The per-token pass then walks the FREE columns alone
(the canonical table drops the band's ids: ``K - init_blocks -
window_blocks`` columns, all whole blocks) and starts each token's max,
sum and accumulator from the band's, so the merge of the two partial
softmaxes happens inside ``sparse_attn_fwd``. Backward,
``sparse_attn_bwd_band`` makes a tile's scores and ``dP`` once from the
merged log-sum-exp and leaves the band's ``dq`` (float32) and ``dK``,
``dV``; ``sparse_attn_bwd`` adds the free blocks' on top (``dq`` a
token; ``dK``, ``dV`` in the band's own buffers, fetched into VMEM where
a group begins). **The contract with the arguments**: every row holds
each of its valid band blocks (``b < init_blocks`` or ``b > own -
window_blocks``, ``b <= own``) and at most ``K - init_blocks -
window_blocks`` others — ``select_blocks`` guarantees it by construction
(forced scores are +inf); a row that lacks a band block is attended as
if it held it, and free ids past the free columns are dropped. The band
engages only where the shapes allow it (``band_engages``: free columns
exist and the sequence is longer than the band), else the any-table
path runs; ``band_blocks`` says how many block reads it serves.
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

from .pallas_common import NN, NT, TN, interpret_default, mm

__all__ = ["select_blocks", "block_sparse_attention", "band_engages",
           "band_blocks", "BLOCK", "KERNELS", "BAND_KERNELS"]

BLOCK = 64                       # keys a block holds: the kernels' constant
KERNELS = ("sparse_attn_fwd", "sparse_attn_bwd")      # they write o and dq
BAND_KERNELS = ("sparse_attn_fwd_band", "sparse_attn_bwd_band")
_TOKENS = 128                    # tokens a grid step holds
_BODY_BLOCKS = 64                # block copies a body of the per-token loop
                                 # holds: two tokens where their columns fit
_BAND_KEYS = (2048, 512)         # keys a tile of the band pass holds, forward
                                 # and backward
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


def band_engages(s: int, width: int, init_blocks: Optional[int],
                 window_blocks: Optional[int]) -> bool:
    """Whether a table of ``width`` columns over ``s`` tokens, made by a
    rule that forces ``init_blocks`` first blocks and ``window_blocks`` up
    to a token's own, is attended as band + free blocks: the rule is
    known, it leaves free columns, and the sequence is longer than the
    band."""
    if init_blocks is None or window_blocks is None:
        return False
    forced = init_blocks + window_blocks
    return (init_blocks >= 0 and window_blocks >= 1 and width > forced
            and s // BLOCK > forced)


def band_blocks(s: int, width: int, init_blocks: Optional[int],
                window_blocks: Optional[int]) -> int:
    """Block reads the band pass serves over one sequence and kv group
    (0 where it does not engage): a token of block ``b`` has the window's
    ``min(b + 1, window_blocks)`` and the first blocks before it."""
    if not band_engages(s, width, init_blocks, window_blocks):
        return 0
    return BLOCK * sum(
        min(b + 1, window_blocks) + min(init_blocks,
                                        max(b - window_blocks + 1, 0))
        for b in range(s // BLOCK))


def _canonical(table, s: int, band=None):
    """table [B, G, S, K] (any) -> [B, G, S, W + 1] int32: the valid
    entries ascending, then zeros; in the last column how many of the
    W * 64 keys so laid out the token sees — they are the first ones,
    because only the last valid block can be the token's own. W is K, or
    with ``band`` = (init blocks, window blocks) K less the band's
    columns: the band's ids are no entries then (a row's free blocks are
    all whole)."""
    t = jnp.arange(s, dtype=jnp.int32)[None, None, :, None]
    own = t // BLOCK
    valid = (table >= 0) & (table <= own)
    width = table.shape[-1]
    if band is not None:
        init, window = band
        valid &= (table >= init) & (table <= own - window)
        width -= init + window
    big = jnp.int32(2 ** 30)
    ids = jnp.sort(jnp.where(valid, table.astype(jnp.int32), big),
                   axis=-1)[..., :width]
    n = jnp.minimum(jnp.sum(valid, axis=-1, dtype=jnp.int32), width)
    last = jnp.take_along_axis(ids, jnp.maximum(n - 1, 0)[..., None], -1)
    partial = jnp.where(last == own, t % BLOCK + 1, BLOCK)[..., 0]
    keys = jnp.where(n > 0, (n - 1) * BLOCK + partial, 0)
    return jnp.concatenate([jnp.where(ids < big, ids, 0), keys[..., None]],
                           axis=-1).astype(jnp.int32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


# the per-token pass ---------------------------------------------------------


def _gather(tab_ref, t, pairs):
    """Copy the blocks row ``t`` of the table names next to each other:
    ``pairs`` of (resident [1, 1, S, d] ref, scratch [W * 64, d] ref). A
    static loop: unrolled, a token's copies overlap (on the chip the
    forward read 41 ms for the 70 of a ``fori_loop``, PERF.md section 6)."""
    for j in range(tab_ref.shape[3] - 1):
        start = pl.multiple_of(tab_ref[0, 0, t, j] * BLOCK, BLOCK)
        for src, dst in pairs:
            dst[j * BLOCK:(j + 1) * BLOCK, :] = \
                src[0, 0, pl.ds(start, BLOCK), :]


def _lane_is(width, t):
    return _iota((1, width), 1) == t


def _at_lane(stats, t):
    """stats [heads, tokens] -> the column of token ``t`` [heads, 1]."""
    return jnp.sum(jnp.where(_lane_is(stats.shape[1], t), stats, 0.0),
                   axis=1, keepdims=True)


def _fwd_kernel(tab_ref, q_ref, k_ref, v_ref, *rest, scale, band):
    """A grid step's tokens over their table's blocks, ``_chains`` tokens
    a loop body walked stage by stage (their chains of copy, scores, max,
    exp, sum, product are independent and interleave); with ``band`` a
    token's running max, sum and accumulator start from the band pass's
    partial result (``ob_ref``, float32) and log-sum-exp."""
    if band:
        ob_ref, lb_ref, o_ref, lse_ref, ksel, vsel = rest
        before = lb_ref[0, 0, 0]                           # [heads, tokens]
    else:
        o_ref, lse_ref, ksel, vsel = rest
    dt = q_ref.dtype
    tokens, chains = q_ref.shape[2], ksel.shape[0]
    lane = _iota((1, ksel.shape[1]), 1)
    each = range(chains)

    def body(step, stats):
        ts = [step * chains + c for c in each]
        for c in each:
            _gather(tab_ref, ts[c], ((k_ref, ksel.at[c]), (v_ref, vsel.at[c])))
        seen = [lane < tab_ref[0, 0, t, tab_ref.shape[3] - 1] for t in ts]
        s = [jnp.where(seen[c], scale * mm(q_ref[0, 0, ts[c]], ksel[c], NT,
                                           dt), _NEG) for c in each]
        top = [jnp.max(x, axis=1, keepdims=True) for x in s]
        if band:
            base = [_at_lane(before, t) for t in ts]
            top = [jnp.maximum(a, b) for a, b in zip(top, base)]
        p = [jnp.where(seen[c], jnp.exp(s[c] - top[c]), 0.0) for c in each]
        total = [jnp.sum(x, axis=1, keepdims=True) for x in p]
        o = [mm(p[c], vsel[c], NN, dt) for c in each]
        if band:
            share = [jnp.exp(b - a) for a, b in zip(top, base)]
            total = [a + w for a, w in zip(total, share)]
            o = [o[c] + share[c] * ob_ref[0, 0, ts[c]] for c in each]
        for c in each:
            some = total[c] > 0.0
            safe = jnp.where(some, total[c], 1.0)
            o_ref[0, 0, ts[c]] = (o[c] / safe).astype(o_ref.dtype)
            lse = jnp.where(some, top[c] + jnp.log(safe), 0.0)
            stats = jnp.where(_lane_is(tokens, ts[c]), lse, stats)
        return stats

    lse_ref[0, 0, 0] = jax.lax.fori_loop(
        0, tokens // chains, body, jnp.zeros(lse_ref.shape[3:], jnp.float32))


def _bwd_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *rest,
                scale, band):
    """``dq`` a token, its ``dK``, ``dV`` rows added into the resident
    float32 blocks, ``_chains`` tokens a loop body as the forward; with
    ``band`` on top of the band pass's: its ``dq`` (``dqb_ref``, float32)
    a token, its ``dK``, ``dV`` (``dkb_ref``, ``dvb_ref``: in HBM, the
    buffers of ``dk_ref``, ``dv_ref``) fetched where a group begins."""
    if band:
        (dqb_ref, dkb_ref, dvb_ref, dq_ref, dk_ref, dv_ref,
         ksel, vsel, sem) = rest
    else:
        dq_ref, dk_ref, dv_ref, ksel, vsel = rest
    i, g = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        if band:
            copies = [pltpu.make_async_copy(src.at[i, g], dst.at[0, 0],
                                            sem.at[n])
                      for n, (src, dst) in enumerate(
                          ((dkb_ref, dk_ref), (dvb_ref, dv_ref)))]
            for copy in copies:
                copy.start()
            for copy in copies:
                copy.wait()
        else:
            dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
            dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    dt = q_ref.dtype
    tokens, chains = q_ref.shape[2], ksel.shape[0]
    lane = _iota((1, ksel.shape[1]), 1)
    stats = lse_ref[0, 0, 0]                               # [heads, tokens]
    each = range(chains)

    def body(step, carry):
        ts = [step * chains + c for c in each]
        for c in each:
            _gather(tab_ref, ts[c], ((k_ref, ksel.at[c]), (v_ref, vsel.at[c])))
        seen = [lane < tab_ref[0, 0, t, tab_ref.shape[3] - 1] for t in ts]
        q, do = ([ref[0, 0, t] for t in ts] for ref in (q_ref, do_ref))
        lse = [_at_lane(stats, t) for t in ts]
        delta = [jnp.sum(do[c].astype(jnp.float32)
                         * o_ref[0, 0, ts[c]].astype(jnp.float32), axis=1,
                         keepdims=True) for c in each]
        s = [scale * mm(q[c], ksel[c], NT, dt) for c in each]
        p = [jnp.where(seen[c], jnp.exp(jnp.where(seen[c], s[c] - lse[c],
                                                  0.0)), 0.0) for c in each]
        dp = [mm(do[c], vsel[c], NT, dt) for c in each]
        ds = [scale * p[c] * (dp[c] - delta[c]) for c in each]
        dq = [mm(ds[c], ksel[c], NN, dt) for c in each]
        for c in each:
            if band:
                dq[c] = dq[c] + dqb_ref[0, 0, ts[c]]
            dq_ref[0, 0, ts[c]] = dq[c].astype(dq_ref.dtype)
        # a token's dK, dV rows, two blocks (128 lanes of dS, P) a product,
        # added where they belong with no stop in scratch (the loop is bound
        # by its vector stores: PERF.md section 6); token after token: two
        # tokens of a body may name one block
        picks = tab_ref.shape[3] - 1
        for c in each:
            for first in range(0, picks, 2):
                blocks = min(2, picks - first)
                lanes = slice(first * BLOCK, (first + blocks) * BLOCK)
                dk = mm(ds[c][:, lanes], q[c], TN, dt)
                dv = mm(p[c][:, lanes], do[c], TN, dt)
                for j in range(blocks):
                    start = pl.multiple_of(
                        tab_ref[0, 0, ts[c], first + j] * BLOCK, BLOCK)
                    at = slice(j * BLOCK, (j + 1) * BLOCK)
                    dk_ref[0, 0, pl.ds(start, BLOCK), :] += dk[at]
                    dv_ref[0, 0, pl.ds(start, BLOCK), :] += dv[at]
        return carry

    jax.lax.fori_loop(0, tokens // chains, body, 0)


# the band pass --------------------------------------------------------------


def _band_walk(own, heads: int, band, visit):
    """The key tiles of query block ``own``'s band, in turn:
    ``visit(start, keys, limit)`` for the keys ``start .. start + keys``,
    of which row r (token r // heads of the block) sees the first
    ``limit[r]`` (all of them where ``limit`` is None). First the blocks
    before the window that the rule forces (where the window does not
    begin at 0), then the window's tiles of ``span`` blocks from its
    first block on: every tile but the last is whole, the last holds the
    tokens' own block (its keys after a token, and a clipped window's
    tiles past it, are no keys)."""
    init, window, span = band
    first = jnp.maximum(own - window + 1, 0)
    if init:
        @pl.when(first > 0)
        def _():
            visit(0, init * BLOCK, jnp.minimum(first, init) * BLOCK)

    def whole(i, carry):
        visit(pl.multiple_of((first + i * span) * BLOCK, BLOCK),
              span * BLOCK, None)
        return carry

    before = (own - first) // span          # whole tiles before the last
    if span < window:                       # else the window is one tile
        jax.lax.fori_loop(0, before, whole, 0)
    start = pl.multiple_of((first + before * span) * BLOCK, BLOCK)
    token = own * BLOCK + _iota((BLOCK * heads, 1), 0) // heads
    visit(start, span * BLOCK, token - start + 1)


def _mine(heads: int, tokens: int, first):
    """[64 * heads, tokens] bool: row r (token r // heads of a query
    block, head r % heads) against the lane of its token, the block's
    tokens standing at the lanes from ``first`` on."""
    shape = (BLOCK * heads, tokens)
    return first + _iota(shape, 0) // heads == _iota(shape, 1)


def _to_lanes(column, heads: int, tokens: int, first):
    """column [64 * heads, 1] (a row a token and head of a query block)
    -> [heads, tokens], zeros beside the block's tokens."""
    spread = jnp.where(_mine(heads, tokens, first), column, 0.0)
    return jnp.sum(spread.reshape(BLOCK, heads, tokens), axis=0)


def _to_rows(stats, heads: int, first):
    """``_to_lanes``' inverse: [heads, tokens] -> [64 * heads, 1]."""
    tokens = stats.shape[1]
    tiled = jnp.broadcast_to(stats[None], (BLOCK, heads, tokens)).reshape(
        BLOCK * heads, tokens)
    return jnp.sum(jnp.where(_mine(heads, tokens, first), tiled, 0.0), axis=1,
                   keepdims=True)


def _band_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, scale, heads, band):
    """Flash attention of a grid step's query blocks (64 tokens x
    ``heads`` rows each) over their bands: a partial result (float32,
    normalised) and its log-sum-exp a token and head."""
    dt = q_ref.dtype
    rows = BLOCK * heads
    tokens = q_ref.shape[2] // heads
    before = pl.program_id(2) * (tokens // BLOCK)       # query blocks

    def block(j, stats):
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def visit(start, keys, limit):
            s = scale * mm(q_ref[0, 0, at, :],
                           k_ref[0, 0, pl.ds(start, keys), :], NT, dt)
            if limit is not None:
                s = jnp.where(_iota((1, keys), 1) < limit, s, _NEG)
            was = m_scr[...]
            top = jnp.maximum(was, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - top)         # a visit's rows each see a key
            fade = jnp.exp(was - top)
            l_scr[...] = fade * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[...] = fade * acc_scr[...] + mm(
                p, v_ref[0, 0, pl.ds(start, keys), :], NN, dt)
            m_scr[...] = top

        _band_walk(before + j, heads, band, visit)
        o_ref[0, 0, at, :] = acc_scr[...] / l_scr[...]
        return stats + _to_lanes(m_scr[...] + jnp.log(l_scr[...]), heads,
                                 tokens, j * BLOCK)

    lse_ref[0, 0, 0] = jax.lax.fori_loop(
        0, tokens // BLOCK, block, jnp.zeros((heads, tokens), jnp.float32))


def _band_bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref,
                     dk_ref, dv_ref, dq_scr, *, scale, heads, band):
    """The band's share of the gradients from the merged log-sum-exp: a
    tile's scores and ``dP`` once, ``dq`` (float32) a query block, ``dK``
    and ``dV`` added into the resident float32 blocks."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    dt = q_ref.dtype
    rows = BLOCK * heads
    tokens = q_ref.shape[2] // heads
    before = pl.program_id(2) * (tokens // BLOCK)       # query blocks

    def block(j, carry):
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        lse = _to_rows(lse_ref[0, 0, 0], heads, j * BLOCK)
        delta = jnp.sum(do_ref[0, 0, at, :].astype(jnp.float32)
                        * o_ref[0, 0, at, :].astype(jnp.float32), axis=1,
                        keepdims=True)
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

        def visit(start, keys, limit):
            q, do = q_ref[0, 0, at, :], do_ref[0, 0, at, :]
            k = k_ref[0, 0, pl.ds(start, keys), :]
            s = scale * mm(q, k, NT, dt) - lse
            if limit is not None:
                s = jnp.where(_iota((1, keys), 1) < limit, s, _NEG)
            p = jnp.exp(s)
            ds = scale * p * (mm(
                do, v_ref[0, 0, pl.ds(start, keys), :], NT, dt) - delta)
            dq_scr[...] += mm(ds, k, NN, dt)
            dk_ref[0, 0, pl.ds(start, keys), :] += mm(ds, q, TN, dt)
            dv_ref[0, 0, pl.ds(start, keys), :] += mm(p, do, TN, dt)

        _band_walk(before + j, heads, band, visit)
        dq_ref[0, 0, at, :] = dq_scr[...]
        return carry

    jax.lax.fori_loop(0, tokens // BLOCK, block, 0)


# the launchers --------------------------------------------------------------


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


def _rows_spec(n, hg, d):
    """A token tile of a heads-major array seen as rows [B, G, S hg, d]
    (row (t, h) of a group: the same bytes)."""
    return pl.BlockSpec((1, 1, n * hg, d), lambda i, g, t: (i, g, t, 0))


def _as_rows(x):
    b, g, s, hg, d = x.shape
    return x.reshape(b, g, s * hg, d)


def _span(window: int, keys: int) -> int:
    """Blocks a key tile of ``keys`` keys of the band holds: they tile
    the window."""
    return math.gcd(window, keys // BLOCK)


def _chains(picks: int) -> int:
    """Tokens a body of the per-token loop holds."""
    return 2 if 2 * picks <= _BODY_BLOCKS else 1


def _by_group(x, g):
    """[B, S, H, d] -> [B, G, S, H / G, d]."""
    b, s, h, d = x.shape
    return x.reshape(b, s, g, h // g, d).transpose(0, 2, 1, 3, 4)


def _by_token(x):
    """``_by_group``'s inverse."""
    b, g, s, hg, d = x.shape
    return x.transpose(0, 2, 1, 3, 4).reshape(b, s, g * hg, d)


@functools.partial(jax.jit, static_argnames=("band", "interpret"))
def _sparse_fwd(q, k, v, ids, band, interpret: bool):
    """-> (o [B, G, S, hg, d], lse [B, G, S / n, hg, n]) from q as
    ``_by_group`` lays it out, k, v [B, G, S, d] and the canonical
    table: of every block, or with ``band`` = (init blocks, window
    blocks) of the free ones, after the band pass. Jitted so that a
    model's layers share one trace."""
    b, g, s, hg, d = q.shape
    n, picks = _tokens(s), ids.shape[3] - 1
    tab, tile, whole, stats = _specs(s, n, hg, d, picks)
    held = (_chains(picks), picks * BLOCK, d)      # a body's blocks, gathered
    scale, before, specs = d ** -0.5, (), ()
    if band is not None:
        rows = _rows_spec(n, hg, d)
        partial, lse = pl.pallas_call(
            functools.partial(_band_fwd_kernel, scale=scale, heads=hg,
                              band=band + (_span(band[1], _BAND_KEYS[0]),)),
            grid=(b, g, s // n),
            in_specs=[rows, whole, whole],
            out_specs=[rows, stats],
            out_shape=[jax.ShapeDtypeStruct((b, g, s * hg, d), jnp.float32),
                       jax.ShapeDtypeStruct((b, g, s // n, hg, n),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((BLOCK * hg, 1), jnp.float32),
                            pltpu.VMEM((BLOCK * hg, 1), jnp.float32),
                            pltpu.VMEM((BLOCK * hg, d), jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name=BAND_KERNELS[0],
        )(_as_rows(q), k, v)
        before, specs = (partial.reshape(q.shape), lse), (tile, stats)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, band=band is not None),
        grid=(b, g, s // n),
        in_specs=[tab, tile, whole, whole, *specs],
        out_specs=[tile, stats],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, g, s // n, hg, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(held, k.dtype), pltpu.VMEM(held, v.dtype)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[0],
    )(ids, q, k, v, *before)


@functools.partial(jax.jit, static_argnames=("band", "interpret"))
def _sparse_bwd(q, k, v, ids, o, lse, do, band, interpret: bool):
    """-> (dq [B, G, S, hg, d], dk, dv [B, G, S, d] float32)."""
    b, g, s, hg, d = q.shape
    n, picks = _tokens(s), ids.shape[3] - 1
    tab, tile, whole, stats = _specs(s, n, hg, d, picks)
    held = (_chains(picks), picks * BLOCK, d)
    scale, before, specs, more, aliases = d ** -0.5, (), (), (), {}
    grads = [jax.ShapeDtypeStruct(k.shape, jnp.float32),
             jax.ShapeDtypeStruct(v.shape, jnp.float32)]
    if band is not None:
        rows = _rows_spec(n, hg, d)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_band_bwd_kernel, scale=scale, heads=hg,
                              band=band + (_span(band[1], _BAND_KEYS[1]),)),
            grid=(b, g, s // n),
            in_specs=[rows, whole, whole, rows, stats, rows],
            out_specs=[rows, whole, whole],
            out_shape=[jax.ShapeDtypeStruct((b, g, s * hg, d), jnp.float32),
                       *grads],
            scratch_shapes=[pltpu.VMEM((BLOCK * hg, d), jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
            name=BAND_KERNELS[1],
        )(_as_rows(q), k, v, _as_rows(o), lse, _as_rows(do))
        anywhere = pl.BlockSpec(memory_space=pl.ANY)
        before, specs = (dq.reshape(q.shape), dk, dv), (tile, anywhere,
                                                         anywhere)
        more, aliases = (pltpu.SemaphoreType.DMA((2,)),), {8: 1, 9: 2}
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, band=band is not None),
        grid=(b, g, s // n),
        in_specs=[tab, tile, whole, whole, tile, stats, tile, *specs],
        out_specs=[tile, whole, whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), *grads],
        scratch_shapes=[pltpu.VMEM(held, k.dtype), pltpu.VMEM(held, v.dtype),
                        *more],
        input_output_aliases=aliases,
        compiler_params=_PARAMS,
        interpret=interpret,
        name=KERNELS[1],
    )(ids, q, k, v, o, lse, do, *before)


def block_sparse_attention(q, k, v, table, interpret: Optional[bool] = None,
                           init_blocks: Optional[int] = None,
                           window_blocks: Optional[int] = None):
    """The attention of the module docstring: q [B, S, H, d], k, v [B, S,
    G, d], table [B, G, S, K] int -> o [B, S, H, d]. ``init_blocks`` and
    ``window_blocks``: the rule that made the table, where one did (the
    module docstring's contract); they engage the band pass where
    ``band_engages`` says the shapes allow it."""
    band = None
    if band_engages(q.shape[1], table.shape[-1], init_blocks, window_blocks):
        band = (init_blocks, window_blocks)
    return _attend(q, k, v, table, interpret, band)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend(q, k, v, table, interpret, band):
    return _attn_fwd(q, k, v, table, interpret, band)[0]


def _attn_fwd(q, k, v, table, interpret, band):
    b, s, h, d = q.shape
    g = k.shape[2]
    _tokens(s)                # a ValueError where S is no multiple of a block
    if h % g or table.shape[:3] != (b, g, s):
        raise ValueError(f"table {table.shape} for q {q.shape}, k {k.shape}")
    if interpret is None:
        interpret = interpret_default()
    ids = _canonical(table, s, band)
    q5, k4, v4 = _by_group(q, g), k.transpose(0, 2, 1, 3), \
        v.transpose(0, 2, 1, 3)
    o5, lse = _sparse_fwd(q5, k4, v4, ids, band, interpret)
    return _by_token(o5), (q5, k4, v4, ids, o5, lse)


def _attn_bwd(interpret, band, res, do):
    q5, k4, v4, ids, o5, lse = res
    if interpret is None:
        interpret = interpret_default()
    dq5, dk4, dv4 = _sparse_bwd(q5, k4, v4, ids, o5, lse,
                                _by_group(do, k4.shape[1]), band, interpret)
    width = ids.shape[3] - 1 + (sum(band) if band else 0)
    return (_by_token(dq5), dk4.transpose(0, 2, 1, 3).astype(k4.dtype),
            dv4.transpose(0, 2, 1, 3).astype(v4.dtype),
            np.zeros(ids.shape[:3] + (width,), jax.dtypes.float0))


_attend.defvjp(_attn_fwd, _attn_bwd)

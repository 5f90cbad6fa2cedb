"""Paged (block) KV cache for serving-grade decode.

TPU-native counterpart of the reference's paged-attention serving
stack (ref: python/paddle/incubate/nn/functional/
block_multihead_attention.py — key/value caches laid out as
[max_block_num, num_head, block_size, head_size] pools indexed by
per-sequence block tables; kernels in
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel).

Design:
- ``k_pool``/``v_pool`` are [kv_heads, num_blocks, block_size, head_dim]
  pools per layer (the TPU paged-attention kernel's native layout);
  ``block_tables`` is a [batch, max_blocks_per_seq] int32 map from a
  sequence's logical block to a physical pool slot (shared by all
  layers — each layer has its own pools but the layout is identical).
  All shapes are static, so the decode step stays one cached XLA
  program.
- Writes scatter the new tokens to (table[pos//bs], pos%bs) with
  ``Array.at[...].set`` — a static-shape scatter XLA fuses into the
  step. Prefill reads gather the table back into a [batch, max_len]
  view and run the same masked attention as the dense path, making
  paged attention token-for-token identical to the dense cache by
  construction. Single-token DECODE instead runs the Pallas paged-
  attention kernel (jax.experimental.pallas.ops.tpu.paged_attention —
  scalar-prefetched block tables steer the block DMAs, no padded-view
  materialization), with the gather path as the non-TPU fallback.
- ``BlockManager`` is the host-side allocator (free list, per-sequence
  allocation/free) for serving loops where sequences join and leave the
  batch; ``contiguous_tables`` is the trivial layout ``generate`` uses.

The memory win over the dense [B, max_len, ...] cache: the pool is
sized by blocks actually needed (sum of ceil(len/bs)), not
B * max_len, and freed sequences return blocks to the pool.

Int8 KV quantization (``kv_dtype="int8"``): pools store int8 values
plus PER-BLOCK SCALE POOLS [kv_heads, num_blocks, block_size] holding
one absmax scale per cached token per head — halving KV bytes (the
decode roofline at serving batch sizes is KV-bandwidth bound, so bytes
are throughput). Scales live in pool rows indexed by the SAME physical
block ids as the values, so BlockManager ``fork``/``adopt`` and the
PrefixCache carry them with the block for free — COW and prefix reuse
work unchanged. Writes quantize in the same scatter (amax over
head_dim per new token: a single per-block scale would force a
read-modify-write requantization of the whole block every time a new
token raised its amax — per-entry scales keep the write an O(s)
scatter); reads dequantize in-register: the TPU Pallas decode kernel
takes ``QuantizedTensor`` pages natively, and the gather/prefill path
multiplies scales back after the gather. The quantization convention
(q = rint(x * 127.5 / amax), dequant = q * amax / 127.5) matches
jax.experimental.pallas.ops.tpu.paged_attention.quantization_utils so
both paths decode the same bytes identically.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PagedLayerCache", "BlockManager", "BlockImportError", "PrefixCache",
    "contiguous_tables", "alloc_paged_kv_caches", "paged_update_kv_cache",
    "paged_gather_kv", "paged_write_kv", "paged_decode_attention",
]


class BlockImportError(RuntimeError):
    """A KV-block import could not be placed RIGHT NOW (destination
    pool too full / no free slot). Classified TRANSIENT by the disagg
    handoff's retry policy: decode drains free blocks continuously, so
    the correct reaction is backoff-and-retry under the request's
    deadline, not failure."""


class PagedLayerCache(NamedTuple):
    """One layer's paged cache: pools + the (shared) block table.

    ``contiguous`` (a STATIC python bool, not traced) records that the
    table is the identity layout (sequence b owns blocks
    [b*n, (b+1)*n)) — generate()'s case — unlocking the reshape-view
    attention path that skips both the fancy-index gather and the
    Pallas kernel's per-page DMAs.

    ``k_scale``/``v_scale`` (None for float pools) are the int8-KV
    per-block scale pools [kv_heads, num_blocks, block_size]: one
    absmax per cached token per head, row-indexed by the same physical
    block ids as the value pools."""

    k_pool: object  # Tensor [kv_heads, num_blocks, block_size, head_dim]
    v_pool: object
    block_tables: object  # Tensor [batch, max_blocks_per_seq] int32
    contiguous: bool = False
    k_scale: object = None  # Tensor [kv_heads, num_blocks, block_size]
    v_scale: object = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def contiguous_tables(batch: int, max_len: int, block_size: int) -> np.ndarray:
    """Dense layout: sequence b owns blocks [b*n, (b+1)*n)."""
    per_seq = -(-max_len // block_size)
    return (
        np.arange(batch * per_seq, dtype=np.int32).reshape(batch, per_seq)
    )


class BlockManager:
    """Host-side free-list allocator for serving (ref: the block table
    management inside the reference's AppendAttention/BlockMHA serving
    path — here a small Python object, since the single-controller
    runtime owns the whole batch).

    Blocks are REF-COUNTED so a physical block can back several logical
    owners at once (vLLM/SGLang-style prefix sharing): a sequence that
    ``adopt``\\s a cached prefix block and the :class:`PrefixCache` that
    pinned it each hold one reference; the block returns to the free
    list only when the LAST reference drops. A shared block is
    read-only by contract — an owner that must write into one calls
    :meth:`fork` first (copy-on-write: the owner gets a private block,
    the other readers keep the original untouched). Every physical
    block counts ONCE in occupancy no matter how many owners share it:
    ``free_blocks`` is physical, and ``can_allocate`` counts a
    sequence's adopted (shared) blocks as already owned."""

    def __init__(self, num_blocks: int, block_size: int):
        self.block_size = block_size
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))
        self._owned: dict = {}
        self._refs: Dict[int, int] = {}  # physical block -> live refs

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        """Live references on a physical block (0 = on the free list)."""
        return self._refs.get(int(block), 0)

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` positions (ceil)."""
        return -(-int(num_tokens) // self.block_size)

    def can_allocate(self, seq_id, num_tokens: int) -> bool:
        """Admission probe: would ``allocate(seq_id, num_tokens)``
        succeed right now? (Counts blocks the sequence already owns —
        adopted shared blocks included, each exactly once — the serving
        engine's block-availability admission test, checked WITHOUT
        mutating the free list.)"""
        owned = len(self._owned.get(seq_id, []))
        return self.blocks_for(num_tokens) - owned <= len(self._free)

    def allocate(self, seq_id, num_tokens: int) -> List[int]:
        """Ensure seq_id owns enough blocks for num_tokens; returns the
        full block list (adopted shared blocks first, in logical
        order — only the shortfall beyond them is newly allocated)."""
        owned = self._owned.setdefault(seq_id, [])
        need = -(-num_tokens // self.block_size) - len(owned)
        if need > len(self._free):
            raise RuntimeError(
                f"paged KV cache exhausted: need {need} blocks, "
                f"{len(self._free)} free (of {self.num_blocks})"
            )
        for _ in range(max(need, 0)):
            b = self._free.pop()
            self._refs[b] = 1
            owned.append(b)
        return list(owned)

    def adopt(self, seq_id, blocks: List[int]) -> None:
        """Append SHARED blocks to ``seq_id``'s logical block list (the
        prefix-cache hit path): each gains one reference; nothing is
        taken from the free list. Must run before :meth:`allocate` so
        the adopted prefix keeps logical positions 0..len(blocks)-1."""
        owned = self._owned.setdefault(seq_id, [])
        for b in blocks:
            b = int(b)
            if self._refs.get(b, 0) <= 0:
                raise RuntimeError(
                    f"adopt of dead block {b}: it has no live reference "
                    "(was it evicted between lookup and adopt?)")
            self._refs[b] += 1
            owned.append(b)

    def fork(self, seq_id, logical_index: int) -> Tuple[int, int]:
        """Copy-on-write: make ``seq_id``'s ``logical_index``-th block
        PRIVATE before a write. Returns ``(old, new)`` physical ids —
        equal when the block was already private (sole reference).
        Otherwise one free block is consumed, the sequence's reference
        moves onto it, and the caller must copy the pool contents
        ``old -> new`` before writing (readers of ``old`` — the cache,
        other sequences — keep their bytes untouched)."""
        owned = self._owned[seq_id]
        old = owned[logical_index]
        if self._refs.get(old, 0) <= 1:
            return old, old
        if not self._free:
            raise RuntimeError(
                "paged KV cache exhausted: no free block for a "
                "copy-on-write fork")
        new = self._free.pop()
        self._refs[new] = 1
        self._refs[old] -= 1
        owned[logical_index] = new
        return old, new

    def ref(self, block: int) -> None:
        """Take an extra reference on a live block (the PrefixCache's
        pin). Never resurrects a freed block."""
        b = int(block)
        if self._refs.get(b, 0) <= 0:
            raise RuntimeError(f"ref of dead block {b}")
        self._refs[b] += 1

    def release(self, block: int) -> bool:
        """Drop one reference; returns True when the block actually hit
        the free list (last reference gone). A live-referenced block is
        NEVER recycled."""
        b = int(block)
        refs = self._refs.get(b, 0)
        if refs <= 0:
            raise RuntimeError(f"release of dead block {b}")
        if refs == 1:
            del self._refs[b]
            self._free.append(b)
            return True
        self._refs[b] = refs - 1
        return False

    def free_sequence(self, seq_id) -> None:
        for b in self._owned.pop(seq_id, []):
            self.release(b)

    def owned_blocks(self, seq_id) -> List[int]:
        """The sequence's current logical block list (post-fork ids)."""
        return list(self._owned.get(seq_id, []))

    def accounting(self) -> dict:
        """Conservation snapshot for the leak sanitizer (graft-own):
        ``{"total", "free", "refs": {block: live refs},
        "owned": {seq_id: [blocks]}}``. The pool invariant is
        ``free + len(refs) == total`` — every physical block is either
        on the free list or live-referenced, never both, never
        neither."""
        return {
            "total": int(self.num_blocks),
            "free": len(self._free),
            "refs": {int(b): int(c) for b, c in self._refs.items()},
            "owned": {k: [int(b) for b in v]
                      for k, v in self._owned.items()},
        }

    def table_row(self, seq_id, max_blocks_per_seq: int,
                  fill: int = 0) -> np.ndarray:
        """The sequence's block-table row, padded with ``fill`` (the
        serving engine passes its trash block id so unused table slots
        scatter into the sacrificial page)."""
        row = np.full((max_blocks_per_seq,), fill, np.int32)
        owned = self._owned.get(seq_id, [])
        row[: len(owned)] = owned
        return row

    # -- KV-block export/import (disaggregated prefill/decode) ----------
    def export_blocks(self, seq_id, pools,
                      num_tokens: Optional[int] = None):
        """Gather ``seq_id``'s KV blocks out of the pools into host
        arrays for a cross-engine handoff. ``pools`` is the engine's
        per-layer pool list — ``(k, v)`` tuples of
        [kvh, blocks, bs, D] arrays, or ``(k, v, k_scale, v_scale)``
        for int8 pools (scale rows ride along: the per-block scales are
        indexed by the SAME physical ids, so a quantized block's bytes
        and its dequant scales travel together).

        Returns ``(pages, scales, meta)``: ``pages`` is
        [layers, 2, kvh, n, bs, D] (k then v), ``scales`` is
        [layers, 2, kvh, n, bs] or None, ``meta`` describes the frame.
        ``num_tokens`` limits the export to the blocks actually holding
        KV (a prefill-role engine allocates no decode-growth blocks,
        but a prefix-cache tail may over-own).

        READ-ONLY by construction: adopted/COW-shared blocks are
        gathered without touching refcounts — other readers (the
        prefix cache, sibling sequences) keep their blocks."""
        owned = self._owned.get(seq_id)
        if not owned:
            raise KeyError(f"export_blocks: unknown sequence {seq_id!r}")
        n = len(owned)
        if num_tokens is not None:
            n = min(self.blocks_for(num_tokens), n)
        idx = np.asarray(owned[:n], np.int64)
        # gather ON DEVICE first: asarray of the full pool would copy
        # the whole [kvh, num_blocks, bs, D] array to host per layer
        # per k/v just to keep a few exported rows
        pages = np.stack([
            np.stack([np.asarray(entry[0][:, idx]),
                      np.asarray(entry[1][:, idx])])
            for entry in pools])
        scales = None
        if len(pools[0]) >= 4:
            scales = np.stack([
                np.stack([np.asarray(entry[2][:, idx]),
                          np.asarray(entry[3][:, idx])])
                for entry in pools])
        meta = {
            "num_blocks": int(n),
            "block_size": int(self.block_size),
            "layers": int(pages.shape[0]),
            "dtype": str(pages.dtype),
            "quantized": scales is not None,
        }
        return pages, scales, meta

    def import_blocks(self, seq_id, pages, scales, meta, pools):
        """Inverse of :meth:`export_blocks`: allocate fresh PRIVATE
        blocks for ``seq_id`` (physical ids need not — and generally do
        not — match the exporter's) and write the exported rows into
        this engine's pools. Returns ``(new_pools, blocks)``.

        Raises :class:`BlockImportError` (transient — retry under the
        request's deadline) when the destination pool is too full;
        config mismatches (block size, layer count, quantization) are
        ValueError — no retry can fix those. On ANY failure nothing is
        left allocated."""
        n = int(meta["num_blocks"])
        if int(meta["block_size"]) != self.block_size:
            raise ValueError(
                f"import_blocks: exporter block_size "
                f"{meta['block_size']} != local {self.block_size}")
        if int(meta["layers"]) != len(pools):
            raise ValueError(
                f"import_blocks: exporter has {meta['layers']} layers, "
                f"local pools {len(pools)}")
        if bool(meta.get("quantized")) != (len(pools[0]) >= 4):
            raise ValueError(
                "import_blocks: quantized/float pool mismatch between "
                "exporter and importer")
        if self._owned.get(seq_id):
            raise ValueError(
                f"import_blocks: sequence {seq_id!r} already owns blocks")
        if n > self.num_blocks:
            raise ValueError(  # permanent: can never fit in this pool
                f"import_blocks: {n} blocks exceed the pool's total "
                f"size {self.num_blocks}")
        if n > len(self._free):
            raise BlockImportError(
                f"paged KV pool too full to import {n} blocks "
                f"({len(self._free)} free of {self.num_blocks})")
        blocks = self.allocate(seq_id, n * self.block_size)
        idx = jnp.asarray(blocks, jnp.int32)
        new_pools = []
        for li, entry in enumerate(pools):
            k = entry[0].at[:, idx].set(
                jnp.asarray(pages[li, 0], entry[0].dtype))
            v = entry[1].at[:, idx].set(
                jnp.asarray(pages[li, 1], entry[1].dtype))
            if len(entry) >= 4:
                ks = entry[2].at[:, idx].set(
                    jnp.asarray(scales[li, 0], entry[2].dtype))
                vs = entry[3].at[:, idx].set(
                    jnp.asarray(scales[li, 1], entry[3].dtype))
                new_pools.append((k, v, ks, vs))
            else:
                new_pools.append((k, v))
        return new_pools, blocks


class _PrefixNode:
    __slots__ = ("children", "block", "stamp", "parent", "key")

    def __init__(self, parent=None, key=None, block: Optional[int] = None):
        self.children: Dict[tuple, "_PrefixNode"] = {}
        self.block = block  # physical block id (None in matcher mode)
        self.stamp = 0  # LRU clock value of the last touch
        self.parent = parent
        self.key = key


class PrefixCache:
    """Radix-style prefix index over prompt tokens at BLOCK granularity
    (SGLang's RadixAttention idea collapsed onto the paged layout: the
    natural reuse unit is one KV block, so the tree's edge label is one
    block's worth of token ids).

    Two modes:

    - **manager mode** (``manager=`` a :class:`BlockManager`): each node
      pins one physical block holding that chunk's KV — the cache takes
      its own reference via ``manager.ref`` so finished sequences'
      prefix blocks survive ``free_sequence`` and later identical
      prefixes adopt them instead of re-prefilling. ``evict`` walks
      leaves in LRU order releasing pins when the pool runs dry.
    - **matcher mode** (``manager=None``): no blocks, just the trie —
      the cluster router uses this to estimate how much of a prompt's
      prefix a replica already holds, bounded by ``max_nodes``.

    Only FULL blocks enter the tree (a partial tail block keeps
    receiving decode writes, so sharing it would alias live state).

    **Namespaces**: ``lookup``/``insert`` accept an optional ``ns`` key
    selecting an independent tree root (``None`` = the default root).
    The serving engine keys namespaces by tenant so one tenant's prompts
    never match another's, while a designated shared namespace holds
    common system prompts whose physical blocks are pinned from several
    namespaces at once (ref-counted COW sharing: a cross-tenant adopter
    forks before writing, exactly like any other prefix hit). LRU state
    (clock, leaf registry, eviction) is global across namespaces — a
    cold tenant's tree shrinks first regardless of where pressure
    originated.
    """

    def __init__(self, block_size: int, manager: Optional[BlockManager]
                 = None, max_nodes: Optional[int] = None):
        self.block_size = int(block_size)
        self.manager = manager
        self.max_nodes = max_nodes
        self.root = _PrefixNode()
        self._ns_roots: Dict[str, _PrefixNode] = {}
        self._clock = 0
        self._nodes = 0
        # incremental leaf registry (id(node) -> node): eviction picks
        # LRU leaves constantly on the router's hot path, so a full
        #-tree DFS per dropped node would be O(nodes) each time
        self._leaf_reg: Dict[int, _PrefixNode] = {}
        self.hits = 0
        self.lookups = 0
        self.hit_tokens = 0
        self.evicted_blocks = 0

    def _chunks(self, tokens) -> List[tuple]:
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        bs = self.block_size
        n_full = len(toks) // bs
        return [tuple(toks[i * bs:(i + 1) * bs]) for i in range(n_full)]

    def _touch(self, node: _PrefixNode) -> None:
        self._clock += 1
        node.stamp = self._clock

    def _root_for(self, ns) -> _PrefixNode:
        if ns is None:
            return self.root
        root = self._ns_roots.get(ns)
        if root is None:
            root = self._ns_roots[ns] = _PrefixNode()
        return root

    def lookup(self, tokens, ns=None) -> Tuple[int, List[int]]:
        """Longest cached prefix of ``tokens``: returns
        ``(n_tokens, blocks)`` where ``n_tokens`` is a multiple of
        ``block_size`` and ``blocks`` the pinned physical blocks in
        logical order (empty in matcher mode). Touches the matched path
        for LRU. ``ns`` selects a namespace tree (None = default)."""
        self.lookups += 1
        node, blocks, n = self._root_for(ns), [], 0
        for key in self._chunks(tokens):
            child = node.children.get(key)
            if child is None:
                break
            self._touch(child)
            if child.block is not None:
                blocks.append(child.block)
            n += self.block_size
            node = child
        if n:
            self.hits += 1
            self.hit_tokens += n
        return n, blocks

    def insert(self, tokens, blocks: Optional[List[int]] = None,
               ns=None) -> int:
        """Register ``tokens``' full blocks. Idempotent: existing nodes
        are kept (their pinned block stays authoritative); each NEW node
        pins its block (manager mode). Returns the number of new nodes.
        ``blocks`` must cover every full chunk in manager mode. ``ns``
        selects a namespace tree (None = default); inserting the same
        physical blocks under two namespaces double-pins them, which is
        exactly the COW-sharing contract for common system prompts."""
        chunks = self._chunks(tokens)
        if self.manager is not None:
            if blocks is None or len(blocks) < len(chunks):
                raise ValueError(
                    f"insert needs one block per full chunk: "
                    f"{len(chunks)} chunks, "
                    f"{0 if blocks is None else len(blocks)} blocks")
        node, created = self._root_for(ns), 0
        for i, key in enumerate(chunks):
            child = node.children.get(key)
            if child is None:
                block = None
                if self.manager is not None:
                    block = int(blocks[i])
                    self.manager.ref(block)
                child = _PrefixNode(parent=node, key=key, block=block)
                node.children[key] = child
                self._nodes += 1
                created += 1
                self._leaf_reg.pop(id(node), None)  # node grew a child
                self._leaf_reg[id(child)] = child
            self._touch(child)
            node = child
        if self.max_nodes is not None:
            self._evict_nodes(self._nodes - self.max_nodes)
        return created

    # -- eviction --------------------------------------------------------
    def _leaves(self) -> List[_PrefixNode]:
        return list(self._leaf_reg.values())

    def _drop_leaf(self, leaf: _PrefixNode) -> bool:
        """Remove one leaf; returns True when its block actually became
        free (last reference was the cache's pin)."""
        freed = False
        if leaf.block is not None and self.manager is not None:
            freed = self.manager.release(leaf.block)
            if freed:
                self.evicted_blocks += 1
        del leaf.parent.children[leaf.key]
        self._nodes -= 1
        self._leaf_reg.pop(id(leaf), None)
        parent = leaf.parent
        # namespace roots (key is None) never enter the leaf registry
        if parent.key is not None and not parent.children:
            self._leaf_reg[id(parent)] = parent
        return freed

    def _evict_nodes(self, n: int) -> None:
        while n > 0 and self._nodes > 0:
            leaf = min(self._leaves(), key=lambda x: x.stamp)
            self._drop_leaf(leaf)
            n -= 1

    def evict(self, need_blocks: int) -> int:
        """Release LRU leaves until ``need_blocks`` physical blocks hit
        the free list, dropping ONLY leaves whose pin is the last
        reference (those free a block NOW). Leaves shared with a live
        sequence are left cached — unpinning them frees nothing today
        and would wipe the hot working set on one transient
        unsatisfiable admission. Returns blocks actually freed (may be
        short of ``need_blocks`` when nothing more is freeable)."""
        freed = 0
        while freed < need_blocks and self._nodes > 0:
            sole = [lf for lf in self._leaves()
                    if lf.block is not None
                    and self.manager.refcount(lf.block) == 1]
            if not sole:
                break
            if self._drop_leaf(min(sole, key=lambda x: x.stamp)):
                freed += 1
        return freed

    def clear(self) -> None:
        while self._nodes > 0:
            self._drop_leaf(min(self._leaves(), key=lambda x: x.stamp))

    @property
    def nodes(self) -> int:
        return self._nodes

    def stats(self) -> dict:
        return {
            "nodes": self._nodes,
            "namespaces": 1 + len(self._ns_roots),
            "lookups": self.lookups,
            "hits": self.hits,
            "hit_tokens": self.hit_tokens,
            "evicted_blocks": self.evicted_blocks,
        }


def alloc_paged_kv_caches(
    num_layers: int, batch: int, max_len: int, num_kv_heads: int,
    head_dim: int, dtype, block_size: int = 64,
    num_blocks: Optional[int] = None,
    tables: Optional[np.ndarray] = None,
    kv_dtype: Optional[str] = None,
) -> List[PagedLayerCache]:
    """Per-layer paged caches with a shared block table.

    ``kv_dtype="int8"`` allocates int8 value pools plus per-block f32
    scale pools (see module docstring); ``dtype`` then only sets the
    COMPUTE dtype reads dequantize into."""
    from ..base.tensor import Tensor

    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
    per_seq = -(-max_len // block_size)
    if tables is None:
        tables = contiguous_tables(batch, max_len, block_size)
    is_contig = bool(
        tables.shape == (batch, per_seq)
        and np.array_equal(
            np.asarray(tables), contiguous_tables(batch, max_len, block_size)
        )
    )
    if num_blocks is None:
        num_blocks = int(tables.max()) + 1
    tables_t = Tensor(jnp.asarray(tables, jnp.int32), _internal=True)
    pool_dt = jnp.int8 if kv_dtype == "int8" else dtype
    caches = []
    for _ in range(num_layers):
        k = Tensor(
            jnp.zeros((num_kv_heads, num_blocks, block_size, head_dim),
                      pool_dt),
            _internal=True,
        )
        v = Tensor(
            jnp.zeros((num_kv_heads, num_blocks, block_size, head_dim),
                      pool_dt),
            _internal=True,
        )
        if kv_dtype == "int8":
            ks = Tensor(jnp.zeros((num_kv_heads, num_blocks, block_size),
                                  jnp.float32), _internal=True)
            vs = Tensor(jnp.zeros((num_kv_heads, num_blocks, block_size),
                                  jnp.float32), _internal=True)
            caches.append(
                PagedLayerCache(k, v, tables_t, is_contig, ks, vs))
        else:
            caches.append(PagedLayerCache(k, v, tables_t, is_contig))
    return caches


# int8 KV convention — MUST match the Pallas paged-attention kernel's
# quantization_utils (MAX_INT8 = 127.5; dequant = q * amax / 127.5) so
# the kernel's in-register dequant and the gather fallback agree
# bit-for-bit on the same pool bytes. The clip keeps the amax element
# itself from rounding to +128 and wrapping in int8.
_KV_QMAX = 127.5


def _kv_quantize(x):
    """[B, s, kvh, D] float -> (int8 values, per-token amax [B, s, kvh])."""
    h = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    h = jnp.maximum(h, 1e-8)
    q = jnp.clip(jnp.rint(x.astype(jnp.float32) * (_KV_QMAX / h[..., None])),
                 -127, 127).astype(jnp.int8)
    return q, h


def _kv_dequantize(q, h, dtype):
    """Invert :func:`_kv_quantize`: ``h`` broadcasts over head_dim."""
    return (q.astype(jnp.float32) * (h[..., None] / _KV_QMAX)).astype(dtype)


def _validate_cache_len(cl, b: int):
    """Single source of truth for the scalar-or-[B] cache_len contract."""
    cl = jnp.asarray(cl)
    if cl.ndim not in (0, 1) or (cl.ndim == 1 and cl.shape != (b,)):
        raise ValueError(
            f"cache_len must be a scalar or [batch]={b} array, got "
            f"shape {cl.shape}"
        )
    return cl


def _per_seq_positions(cl, b: int, s: int):
    """[B, s] write positions from a scalar or per-sequence [B] start.
    Ragged serving batches (BlockManager's whole point) pass [B]."""
    cl = _validate_cache_len(cl, b)
    if cl.ndim == 0:
        return jnp.broadcast_to(cl + jnp.arange(s), (b, s))
    return cl[:, None] + jnp.arange(s)[None, :]


def _write_positions(tables, cl, b: int, s: int, bs: int, pool_rows: int):
    """(phys, off) [B, s] scatter targets with OOB lanes routed past
    the pool. Padded lanes can run PAST the table row (a fixed-width
    prefill starting at a nonzero offset — the prefix-cache hit path —
    or a chunk tail near max_len). take_along_axis would CLAMP them
    onto the row's last entry, aliasing the garbage onto a real block's
    early offsets; route them to an out-of-range pool row instead so
    the scatter DROPS them (jax .at[].set drops OOB updates)."""
    positions = _per_seq_positions(cl, b, s)  # [B, s]
    logical = positions // bs  # [B, s]
    off = positions % bs  # [B, s]
    nbt = tables.shape[1]
    phys = jnp.take_along_axis(
        tables, jnp.minimum(logical, nbt - 1), axis=1)  # [B, s]
    phys = jnp.where(logical < nbt, phys, pool_rows)
    return phys, off


def paged_write_kv(kk, vv, k_pool, v_pool, tables, cl, s: int,
                   k_scale=None, v_scale=None):
    """Scatter s new tokens (starting at position ``cl``, scalar or
    per-sequence [B]) into the [kvh, blocks, bs, D] pools; returns the
    updated pools. With int8 pools pass the scale pools — new tokens
    quantize in the same scatter and the 4-tuple
    ``(k_pool, v_pool, k_scale, v_scale)`` comes back."""
    bs = k_pool.shape[2]
    b = kk.shape[0]
    phys, off = _write_positions(tables, cl, b, s, bs, k_pool.shape[1])
    # consecutive advanced indices (dims 1,2) keep their position, so
    # the value layout is [kvh, B, s, D]
    if k_scale is not None:
        qk, hk = _kv_quantize(kk)
        qv, hv = _kv_quantize(vv)
        k_pool = k_pool.at[:, phys, off].set(jnp.moveaxis(qk, 2, 0))
        v_pool = v_pool.at[:, phys, off].set(jnp.moveaxis(qv, 2, 0))
        k_scale = k_scale.at[:, phys, off].set(
            jnp.moveaxis(hk, 2, 0).astype(k_scale.dtype))
        v_scale = v_scale.at[:, phys, off].set(
            jnp.moveaxis(hv, 2, 0).astype(v_scale.dtype))
        return k_pool, v_pool, k_scale, v_scale
    k_pool = k_pool.at[:, phys, off].set(
        jnp.moveaxis(kk.astype(k_pool.dtype), 2, 0)
    )
    v_pool = v_pool.at[:, phys, off].set(
        jnp.moveaxis(vv.astype(v_pool.dtype), 2, 0)
    )
    return k_pool, v_pool


def paged_update_kv_cache(kk, vv, k_pool, v_pool, tables, cl, s: int,
                          contiguous: bool = False,
                          k_scale=None, v_scale=None):
    """Scatter + gather protocol for PREFILL (or the non-TPU fallback):
    returns (k_pool, v_pool, kc_view, vc_view, mask) where the views
    are the gathered [B, max_len, kv_heads, head_dim] caches and the
    mask is identical to the dense ``update_kv_cache`` mask — raw jnp
    arrays, same protocol as generation.update_kv_cache. With int8
    pools (scales passed) the views come back DEQUANTIZED to ``kk``'s
    dtype and the return grows to
    ``(k_pool, v_pool, k_scale, v_scale, kc, vc, mask)``."""
    if k_scale is not None:
        k_pool, v_pool, k_scale, v_scale = paged_write_kv(
            kk, vv, k_pool, v_pool, tables, cl, s,
            k_scale=k_scale, v_scale=v_scale)
        kc, vc = paged_gather_kv(
            k_pool, v_pool, tables, contiguous=contiguous,
            k_scale=k_scale, v_scale=v_scale, out_dtype=kk.dtype)
    else:
        k_pool, v_pool = paged_write_kv(
            kk, vv, k_pool, v_pool, tables, cl, s)
        kc, vc = paged_gather_kv(k_pool, v_pool, tables,
                                 contiguous=contiguous)
    max_len = kc.shape[1]
    b = kk.shape[0]
    q_pos = _per_seq_positions(cl, b, s)  # [B, s]
    # [B, 1, s, max_len] causal mask (broadcasts over heads)
    mask = jnp.arange(max_len)[None, None, None, :] <= q_pos[:, None, :, None]
    if k_scale is not None:
        return k_pool, v_pool, k_scale, v_scale, kc, vc, mask
    return k_pool, v_pool, kc, vc, mask


def paged_gather_kv(k_pool, v_pool, tables, contiguous: bool = False,
                    k_scale=None, v_scale=None, out_dtype=None):
    """[B, max_blocks] tables -> padded [B, max_blocks*bs, kvh, D] views.

    ``contiguous=True`` (identity table layout — generate()'s case)
    replaces the fancy-index gather with a reshape+transpose XLA fuses
    into the consumer: pool rows [b*per, (b+1)*per) ARE sequence b's
    blocks in order, so ``k_pool[:, tables]`` is exactly
    ``k_pool.reshape(kvh, B, per*bs, d)``.

    Int8 pools (scales passed): the gathered views dequantize to
    ``out_dtype`` (the scales gather through the same table
    indexing — a freed/forked block's scales travel with its bytes)."""
    b, nb = tables.shape
    kvh, _, bs, d = k_pool.shape
    if contiguous and k_pool.shape[1] == b * nb:
        kc = jnp.moveaxis(k_pool.reshape(kvh, b, nb * bs, d), 0, 2)
        vc = jnp.moveaxis(v_pool.reshape(kvh, b, nb * bs, d), 0, 2)
        if k_scale is not None:
            sk = jnp.moveaxis(k_scale.reshape(kvh, b, nb * bs), 0, 2)
            sv = jnp.moveaxis(v_scale.reshape(kvh, b, nb * bs), 0, 2)
            kc = _kv_dequantize(kc, sk, out_dtype or jnp.float32)
            vc = _kv_dequantize(vc, sv, out_dtype or jnp.float32)
        return kc, vc
    kc = jnp.moveaxis(k_pool[:, tables], 0, 3).reshape(b, nb * bs, kvh, d)
    vc = jnp.moveaxis(v_pool[:, tables], 0, 3).reshape(b, nb * bs, kvh, d)
    if k_scale is not None:
        sk = jnp.moveaxis(k_scale[:, tables], 0, 3).reshape(b, nb * bs, kvh)
        sv = jnp.moveaxis(v_scale[:, tables], 0, 3).reshape(b, nb * bs, kvh)
        kc = _kv_dequantize(kc, sk, out_dtype or jnp.float32)
        vc = _kv_dequantize(vc, sv, out_dtype or jnp.float32)
    return kc, vc


def paged_attention_step(q, k, v, cache: "PagedLayerCache", cur_len, s: int,
                         rope_fn=None):
    """Shared model-side paged-cache step (used by LlamaAttention and
    GPTAttention — ONE copy of the tape plumbing, so protocol changes
    land in one place).

    q/k/v: [B, s, H|kvh, D] Tensors. ``rope_fn(qq, kk, cl) -> (qq, kk)``
    applies positional rotation inside the traced step (None for
    absolute-position models).

    Returns:
    - decode (s == 1): ``(out, new_cache)`` where ``out`` is the
      attention output [B, 1, H, D] (path policy per
      paged_decode_attention — note no attention-probability dropout
      exists on this path; callers must enforce eval semantics);
    - prefill (s > 1): ``(q_t, kc, vc, mask, new_cache)`` — the caller
      runs its own SDPA (dropout and all) over the gathered view.
    """
    from ..base.tape import apply

    contiguous = bool(getattr(cache, "contiguous", False))
    quant = getattr(cache, "k_scale", None) is not None
    if s == 1:
        if quant:
            def pstep_decode_q(qq, kk, vv, kp, vp, ks, vs, tbl, cl):
                if rope_fn is not None:
                    qq, kk = rope_fn(qq, kk, cl)
                kp, vp, ks, vs = paged_write_kv(
                    kk, vv, kp, vp, tbl, cl, 1, k_scale=ks, v_scale=vs)
                out = paged_decode_attention(
                    qq, kp, vp, tbl, cl, contiguous=contiguous,
                    k_scale=ks, v_scale=vs)
                return out, kp, vp, ks, vs

            out, k_pool, v_pool, ks, vs = apply(
                pstep_decode_q, q, k, v, cache.k_pool, cache.v_pool,
                cache.k_scale, cache.v_scale, cache.block_tables, cur_len,
                op_name="paged_decode",
            )
            return out, PagedLayerCache(
                k_pool, v_pool, cache.block_tables, contiguous, ks, vs
            )

        def pstep_decode(qq, kk, vv, kp, vp, tbl, cl):
            if rope_fn is not None:
                qq, kk = rope_fn(qq, kk, cl)
            kp, vp = paged_write_kv(kk, vv, kp, vp, tbl, cl, 1)
            out = paged_decode_attention(
                qq, kp, vp, tbl, cl, contiguous=contiguous
            )
            return out, kp, vp

        out, k_pool, v_pool = apply(
            pstep_decode, q, k, v, cache.k_pool, cache.v_pool,
            cache.block_tables, cur_len, op_name="paged_decode",
        )
        return out, PagedLayerCache(
            k_pool, v_pool, cache.block_tables, contiguous
        )

    if quant:
        def pstep_q(qq, kk, vv, kp, vp, ks, vs, tbl, cl):
            if rope_fn is not None:
                qq, kk = rope_fn(qq, kk, cl)
            kp, vp, ks, vs, kc, vc, mask = paged_update_kv_cache(
                kk, vv, kp, vp, tbl, cl, s, contiguous=contiguous,
                k_scale=ks, v_scale=vs)
            return qq, kp, vp, ks, vs, kc, vc, mask

        q_t, k_pool, v_pool, ks, vs, kc, vc, mask = apply(
            pstep_q, q, k, v, cache.k_pool, cache.v_pool,
            cache.k_scale, cache.v_scale, cache.block_tables, cur_len,
            op_name="paged_kv_cache_update",
        )
        return q_t, kc, vc, mask, PagedLayerCache(
            k_pool, v_pool, cache.block_tables, contiguous, ks, vs
        )

    def pstep(qq, kk, vv, kp, vp, tbl, cl):
        if rope_fn is not None:
            qq, kk = rope_fn(qq, kk, cl)
        kp, vp, kc, vc, mask = paged_update_kv_cache(
            kk, vv, kp, vp, tbl, cl, s, contiguous=contiguous
        )
        return qq, kp, vp, kc, vc, mask

    q_t, k_pool, v_pool, kc, vc, mask = apply(
        pstep, q, k, v, cache.k_pool, cache.v_pool,
        cache.block_tables, cur_len, op_name="paged_kv_cache_update",
    )
    return q_t, kc, vc, mask, PagedLayerCache(
        k_pool, v_pool, cache.block_tables, contiguous
    )


def _largest_divisor(n: int, cap: int) -> int:
    for c in range(min(cap, n), 0, -1):
        if n % c == 0:
            return c
    return 1


def _ratio_aware_pages_per_block(pages_per_seq: int, ratio: int) -> int:
    """Pick ``pages_per_compute_block`` from the q-head:kv-head ratio.

    The kernel's grid is (batch, kv_heads, page-chunks) and each
    program multiplies a [ratio, d] query tile against its chunk's
    [pages*bs, d] keys/values. At ratio >= 8 the MXU tile is full and
    small chunks (8 pages) maximize grid parallelism — the measured
    winning regime. BELOW that, each program's matmul underuses the
    MXU and the per-page DMA steering dominates, so widen the chunk
    inversely with the ratio (ratio 4 -> 16 pages, ratio 2 -> 32,
    MHA -> 64): fewer programs, each amortizing its DMA setup across
    proportionally more contraction work."""
    cap = 8 * max(1, 8 // max(ratio, 1))
    return _largest_divisor(pages_per_seq, cap)


def paged_decode_attention(q, k_pool, v_pool, tables, cache_len,
                           contiguous: bool = False,
                           k_scale=None, v_scale=None):
    """Single-token decode attention over the paged cache.

    q: [B, 1, num_heads, D]; pools [kvh, blocks, bs, D]; cache_len:
    position of the token being written — a scalar OR a per-sequence
    [B] array for ragged serving batches (each sequence attends over
    its own cache_len+1 tokens).

    Path selection (MEASURED — 542M-class decode, B=8, P=1600, v5e,
    same-session multi_step scans; ms/step; kernel column was measured
    with the FIXED 8-page compute block):

    | q_heads/kv_heads | dense | reshape-view | Pallas kernel | gather |
    |---|---|---|---|---|
    | 1 (MHA)  | 3.13 | **2.80** | 8.29 | 3.55 |
    | 4        | 2.88 | 2.68 | **2.78*** | 3.22 |
    | 8 (GQA)  | 1.92 | 2.06 | **1.49** | 2.54 |

    The kernel's grid is (batch, kv_heads, page-chunks): with few
    q-heads per kv-head each program does almost no compute and the
    per-page DMA steering costs more than it saves. Ratio-aware block
    shapes (``_ratio_aware_pages_per_block``) widen the page chunk
    inversely with the ratio, so the ratio-4 row above (*fixed-block
    number, 0.10 ms behind reshape-view) is the regime the widened
    block targets; the widened blocks are not measured. At ratios
    >= ~8 the kernel beats everything including the dense cache.

    Policy:
    - contiguous tables: reshape to a dense view (free) unless the GQA
      ratio >= 4 AND the kernel can tile (then the ratio-aware-block
      kernel wins; at ratio 4 the fixed-block kernel was already at
      parity and the widened block removes the DMA-steering deficit).
    - RAGGED tables (BlockManager serving): ALWAYS the kernel when it
      can tile — the gather fallback materializes the full
      table-width padded view, which at serving shapes (position
      budget >> live tokens) costs exactly the dense-cache memory the
      paged layout exists to avoid; the kernel reads only live pages.
      The gather runs only when the kernel can't tile (head_dim %
      128 or block_size % 8) or off-TPU. All paths are
      token-identical.

    Int8 pools (``k_scale``/``v_scale`` passed): the kernel path wraps
    the pools + scale pools as ``QuantizedTensor`` pages — the Pallas
    kernel dequantizes in-register per page DMA (same convention, see
    ``_KV_QMAX``) — and the gather fallback dequantizes the gathered
    view to ``q.dtype``."""
    b, s, h, d = q.shape
    assert s == 1, "paged_decode_attention is the s==1 decode path"
    cache_len = _validate_cache_len(cache_len, b)
    kvh = k_pool.shape[0]
    ratio = h // max(kvh, 1)
    platform = jax.devices()[0].platform
    bs = k_pool.shape[2]
    # TPU tiling: kernel blocks are (page_size, head_dim) tiles
    if (
        platform == "tpu" and d % 128 == 0 and bs % 8 == 0
        and (not contiguous or ratio >= 4)
    ):
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as _paged_attention_kernel,
        )

        k_pages, v_pages = k_pool, v_pool
        if k_scale is not None:
            from jax.experimental.pallas.ops.tpu.paged_attention import (
                quantization_utils as _qu,
            )

            # scales gain the kernel's trailing keepdims axis; the
            # kernel DMAs the scale page alongside the value page and
            # dequantizes in-register (from_int8: q * h / 127.5)
            k_pages = _qu.QuantizedTensor(k_pool, k_scale[..., None])
            v_pages = _qu.QuantizedTensor(v_pool, v_scale[..., None])
        lengths = jnp.broadcast_to(cache_len + 1, (b,)).astype(jnp.int32)
        pages_per_seq = tables.shape[1]
        scale = jnp.asarray(1.0 / np.sqrt(d), q.dtype)
        out = _paged_attention_kernel(
            q[:, 0] * scale,  # kernel applies no 1/sqrt(d) itself
            k_pages, v_pages,
            lengths, tables,
            pages_per_compute_block=_ratio_aware_pages_per_block(
                pages_per_seq, ratio),
        )
        return out[:, None]  # [B, 1, H, D]
    # contiguous: reshape-view (free); ragged: gathered padded view —
    # both through the SAME attention math as the dense/prefill path
    # (keeps paged-vs-dense parity by construction)
    from ..nn.functional.attention import _naive_attention

    kc, vc = paged_gather_kv(k_pool, v_pool, tables, contiguous=contiguous,
                             k_scale=k_scale, v_scale=v_scale,
                             out_dtype=q.dtype)
    max_len = kc.shape[1]
    # [B or 1, 1, 1, S] — per-sequence lengths mask their own tails
    mask = (
        jnp.arange(max_len)[None, :] <= cache_len.reshape(-1, 1)
    )[:, None, None, :]
    return _naive_attention(q, kc, vc, mask, 0.0, False, None, None)

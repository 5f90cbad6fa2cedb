"""Autoregressive generation with KV caches.

ref: generation lives downstream of the reference (PaddleNLP
generation_utils: greedy/sampling loops over cached decoders); the
in-repo surface it depends on is the cached attention path this module
drives.

TPU-native design: KV caches are **buffers of a cache-state Layer**, so
``jit.to_static`` threads and DONATES them with the rest of the model
state — each decode step updates the caches in place on device (no
per-token cache copy) and the compiled prefill/decode programs are
cached on the model and reused across ``generate`` calls (static
shapes, no per-length retrace). Sampling keys draw from the framework
RNG (threaded through the compiled step).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..base import random as _random
from ..base.tape import apply
from ..base.tensor import Tensor

__all__ = ["alloc_kv_caches", "update_kv_cache", "generate"]


def alloc_kv_caches(num_layers, batch, max_len, num_kv_heads, head_dim, dtype):
    caches = []
    for _ in range(num_layers):
        k = Tensor(jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
                   _internal=True)
        v = Tensor(jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
                   _internal=True)
        caches.append((k, v))
    return caches


def update_kv_cache(kk, vv, kc, vc, cl, s: int):
    """Shared cache-write + causal-mask protocol (raw jnp arrays; used
    by both Llama and GPT attention): writes the new [B, s, H, D] block
    at position ``cl`` and returns (k_cache, v_cache, mask) where mask
    is the [1, 1, s, max_len] bool mask letting query i see keys
    <= cl + i."""
    max_len = kc.shape[1]
    kc = jax.lax.dynamic_update_slice(kc, kk.astype(kc.dtype), (0, cl, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, vv.astype(vc.dtype), (0, cl, 0, 0))
    k_idx = jnp.arange(max_len)[None, :]
    q_idx = cl + jnp.arange(s)[:, None]
    return kc, vc, (k_idx <= q_idx)[None, None]


class _KVCacheState:
    """Holds cache tensors as non-persistable buffers of a Layer so the
    compiled step threads + donates them (see module docstring).
    ``block_size`` switches to the paged (block-table) cache layout
    (ops/paged_attention.py)."""

    def __init__(self, model, batch, max_len, block_size=None,
                 kv_dtype=None):
        from ..nn.layer.layers import Layer

        class Holder(Layer):
            pass

        self.holder = Holder()
        # decode-loop state for the CHUNKED path: the current token and
        # the eos-finished mask live on device with the caches, so a
        # lax.scan over decode steps carries them — one dispatch per
        # chunk instead of per token (per-token host dispatch otherwise
        # bounds decode throughput)
        self.holder.register_buffer(
            "tok", Tensor(jnp.zeros((batch,), jnp.int32), _internal=True),
            persistable=False,
        )
        self.holder.register_buffer(
            "finished", Tensor(jnp.zeros((batch,), bool), _internal=True),
            persistable=False,
        )
        self.paged = block_size is not None
        kwargs = {"block_size": block_size} if self.paged else {}
        if kv_dtype is not None:
            kwargs["kv_dtype"] = kv_dtype
        caches = model.init_cache(batch, max_len, **kwargs)
        self.n = len(caches)
        self.shapes_dtypes = []
        self.quantized = False
        if self.paged:
            from ..ops.paged_attention import PagedLayerCache  # noqa: F401

            self._tables = caches[0].block_tables
            self._contiguous = bool(getattr(caches[0], "contiguous", False))
            self.quantized = getattr(caches[0], "k_scale", None) is not None
            for i, c in enumerate(caches):
                self.holder.register_buffer(f"k{i}", c.k_pool, persistable=False)
                self.holder.register_buffer(f"v{i}", c.v_pool, persistable=False)
                self.shapes_dtypes.append(
                    (tuple(c.k_pool.shape), c.k_pool._data.dtype)
                )
                if self.quantized:
                    # int8 KV: the per-block scale pools are device
                    # state exactly like the value pools — registered
                    # so to_static threads + donates them with the rest
                    self.holder.register_buffer(
                        f"ks{i}", c.k_scale, persistable=False)
                    self.holder.register_buffer(
                        f"vs{i}", c.v_scale, persistable=False)
        else:
            for i, (k, v) in enumerate(caches):
                self.holder.register_buffer(f"k{i}", k, persistable=False)
                self.holder.register_buffer(f"v{i}", v, persistable=False)
                self.shapes_dtypes.append((tuple(k.shape), k._data.dtype))

    def caches(self):
        if self.paged:
            from ..ops.paged_attention import PagedLayerCache

            return [
                PagedLayerCache(
                    self.holder._buffers[f"k{i}"],
                    self.holder._buffers[f"v{i}"],
                    self._tables,
                    self._contiguous,
                    *((self.holder._buffers[f"ks{i}"],
                       self.holder._buffers[f"vs{i}"])
                      if self.quantized else ()),
                )
                for i in range(self.n)
            ]
        return [
            (self.holder._buffers[f"k{i}"], self.holder._buffers[f"v{i}"])
            for i in range(self.n)
        ]

    def set(self, new_caches):
        for i, c in enumerate(new_caches):
            k, v = (c.k_pool, c.v_pool) if self.paged else (c[0], c[1])
            self.holder._buffers[f"k{i}"]._data = k._data
            self.holder._buffers[f"v{i}"]._data = v._data
            if self.quantized:
                self.holder._buffers[f"ks{i}"]._data = c.k_scale._data
                self.holder._buffers[f"vs{i}"]._data = c.v_scale._data

    def reset(self):
        for i, (shape, dt) in enumerate(self.shapes_dtypes):
            self.holder._buffers[f"k{i}"]._data = jnp.zeros(shape, dt)
            self.holder._buffers[f"v{i}"]._data = jnp.zeros(shape, dt)
            if self.quantized:
                for nm in (f"ks{i}", f"vs{i}"):
                    buf = self.holder._buffers[nm]
                    buf._data = jnp.zeros(buf._data.shape, buf._data.dtype)
        tok = self.holder._buffers["tok"]
        tok._data = jnp.zeros(tok._data.shape, jnp.int32)
        fin = self.holder._buffers["finished"]
        fin._data = jnp.zeros(fin._data.shape, bool)


def _sample(logits, temperature: float, top_k: int):
    """logits [B, V] → token ids [B]; greedy when temperature == 0."""

    def f(lg):
        if temperature == 0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        lg = lg.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        key = _random.next_key()
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)

    return apply(f, logits, op_name="sample_token")


def _get_compiled(model, b, s, max_len, temperature, top_k, use_jit,
                  block_size=None, chunked=False, eos_token_id=None,
                  kv_dtype=None, spec_k=None):
    """Build (or fetch) the prefill/decode programs + cache state for
    this (batch, prompt-len, max-len, sampling) signature.

    ``chunked=True`` builds a decode step that reads/writes the token
    and eos-finished mask as HOLDER BUFFERS (device state) instead of
    passing the token host-side — so ``decode.multi_step`` can scan K
    steps in one dispatch. The eos logic is baked into the step, hence
    eos_token_id joins the cache key.

    ``spec_k=K`` additionally builds the speculative VERIFY program —
    the cached step at width K+1 returning the argmax at EVERY
    position — and the return grows to a 4-tuple
    ``(state, prefill, decode, verify)``."""
    from .. import jit

    key = (b, s, max_len, temperature, top_k, use_jit, block_size,
           chunked, eos_token_id if chunked else None, kv_dtype, spec_k)
    store = getattr(model, "_generation_programs", None)
    if store is None:
        store = model._generation_programs = {}
    if key in store:
        entry = store.pop(key)  # re-insert as newest
        store[key] = entry
        entry[0].reset()
        return entry
    # bound the program cache: each entry pins full KV buffers + two
    # compiled programs; varying prompt lengths would otherwise grow
    # device memory without limit (LRU, insertion-ordered dict)
    while len(store) >= 4:
        store.pop(next(iter(store)))

    state = _KVCacheState(model, b, max_len, block_size=block_size,
                          kv_dtype=kv_dtype)

    def prefill(ids, cur_len):
        logits, new = model.forward_with_cache(ids, state.caches(), cur_len)
        state.set(new)
        tok = _sample(logits[:, -1], temperature, top_k)
        state.holder._buffers["tok"]._data = tok._data
        return tok

    if chunked:
        def decode(cur_len):
            prev = state.holder._buffers["tok"]
            fin = state.holder._buffers["finished"]
            logits, new = model.forward_with_cache(
                prev.reshape([b, 1]), state.caches(), cur_len
            )
            state.set(new)
            tok = _sample(logits[:, -1], temperature, top_k)
            if eos_token_id is not None:
                fin2, tok = apply(
                    lambda f, p, t: (
                        f | (p == eos_token_id),
                        jnp.where(f | (p == eos_token_id), eos_token_id, t),
                    ),
                    fin, prev, tok, op_name="eos_freeze",
                )
                state.holder._buffers["finished"]._data = fin2._data
            state.holder._buffers["tok"]._data = tok._data
            return tok
    else:
        def decode(tok, cur_len):
            logits, new = model.forward_with_cache(
                tok.reshape([b, 1]), state.caches(), cur_len
            )
            state.set(new)
            return _sample(logits[:, -1], temperature, top_k)

    verify = None
    if spec_k:
        def verify(ids, cur_len):
            """Speculative verify: feed [B, spec_k+1] candidate tokens
            at positions cur_len.., write their KV, return the greedy
            argmax at EVERY position (the accept rule runs host-side
            on these K+1 ints — logits never leave the device)."""
            logits, new = model.forward_with_cache(
                ids, state.caches(), cur_len)
            state.set(new)
            return apply(
                lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32),
                logits, op_name="verify_argmax")

    if use_jit:
        prefill = jit.to_static(prefill, layers=[model, state.holder])
        decode = jit.to_static(decode, layers=[model, state.holder])
        if verify is not None:
            verify = jit.to_static(verify, layers=[model, state.holder])
    entry = ((state, prefill, decode) if verify is None
             else (state, prefill, decode, verify))
    store[key] = entry
    return entry


def _decode_chunked(state, decode, first_tok, s, max_new_tokens,
                    chunk: int, eos_token_id):
    """Drive the chunked decode: one regular call (required before
    multi_step, and it compiles the step), then multi_step scans of up
    to ``chunk`` steps per dispatch. Returns the per-position token
    Tensors ([B] each), eos rows frozen in-program."""
    from .. import to_tensor

    out = [first_tok]
    done = 1  # tokens emitted so far (prefill's sample)
    # regular call: position s + done - 1 writes cache slot for token
    out.append(decode(to_tensor(np.asarray(s + done - 1, np.int32))))
    done += 1
    while done < max_new_tokens:
        k = min(chunk, max_new_tokens - done)
        curs = np.arange(s + done - 1, s + done - 1 + k, dtype=np.int32)
        if k == 1:
            out.append(decode(to_tensor(curs[0])))
        else:
            toks = decode.multi_step(to_tensor(curs))  # [k, B]
            from ..tensor.manipulation import unstack

            out.extend(unstack(toks, axis=0))
        done += k
        if eos_token_id is not None and bool(
            np.asarray(state.holder._buffers["finished"]._data).all()
        ):
            # every row finished: emit frozen eos for the remainder
            # without further dispatches
            while done < max_new_tokens:
                out.append(out[-1])
                done += 1
            break
    return out


def _decode_speculative(decode, verify, input_ids, first_tok, s,
                        max_new_tokens, k, eos_token_id, proposer):
    """Drive speculative generation: per round, draft k tokens per row
    (n-gram prompt lookup by default), ONE verify dispatch scores all
    k+1 positions, and every row advances by the BATCH-MIN accepted
    prefix + 1 (a uniform advance keeps the scalar ``cur_len`` the
    dense cache-write contract needs; the serving engine's per-slot
    ragged accept lives in inference/serving.py). Token-exact vs the
    plain loop: accepted drafts EQUAL the argmax by construction, and
    the tail (< k+1 positions of budget left) falls back to single-step
    decode. Returns the [B] per-position token arrays (host int32)."""
    from .. import to_tensor
    from ..inference.speculative import accept_length

    b = int(input_ids.shape[0])
    prompt_np = np.asarray(
        input_ids.numpy() if hasattr(input_ids, "numpy") else input_ids,
        np.int32)
    first_np = np.asarray(first_tok.numpy(), np.int32).reshape(b)
    hist = [list(prompt_np[r]) + [int(first_np[r])] for r in range(b)]
    finished = np.zeros((b,), bool)
    if eos_token_id is not None:
        finished |= first_np == eos_token_id
    out = [first_np]
    done = 1
    while done < max_new_tokens:
        if eos_token_id is not None and finished.all():
            while done < max_new_tokens:  # frozen rows: no dispatches
                out.append(out[-1])
                done += 1
            break
        cur = s + done - 1  # position of the token out[-1] writes
        # tail: a k+1-wide verify would write KV past max_len (the
        # dense cache's dynamic_update_slice would SHIFT the window)
        no_spec = done + k > max_new_tokens
        if not no_spec:
            drafts = np.zeros((b, k), np.int32)
            any_draft = False
            for r in range(b):
                if finished[r]:
                    continue  # frozen; full-accept forced below
                d = np.asarray(proposer.propose(
                    np.asarray(hist[r], np.int32), k),
                    np.int32).reshape(-1)[:k]
                drafts[r, : d.size] = d
                any_draft = any_draft or d.size > 0
            # no row has draft signal: a k+1-wide verify would spend
            # (k+1)x the decode compute to advance ~1 token — take the
            # plain step instead (the engine path's zero-cost fallback)
            no_spec = not any_draft
        if no_spec:
            tok = decode(to_tensor(out[-1]),
                         to_tensor(np.asarray(cur, np.int32)))
            t = np.asarray(tok.numpy(), np.int32).reshape(b)
            if eos_token_id is not None:
                t = np.where(finished, eos_token_id, t).astype(np.int32)
                finished = finished | (t == eos_token_id)
            for r in range(b):
                hist[r].append(int(t[r]))
            out.append(t)
            done += 1
            continue
        ids_step = np.concatenate([out[-1][:, None], drafts], axis=1)
        toks = verify(to_tensor(ids_step),
                      to_tensor(np.asarray(cur, np.int32)))
        toks_np = np.asarray(toks.numpy(), np.int32)  # [B, k+1]
        # batch-min accept: rows that accepted more re-propose next
        # round (still exact — an accepted prefix of a correct prefix
        # is correct); finished rows must not drag the minimum down.
        # ONE implementation of the exactness-critical accept rule:
        # speculative.accept_length (the engine's device cumprod is
        # pinned against it in tests)
        acc = np.asarray([
            k if finished[r]
            else accept_length(drafts[r], toks_np[r, :-1])
            for r in range(b)])
        m = min(int(acc.min()) + 1, max_new_tokens - done)
        for j in range(m):
            t = toks_np[:, j]
            if eos_token_id is not None:
                t = np.where(finished, eos_token_id, t)
                finished = finished | (t == eos_token_id)
            for r in range(b):
                hist[r].append(int(t[r]))
            out.append(t.astype(np.int32))
        done += m
    return out


def generate(model, input_ids, max_new_tokens: int = 32,
             temperature: float = 0.0, top_k: int = 0,
             eos_token_id: Optional[int] = None, use_jit: bool = True,
             block_size: Optional[int] = None,
             decode_chunk: Optional[int] = None,
             kv_dtype: Optional[str] = None,
             speculative_k: Optional[int] = None,
             draft_proposer=None):
    """Generate ``max_new_tokens`` continuations of ``input_ids``
    ([B, S] int Tensor) with KV caching. Returns [B, S + new] ids.

    ``model`` must provide ``init_cache(batch, max_len)`` and
    ``forward_with_cache(ids, caches, cur_len) -> (logits, caches)``
    (models.LlamaForCausalLM / GPTForCausalLM do). ``block_size``
    switches to the paged (block-table) KV cache — same tokens, pool
    memory layout (ref: block_multihead_attention); the model's
    ``init_cache`` must accept ``block_size`` and its attention must
    handle PagedLayerCache (LlamaForCausalLM and GPTForCausalLM do).

    ``decode_chunk=K`` scans K decode steps inside ONE device dispatch
    (lax.scan over the compiled step; token + eos state carried on
    device) — the serving idiom when host↔device latency dominates
    per-token dispatch. Token-identical to the per-token loop; eos rows
    freeze in-program, and generation stops at the first chunk whose
    rows are all finished.

    ``speculative_k=K`` turns on self-speculative decoding (greedy
    only): a :class:`~paddle_tpu.inference.speculative.DraftProposer`
    (default n-gram prompt lookup — no second model, no extra
    dispatches) drafts K tokens per round and ONE verify dispatch
    scores all K+1 positions; rows advance by the batch-min accepted
    prefix + 1. Token-identical to the plain loop by greedy
    accept-prefix construction. ``kv_dtype="int8"`` (requires
    ``block_size``) quantizes the KV pools per block — both levers
    compose."""
    from .. import to_tensor
    from ..base.tape import no_grad

    b, s = input_ids.shape
    if max_new_tokens <= 0:
        return input_ids
    if speculative_k is not None:
        if int(speculative_k) < 1:
            raise ValueError(
                f"speculative_k must be >= 1, got {speculative_k}")
        if temperature != 0:
            raise ValueError(
                "speculative decoding is greedy-only: the accept rule "
                "is argmax-prefix equality (temperature must be 0)")
        if decode_chunk:
            raise ValueError(
                "speculative_k and decode_chunk are alternative decode "
                "drivers — pass one, not both")
    max_len = s + max_new_tokens
    limit = getattr(getattr(model, "config", None), "max_position_embeddings", None)
    if limit is not None and max_len > limit:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) = {max_len} "
            f"exceeds the model's max_position_embeddings ({limit})"
        )

    was_training = model.training
    model.eval()
    chunked = bool(decode_chunk) and use_jit and max_new_tokens > 2
    spec = None if speculative_k is None else min(
        int(speculative_k), max(max_new_tokens - 1, 1))
    try:
        with no_grad():
            if spec is not None:
                from ..inference.speculative import NgramProposer

                state, prefill, decode, verify = _get_compiled(
                    model, b, s, max_len, temperature, top_k, use_jit,
                    block_size=block_size, eos_token_id=eos_token_id,
                    kv_dtype=kv_dtype, spec_k=spec,
                )
                zero = to_tensor(np.asarray(0, np.int32))
                tok = prefill(input_ids, zero)
                out = _decode_speculative(
                    decode, verify, input_ids, tok, s, max_new_tokens,
                    spec, eos_token_id,
                    draft_proposer if draft_proposer is not None
                    else NgramProposer(),
                )
                from ..tensor.manipulation import concat

                new_tokens = to_tensor(
                    np.stack(out, axis=1).astype(np.int32))  # [B, new]
                return concat(
                    [input_ids, new_tokens.astype(input_ids.dtype)], axis=1
                )
            state, prefill, decode = _get_compiled(
                model, b, s, max_len, temperature, top_k, use_jit,
                block_size=block_size, chunked=chunked,
                eos_token_id=eos_token_id, kv_dtype=kv_dtype,
            )
            zero = to_tensor(np.asarray(0, np.int32))
            tok = prefill(input_ids, zero)
            if chunked:
                out = _decode_chunked(
                    state, decode, tok, s, max_new_tokens,
                    int(decode_chunk), eos_token_id,
                )
                from ..tensor.manipulation import concat, stack

                new_tokens = stack(out, axis=1)  # [B, new]
                return concat(
                    [input_ids, new_tokens.astype(input_ids.dtype)], axis=1
                )
            out = [tok]
            finished = apply(
                lambda t: jnp.zeros(t.shape, bool), tok, op_name="zeros_like"
            )
            for step_i in range(1, max_new_tokens):
                cur = to_tensor(np.asarray(s + step_i - 1, np.int32))
                tok = decode(tok, cur)
                if eos_token_id is not None:
                    # once a row emits eos, freeze it to eos thereafter
                    finished = apply(
                        lambda f, p: f | (p == eos_token_id),
                        finished, out[-1], op_name="eos_track",
                    )
                    tok = apply(
                        lambda t, f: jnp.where(f, eos_token_id, t),
                        tok, finished, op_name="eos_mask",
                    )
                out.append(tok)
            from ..tensor.manipulation import concat, stack

            new_tokens = stack(out, axis=1)  # [B, new]
            return concat([input_ids, new_tokens.astype(input_ids.dtype)], axis=1)
    finally:
        if was_training:
            model.train()

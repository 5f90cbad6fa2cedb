"""Decode-throughput bench: dense KV cache vs the paged paths, measured
two ways. Run on the real chip:

    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/decode_bench.py

1. **multi_step scan rows** (primary): per-step cost of the compiled
   decode scanned K steps in ONE dispatch (decode_chunk machinery),
   differenced between K=16 and K=256 — the host dispatch+fetch cost
   appears once per dispatch and cancels.
2. **per-token dispatch rows** (context): the classic one-dispatch-per-
   token loop, host dispatch included; only same-session rows compare.

Variants: dense cache; paged contiguous (reshape-view path); paged
kernel (Pallas paged-attention forced, the ragged-table path); paged
gather (fancy-index fallback, forced). Set GQA=1 in the env to use
num_key_value_heads=2 (the kernel's winning regime)."""
import os
import time

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.ops.paged_attention as PA
from paddle_tpu import to_tensor
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import _get_compiled, generate
from paddle_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()
KVH = 2 if os.getenv("GQA") else 16
config = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                     num_hidden_layers=8, num_attention_heads=16,
                     num_key_value_heads=KVH, max_position_embeddings=2048)
paddle.seed(0)
model = LlamaForCausalLM(config)
model.bfloat16()
B, P, NEW = 8, int(os.getenv("PROMPT", 512)), 300
rng = np.random.RandomState(0)
ids = paddle.to_tensor(rng.randint(0, 32000, (B, P)).astype(np.int64))

orig = PA.paged_decode_attention


def force_kernel(q, kp, vp, tbl, cl, contiguous=False):
    return orig(q, kp, vp, tbl, cl, contiguous=False)


def force_gather(q, kp, vp, tbl, cl, contiguous=False):
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _naive_attention

    kc, vc = PA.paged_gather_kv(kp, vp, tbl)
    max_len = kc.shape[1]
    mask = (jnp.arange(max_len)[None, :]
            <= jnp.asarray(cl).reshape(-1, 1))[:, None, None, :]
    return _naive_attention(q, kc, vc, mask, 0.0, False, None, None)


def scan_row(label, block_size):
    with no_grad():
        model._generation_programs = {}
        state, prefill, decode = _get_compiled(
            model, B, P, P + NEW, 0.0, 0, True,
            block_size=block_size, chunked=True, eos_token_id=None)

        def fresh():
            state.reset()
            prefill(ids, to_tensor(np.asarray(0, np.int32)))
            decode(to_tensor(np.asarray(P, np.int32)))

        def curs(k):
            return to_tensor(np.arange(P + 1, P + 1 + k, dtype=np.int32))

        for k in (16, 256):  # compile both scan lengths
            fresh()
            np.asarray(decode.multi_step(curs(k))._data)
        best = 1e9
        for _ in range(3):
            fresh()
            t0 = time.perf_counter()
            np.asarray(decode.multi_step(curs(256))._data)
            t256 = time.perf_counter() - t0
            fresh()
            t0 = time.perf_counter()
            np.asarray(decode.multi_step(curs(16))._data)
            t16 = time.perf_counter() - t0
            best = min(best, (t256 - t16) / 240)
    print(f"[scan] {label}: {best*1e3:.3f} ms/step = {B/best:.0f} tok/s",
          flush=True)


def per_token_row(label, kw):
    model._generation_programs = {}
    for n in (32, 96):
        generate(model, ids, max_new_tokens=n, temperature=0.0, **kw)
    best = 1e9
    for _ in range(2):
        t0 = time.perf_counter()
        np.asarray(generate(model, ids, max_new_tokens=96,
                            temperature=0.0, **kw)._data)
        t96 = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(generate(model, ids, max_new_tokens=32,
                            temperature=0.0, **kw)._data)
        t32 = time.perf_counter() - t0
        best = min(best, t96 - t32)
    print(f"[per-token] {label}: {B*64/best:.0f} tok/s "
          f"({best/64*1e3:.2f} ms/token)", flush=True)


print(f"config: 542M-class, B={B}, P={P}, kv_heads={KVH}")
scan_row("dense", None)
scan_row("paged contiguous", 64)
PA.paged_decode_attention = force_kernel
scan_row("paged kernel (forced)", 64)
PA.paged_decode_attention = force_gather
scan_row("paged gather (forced)", 64)
PA.paged_decode_attention = orig

per_token_row("dense", {})
per_token_row("paged contiguous", {"block_size": 64})
per_token_row("dense chunked(32)", {"decode_chunk": 32})
per_token_row("paged chunked(32)", {"decode_chunk": 32, "block_size": 64})

"""Serving memory-capacity row: B concurrent sequences
with a 2048-token position budget but only 640 live tokens each
(P=512 prompt + 128 generated). The dense cache must pre-allocate
B x 2048 x kvh x d x 2 x layers; the paged pool allocates blocks for
LIVE tokens only (BlockManager), so the same HBM serves ~3x the
sequences. Run on the real chip:

    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/serving_capacity.py

Measured 2026-07-31 (v5e 15.75 GiB, 542M bf16 model = 1.1 GiB):
- B=128: dense needs 16.0 GiB -> RESOURCE_EXHAUSTED; paged pool is
  5.0 GiB -> allocates AND decodes a real model step.
- the eager probe double-buffers pools (no donation), so its own
  ceiling is ~B=176; the compiled serving loop (generate/to_static)
  donates cache buffers and runs 1x-pool, headroom to ~B=300."""
import gc

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import to_tensor
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()
config = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                     num_hidden_layers=8, num_attention_heads=16,
                     num_key_value_heads=16, max_position_embeddings=2048)
paddle.seed(0)
model = LlamaForCausalLM(config)
model.bfloat16()
B, LIVE, CAP, BSZ = 128, 640, 2048, 64

bytes_seq_dense = CAP * 16 * 128 * 2 * 2 * 8
blocks_live = -(-LIVE // BSZ)
bytes_seq_paged = blocks_live * BSZ * 16 * 128 * 2 * 2 * 8
print(f"per-seq KV: dense {bytes_seq_dense/2**20:.0f} MiB (budget {CAP}) "
      f"vs paged {bytes_seq_paged/2**20:.0f} MiB ({blocks_live} live blocks)")
print(f"B={B}: dense {B*bytes_seq_dense/2**30:.1f} GiB vs paged "
      f"{B*bytes_seq_paged/2**30:.1f} GiB (+1.1 GiB model, 15.75 GiB HBM)")


def try_paged():
    from paddle_tpu.ops.paged_attention import BlockManager

    mgr = BlockManager(num_blocks=B * blocks_live + 8, block_size=BSZ)
    tables = np.zeros((B, -(-CAP // BSZ)), np.int32)
    for b in range(B):
        row = mgr.allocate(b, LIVE)
        tables[b, :len(row)] = row
    caches = model.init_cache(B, CAP, block_size=BSZ,
                              num_blocks=B * blocks_live + 8, tables=tables)
    tok = to_tensor(
        np.random.RandomState(0).randint(0, 32000, (B, 1)).astype(np.int64))
    with no_grad():
        logits, _ = model.forward_with_cache(
            tok, caches, to_tensor(np.asarray(LIVE - 1, np.int32)))
    return np.asarray(logits._data[:, -1].argmax(-1)).shape


def try_dense():
    caches = model.init_cache(B, CAP)
    return sum(float(k._data[0, 0, 0, 0]) for k, _ in caches)


try:
    shape = try_paged()
    print(f"paged: allocated AND decoded one step (argmax shape {shape})")
except Exception as e:  # noqa: BLE001 — OOM is the expected failure mode
    print(f"paged: FAILED -> {type(e).__name__}: {str(e)[:120]}")
gc.collect()

try:
    try_dense()
    print("dense: allocated OK (no OOM) — raise B for the boundary")
except Exception as e:  # noqa: BLE001
    print(f"dense: FAILED -> {type(e).__name__}: {str(e)[:120]}")

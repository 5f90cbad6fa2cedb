"""int8 serving row: decode throughput + quality delta of
convert(execute_dtype="int8") vs bf16 on the 542M-class model, same
session (ref: the reference's llm.int8 deploy path,
paddle/phi/kernels/impl/llm_int8_matmul_kernel_impl.h).

Quantization: every nn.Linear (q/k/v/o, MLP, lm_head) swaps to
Int8InferenceLinear — per-out-channel int8 weights + dynamic activation
quantization, int8 x int8 -> int32 MXU dot (nn/quant). Memory: weights
drop 2 bytes -> 1 byte/param; decode at small batch is weight-streaming
bound, so int8 should WIN tokens/s, not just match.

``--kv int8`` (default) appends the KV-CACHE quantization column:
paged bf16 pools vs paged int8 pools + per-block scale pools
(``kv_dtype="int8"``, ops/paged_attention.py) under the same scan
methodology, plus the paged-prefill last-logit rel-err quality gate.
KV bytes halve; at serving batch the decode roofline is KV-bandwidth
bound, so int8 KV should WIN tok/s like int8 weights did.
``--smoke`` runs the whole bench on a tiny config (CPU harness
validation; absolute numbers meaningless).

Run: PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/int8_decode_bench.py
"""
import argparse
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import to_tensor
from paddle_tpu.base.tape import no_grad
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import _get_compiled, generate
from paddle_tpu.quantization import QAT, QuantConfig, quanter
from paddle_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()
ap = argparse.ArgumentParser()
ap.add_argument("--kv", choices=["none", "int8"], default="int8",
                help="append the int8 KV-cache column (paged pools)")
ap.add_argument("--smoke", action="store_true",
                help="tiny config for a CPU harness-validation run")
args = ap.parse_args()

if args.smoke:
    config = LlamaConfig.tiny()
    B, P, NEW, KV_BS = 2, 16, 24, 8
else:
    config = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=2048)
    B, P, NEW, KV_BS = 8, 512, 300, 64
paddle.seed(0)
model = LlamaForCausalLM(config)
if not args.smoke:
    model.bfloat16()
rng = np.random.RandomState(0)
ids = paddle.to_tensor(
    rng.randint(0, config.vocab_size, (B, P)).astype(np.int64))


def scan_row(m, label, block_size=None, kv_dtype=None):
    with no_grad():
        m._generation_programs = {}
        state, prefill, decode = _get_compiled(
            m, B, P, P + NEW, 0.0, 0, True, chunked=True,
            eos_token_id=None, block_size=block_size, kv_dtype=kv_dtype)

        k_big = min(256, NEW - 4)
        k_small = max(k_big // 16, 1)

        def fresh():
            state.reset()
            prefill(ids, to_tensor(np.asarray(0, np.int32)))
            decode(to_tensor(np.asarray(P, np.int32)))

        def curs(k):
            return to_tensor(np.arange(P + 1, P + 1 + k, dtype=np.int32))

        for k in (k_small, k_big):
            fresh()
            np.asarray(decode.multi_step(curs(k))._data)
        best = 1e9
        for _ in range(3):
            fresh()
            t0 = time.perf_counter()
            np.asarray(decode.multi_step(curs(k_big))._data)
            t256 = time.perf_counter() - t0
            fresh()
            t0 = time.perf_counter()
            np.asarray(decode.multi_step(curs(k_small))._data)
            t16 = time.perf_counter() - t0
            best = min(best, (t256 - t16) / (k_big - k_small))
    print(f"[scan] {label}: {best*1e3:.3f} ms/step = {B/best:.0f} tok/s",
          flush=True)
    return best


def greedy_tokens(m, n=None):
    n = min(64, NEW) if n is None else n
    out = generate(m, ids, max_new_tokens=n, temperature=0.0,
                   decode_chunk=min(32, n))
    return np.asarray(out._data)[:, P:]


def last_logits(m):
    with no_grad():
        caches = m.init_cache(B, P + 4)
        logits, _ = m.forward_with_cache(
            ids, caches, to_tensor(np.asarray(0, np.int32)))
    return np.asarray(logits._data[:, -1].astype("float32"))


# ---- bf16 reference ------------------------------------------------------
bf16_ms = scan_row(model, "bf16")
ref_tokens = greedy_tokens(model)
ref_logits = last_logits(model)

# ---- int8 conversion -----------------------------------------------------
# weight-only int8 deploy: no fake-quant projection — Int8InferenceLinear
# encodes each layer's weight with its TRUE per-out-channel absmax scale
cfg = QuantConfig(activation=None, weight=None)
qat = QAT(cfg)
model = qat.quantize(model)
model = qat.convert(model, execute_dtype="int8")
n_int8 = sum(1 for _, s in model.named_sublayers()
             if type(s).__name__ == "Int8InferenceLinear")
print(f"converted {n_int8} Linear layers to int8 execution")

int8_ms = scan_row(model, "int8")
int8_tokens = greedy_tokens(model)
int8_logits = last_logits(model)

match = float((ref_tokens == int8_tokens).mean())
rel = float(np.abs(int8_logits - ref_logits).mean()
            / (np.abs(ref_logits).mean() + 1e-9))
# top-5 containment: random-weight logits have near-tie argmaxes, so
# exact greedy match understates quality — check the int8 argmax lands
# in the bf16 top-5
top5 = np.argsort(ref_logits, axis=-1)[:, -5:]
in_top5 = float(np.mean([
    int8_logits[i].argmax() in top5[i] for i in range(B)]))
print(f"quality: greedy token match {match:.3f} over {ref_tokens.shape[1]} "
      f"tokens x {B} seqs; prefill last-logit rel err {rel:.4f}; "
      f"int8 argmax in bf16 top-5: {in_top5:.2f}")
print(f"speedup int8 vs bf16: {bf16_ms/int8_ms:.2f}x")


# ---- int4 weight-only conversion -----------------------------------------
# packed two-per-byte weights (0.5 B/param streamed) + group-64 scales;
# compute dequantizes into the bf16 MXU feed (nn/quant WeightOnlyLinear)
from paddle_tpu.nn.quant import convert_to_weight_only

paddle.seed(0)
model4 = LlamaForCausalLM(config)
if not args.smoke:
    model4.bfloat16()
n_int4 = convert_to_weight_only(model4, weight_dtype="int4", group_size=64)
print(f"converted {n_int4} Linear layers to packed-int4 weight-only")

int4_ms = scan_row(model4, "int4")
int4_tokens = greedy_tokens(model4)
int4_logits = last_logits(model4)
match4 = float((ref_tokens == int4_tokens).mean())
rel4 = float(np.abs(int4_logits - ref_logits).mean()
             / (np.abs(ref_logits).mean() + 1e-9))
in_top5_4 = float(np.mean([
    int4_logits[i].argmax() in top5[i] for i in range(B)]))
print(f"int4 quality: greedy match {match4:.3f}; prefill last-logit rel "
      f"err {rel4:.4f}; int4 argmax in bf16 top-5: {in_top5_4:.2f}")
print(f"SUMMARY ms/step: bf16 {bf16_ms*1e3:.3f} | int8 {int8_ms*1e3:.3f} "
      f"| int4 {int4_ms*1e3:.3f}  (same session)")


# ---- int8 KV-cache column (--kv int8) ------------------------------------
# the OTHER int8 lever: weight-only int8 halves weight bytes; paged
# kv_dtype="int8" halves KV bytes (pools + per-block scale pools,
# ops/paged_attention.py) — the lever that scales with BATCH and
# context, and doubles serving capacity on top of paged's block win
if args.kv == "int8":
    def last_logits_paged(m, kv_dtype=None):
        with no_grad():
            caches = m.init_cache(B, P + 4, block_size=KV_BS,
                                  kv_dtype=kv_dtype)
            logits, _ = m.forward_with_cache(
                ids, caches, to_tensor(np.asarray(0, np.int32)))
        return np.asarray(logits._data[:, -1].astype("float32"))

    paddle.seed(0)
    mkv = LlamaForCausalLM(config)
    if not args.smoke:
        mkv.bfloat16()
    paged_ms = scan_row(mkv, "paged-kv-bf16", block_size=KV_BS)
    kv8_ms = scan_row(mkv, "paged-kv-int8", block_size=KV_BS,
                      kv_dtype="int8")
    ref_kv_logits = last_logits_paged(mkv)
    kv8_logits = last_logits_paged(mkv, kv_dtype="int8")
    rel_kv = float(np.abs(kv8_logits - ref_kv_logits).mean()
                   / (np.abs(ref_kv_logits).mean() + 1e-9))
    top5_kv = np.argsort(ref_kv_logits, axis=-1)[:, -5:]
    in_top5_kv = float(np.mean([
        kv8_logits[i].argmax() in top5_kv[i] for i in range(B)]))
    print(f"int8-KV quality: prefill last-logit rel err {rel_kv:.4f}; "
          f"int8-KV argmax in bf16-KV top-5: {in_top5_kv:.2f}")
    print(f"KV column ms/step: paged-bf16 {paged_ms*1e3:.3f} | "
          f"paged-int8KV {kv8_ms*1e3:.3f}  "
          f"(speedup {paged_ms/kv8_ms:.2f}x; KV bytes halved)")

"""Small-shape MFU decomposition: why do
21M (h=512) and 168M (h=1024) sit at 0.485 / 0.548 MFU while 542M
reaches 0.774? Measures, per config and batch size:

- full AdamW step (the recorded row),
- SGD step (optimizer-pass cost by substitution: AdamW - SGD isolates
  the moment math; SGD - fwd/bwd bounds the write+infra cost),
- "none" (grads computed then discarded): NOTE XLA dead-code-eliminates
  the unused backward, so this row is effectively FORWARD-ONLY — treat
  it as a lower bound, not a fwd+bwd measurement,

and reports the analytic lm-head (CE) FLOP fraction — at h=512 the
2*h*V head matmul is the largest single GEMM and the vocab-32k softmax
is bandwidth-heavy relative to the tiny model body.

Run: PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/small_mfu_probe.py
"""
import time

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as popt
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.device.peaks import chip_peaks
from paddle_tpu.tensor import manipulation as M
from paddle_tpu.utils.compile_cache import enable_compile_cache


def probe(name, config, batch, seq, steps=96,
          variants=("adamw", "sgd", "none")):
    import jax

    paddle.seed(0)
    model = LlamaForCausalLM(config)
    model.bfloat16()
    rows = {}
    for opt_name in variants:
        if opt_name == "adamw":
            opt = popt.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True, moment_dtype="bfloat16")
        elif opt_name == "sgd":
            opt = popt.SGD(learning_rate=1e-5,
                           parameters=model.parameters())
        else:
            opt = None

        def step(ids, labels):
            logits = model(ids)
            b, s, v = logits.shape
            loss = F.cross_entropy(
                M.reshape(logits, [b * s, v]), M.reshape(labels, [b * s]))
            loss.backward()
            if opt is not None:
                opt.step()
                opt.clear_grad()
            else:
                for p in model.parameters():
                    p.clear_grad()
            return loss

        compiled = paddle.jit.to_static(
            step, layers=[model],
            optimizers=[opt] if opt is not None else [])
        rng = np.random.RandomState(0)
        ids_np = rng.randint(0, config.vocab_size, (batch, seq))
        ids = paddle.to_tensor(ids_np.astype("int32"))
        labels = paddle.to_tensor(ids_np.astype("int32"))
        compiled(ids, labels)
        k1, k2 = 4, steps
        np.asarray(compiled.multi_step(ids, labels, steps=k1)._data)
        np.asarray(compiled.multi_step(ids, labels, steps=k2)._data)
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(compiled.multi_step(ids, labels, steps=k2)._data)
            t2 = time.perf_counter() - t0
            t0 = time.perf_counter()
            np.asarray(compiled.multi_step(ids, labels, steps=k1)._data)
            t1 = time.perf_counter() - t0
            best = min(best, (t2 - t1) / (k2 - k1))
        rows[opt_name] = best * 1e3

    fpt = model.flops_per_token(seq)
    tok = batch * seq
    mfu = tok * fpt / (rows["adamw"] / 1e3) / chip_peaks().bf16_flops
    head_frac = 6 * config.hidden_size * config.vocab_size / fpt
    extra = "".join(
        f" | {k} {v:.2f} ms" for k, v in rows.items() if k != "adamw")
    print(f"{name} B={batch} S={seq}: adamw {rows['adamw']:.2f} ms"
          f"{extra} | MFU {mfu:.3f} | head(CE) flop frac {head_frac:.2f}",
          flush=True)
    return rows, mfu


tiny = LlamaConfig(vocab_size=32000, hidden_size=512, intermediate_size=2048,
                   num_hidden_layers=4, num_attention_heads=8,
                   num_key_value_heads=8, max_position_embeddings=2048)
small = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=4096,
                    num_hidden_layers=8, num_attention_heads=8,
                    num_key_value_heads=8, max_position_embeddings=2048)

tiny256 = LlamaConfig(vocab_size=256, hidden_size=512,
                      intermediate_size=2048, num_hidden_layers=4,
                      num_attention_heads=8, num_key_value_heads=8,
                      max_position_embeddings=2048)

if __name__ == "__main__":
    chip_peaks()  # no chip (or an unknown one) = error before any run
    enable_compile_cache()
    probe("21M-v32k", tiny, 8, 512)
    probe("21M-v32k", tiny, 32, 512)
    probe("168M", small, 8, 1024)
    # the ORIGINAL 21M row's config (v256): the true bandwidth-ceiling
    # shape; adamw-only keeps the run short
    probe("21M-v256", tiny256, 8, 512, steps=64, variants=("adamw",))

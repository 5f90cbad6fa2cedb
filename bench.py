"""Benchmark: transformer LM train step on the attached chip.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Metric: tokens/sec/chip for a Llama-style decoder LM train step
(forward+backward+AdamW) compiled via paddle_tpu.jit.to_static, bf16
activations path. vs_baseline = achieved MFU / 0.55 (the conventional
A100-class MFU anchor for Llama-2 pretrain stacks: MFU parity =>
vs_baseline >= 1.0).

One process, one chip: this process runs ``main()`` itself. There is no
supervisor, probe child or retry loop — on a machine with the chip
attached a retry can only hide a chip some other process holds. Without
an accelerator the script exits non-zero with a one-line reason and
prints no metric: a CPU run has no device number to report. The peak it
divides by comes from ``paddle_tpu.device.peaks`` (unknown device_kind =
error), and the XLA compile cache goes where
``paddle_tpu.utils.compile_cache`` says. Knobs (env): BENCH_CONFIG=
flagship|long|big, BENCH_BATCH, BENCH_SEQ, BENCH_STEPS, BENCH_SR,
BENCH_INTERLEAVE.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py: no accelerator (jax platform is "
              f"{dev.platform!r}); a CPU run has no device number to "
              "report", file=sys.stderr)
        return 1

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.device.peaks import chip_peaks
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.obs.regress import bench_record
    from paddle_tpu.tensor import manipulation as M
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    peak = chip_peaks(dev).bf16_flops  # unknown device_kind raises
    enable_compile_cache()

    variant = os.environ.get("BENCH_CONFIG", "flagship")
    multi_precision = True
    if variant == "long":
        # long-context row: attention-heavy regime, Pallas flash
        # kernel path
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=8192,
        )
        batch, seq = 1, 8192
        steps, warmup = int(os.environ.get("BENCH_STEPS", 48)), 2
    elif variant == "big":
        # largest-fits row: ~1.5B params; bf16 AdamW moments (fp32
        # masters would need 16 bytes/param and not fit 15.75G)
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2560, intermediate_size=6912,
            num_hidden_layers=18, num_attention_heads=20,
            num_key_value_heads=20, max_position_embeddings=2048,
        )
        batch, seq = int(os.environ.get("BENCH_BATCH", 1)), 2048
        steps, warmup = int(os.environ.get("BENCH_STEPS", 24)), 2
        multi_precision = False
    else:
        # flagship: 542M-param Llama at seq 2048 — small enough to fit
        # params + fp32 master/moments in one chip's HBM
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
        )
        batch = int(os.environ.get("BENCH_BATCH", 4))
        seq = int(os.environ.get("BENCH_SEQ", 2048))
        steps, warmup = int(os.environ.get("BENCH_STEPS", 132)), 2

    paddle.seed(0)
    model = LlamaForCausalLM(config)
    model.bfloat16()  # bf16 params+activations; AdamW keeps fp32 masters
    # Default: masterless bf16 with stochastic-rounded writes — drops
    # the fp32 masters' 8 bytes/param of HBM traffic while keeping the
    # fp32-master loss trajectory (unbiased rounding carries sub-ulp
    # updates in expectation), so the full fp32-master lr applies; the
    # bf16 convergence run reaches the f32 entropy-floor target
    # (tests/test_convergence.py). BENCH_SR=0 restores the fp32-master
    # configuration.
    use_sr = os.environ.get("BENCH_SR", "1") == "1"
    if use_sr:
        multi_precision = False
    # the PLAIN masterless config (multi_precision=False, no SR: bf16
    # WEIGHTS carry the update, ~3 significant digits) needs a smaller
    # step to stay stable; bf16 moment STORAGE itself is safe at lr 1e-4
    # (update math is f32 and fp32 masters accumulate)
    lr = 1e-4 if multi_precision or use_sr else 1e-5
    opt = popt.AdamW(
        learning_rate=lr, parameters=model.parameters(),
        multi_precision=multi_precision,
        use_stochastic_rounding=use_sr,
        # bf16 moment STORAGE (f32 update math): the AdamW pass is
        # HBM-bound and this halves its moment traffic
        moment_dtype="bfloat16",
        # BENCH_INTERLEAVE=1: apply each layer's AdamW update at its
        # grad-finalization point inside backward instead of a serial
        # tail of optimizer HBM traffic after backward
        interleave_updates=os.environ.get("BENCH_INTERLEAVE", "0") == "1",
    )

    def step(ids, labels):
        logits = model(ids)
        b, s, v = logits.shape
        loss = F.cross_entropy(
            M.reshape(logits, [b * s, v]), M.reshape(labels, [b * s])
        )
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = paddle.jit.to_static(step, layers=[model], optimizers=[opt])

    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, config.vocab_size, (batch, seq))
    ids = paddle.to_tensor(ids_np.astype("int32"))
    labels = paddle.to_tensor(ids_np.astype("int32"))

    for _ in range(warmup):
        loss = compiled(ids, labels)
    jax.block_until_ready(loss._data)

    # run K steps in a SINGLE dispatch (lax.scan inside jit,
    # StaticFunction.multi_step) and difference two run lengths so the
    # constant dispatch+fetch overhead cancels:
    #   per_step = (T(K2) - T(K1)) / (K2 - K1)
    k1, k2 = 4, steps
    # warm/compile both scan lengths outside the timed region
    jax.block_until_ready(compiled.multi_step(ids, labels, steps=k1)._data)
    jax.block_until_ready(compiled.multi_step(ids, labels, steps=k2)._data)

    def timed(k):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            loss = compiled.multi_step(ids, labels, steps=k)
            jax.block_until_ready(loss._data)
            best = min(best, time.perf_counter() - t0)
        return best, float(np.asarray(loss._data)[-1])

    t_k1, _ = timed(k1)
    t_k2, final_loss = timed(k2)
    dt = max(t_k2 - t_k1, 1e-9)

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * (k2 - k1) / dt
    flops_per_token = model.flops_per_token(seq)
    achieved = tokens_per_sec * flops_per_token
    mfu = achieved / peak
    vs_baseline = mfu / 0.55

    bench_record(
        "bench", "llama_train_tokens_per_sec_per_chip",
        round(tokens_per_sec, 1), "tokens/s",
        vs_baseline=round(vs_baseline, 4),
        extra={
            "mfu": round(mfu, 4),
            "step_ms": round(1000 * dt / (k2 - k1), 2),
            "loss": round(final_loss, 4),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "params": model.num_params(),
            "batch": batch,
            "seq": seq,
            "dtype": "bfloat16",
        },
        config={"batch": batch, "seq": seq})
    return 0


if __name__ == "__main__":
    sys.exit(main())

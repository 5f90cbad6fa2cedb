"""Big-regime MFU decomposition (round-4 verdict Next #6): why does
1.59B sit at ~0.726 and S=8192 at ~0.719 while the 542M flagship
reaches 0.774-0.778? Per config, by substitution (the flagship's
step-decomposition methodology):

- adamw            — the recorded row (bf16 moments; masterless for
                     1.59B where fp32 masters don't fit),
- adamw+interleave — the fused-optimizer-into-backward schedule
                     (optimizer.interleave_updates),
- fused_adamw      — interleave + the single-pass Pallas AdamW kernel
                     (AdamW(fused=True): one HBM read of p/g/m/v, one
                     write of p/m/v per layer, SR in-register),
- fp8              — every Linear except the lm_head swapped for
                     Fp8Linear (delayed-scaling e4m3/e5m2 GEMMs),
- sgd              — optimizer-pass cost by substitution,
- mean-loss        — cross_entropy replaced by logits.mean(): isolates
                     the 32k-vocab logsumexp/gather CE epilogue (the
                     lm-head GEMM stays),
- analytic fractions — attention and lm-head FLOP shares, since at
  S=8192 attention is ~1/3 of FLOPs at LOWER arithmetic intensity
  than the h=2048 GEMMs, capping achievable MFU below the dense-GEMM
  ceiling (~0.85 of peak on v5e, measured for the flagship).

Rows also land in the BENCH_LEDGER via obs.regress.bench_record, so
``obs regress`` tracks round-over-round movement.

Run (real chip):
    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/big_mfu_decomp.py
    BIG_ONLY=long|big limits to one config; BIG_STEPS overrides K.
    --smoke runs a tiny config few-step pass (CPU-safe: the fused
    kernel interprets, fp8 GEMMs run on XLA CPU) so CI exercises every
    variant's full compile+step path without a chip.
"""
import argparse
import json
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _timing  # noqa: E402  (shared K-differencing timer)

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as popt
from paddle_tpu.device.peaks import chip_peaks
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.tensor import manipulation as M
from paddle_tpu.utils.compile_cache import enable_compile_cache


VARIANTS = ("adamw", "interleave", "fused_adamw", "fp8", "sgd", "meanloss")


def probe(name, config, batch, seq, steps, multi_precision,
          variants=VARIANTS, record=True):
    paddle.seed(0)
    model = LlamaForCausalLM(config)
    model.bfloat16()
    rows = {}
    for variant in variants:
        model_v = model
        if variant == "fp8":
            # conversion swaps sublayers in place — give fp8 its own
            # identically-seeded model so later variants stay bf16
            from paddle_tpu.amp import convert_to_fp8

            paddle.seed(0)
            model_v = LlamaForCausalLM(config)
            model_v.bfloat16()
            convert_to_fp8(model_v, exclude=lambda n: "lm_head" in n)
        opt = None
        if variant in ("adamw", "interleave", "fused_adamw", "fp8",
                       "meanloss"):
            opt = popt.AdamW(
                learning_rate=1e-4, parameters=model_v.parameters(),
                multi_precision=multi_precision,
                use_stochastic_rounding=not multi_precision,
                moment_dtype="bfloat16",
                interleave_updates=(variant in ("interleave",
                                                "fused_adamw")),
                fused=(variant == "fused_adamw"))
        elif variant == "sgd":
            opt = popt.SGD(learning_rate=1e-5,
                           parameters=model_v.parameters())

        mean_loss = variant == "meanloss"

        def step(ids, labels):
            logits = model_v(ids)
            if mean_loss:
                loss = logits.mean()
            else:
                b, s, v = logits.shape
                loss = F.cross_entropy(
                    M.reshape(logits, [b * s, v]),
                    M.reshape(labels, [b * s]))
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        compiled = paddle.jit.to_static(step, layers=[model_v],
                                        optimizers=[opt])
        rng = np.random.RandomState(0)
        ids_np = rng.randint(0, config.vocab_size, (batch, seq))
        ids = paddle.to_tensor(ids_np.astype("int32"))
        labels = paddle.to_tensor(ids_np.astype("int32"))
        compiled(ids, labels)
        rows[variant] = round(
            _timing.diff_time_ms(compiled, ids, labels, steps), 2)
        del opt, compiled, model_v

    if jax.devices()[0].platform != "tpu":
        # a CPU pass proves every variant compiles and steps; it has no
        # device time or utilisation to report
        print(json.dumps({"config": name, "platform": "cpu",
                          "variants_ran": sorted(rows)}), flush=True)
        return None, None
    fpt = model.flops_per_token(seq)
    tok = batch * seq
    peak = chip_peaks().bf16_flops  # unknown device_kind raises
    mfu = {k: round(tok * fpt / (v / 1e3) / peak, 4)
           for k, v in rows.items()}
    c = config
    attn_frac = 12 * c.num_hidden_layers * c.hidden_size * seq / fpt
    head_frac = 6 * c.hidden_size * c.vocab_size / fpt
    print(json.dumps({
        "config": name, "batch": batch, "seq": seq,
        "step_ms": rows, "mfu": mfu,
        "attn_flop_frac": round(attn_frac, 3),
        "head_flop_frac": round(head_frac, 3),
        "params": model.num_params(),
    }), flush=True)
    if record:
        from paddle_tpu.obs.regress import bench_record

        cfg = {"config": name, "batch": batch, "seq": seq,
               "multi_precision": multi_precision}
        for variant, ms in rows.items():
            bench_record("big_mfu_decomp", f"step_ms_{variant}", ms,
                         "ms", config=cfg, mfu=mfu[variant])
    return rows, mfu


LONG = LlamaConfig(vocab_size=32000, hidden_size=2048,
                   intermediate_size=5632, num_hidden_layers=8,
                   num_attention_heads=16, num_key_value_heads=16,
                   max_position_embeddings=8192)
BIG = LlamaConfig(vocab_size=32000, hidden_size=2560,
                  intermediate_size=6912, num_hidden_layers=18,
                  num_attention_heads=20, num_key_value_heads=20,
                  max_position_embeddings=2048)

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config, 2 differencing steps — CPU-safe "
                         "compile+step coverage of every variant")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        tiny = LlamaConfig.tiny()
        probe("smoke-tiny", tiny, 2, 32, 3, multi_precision=False)
        sys.exit(0)
    only = os.environ.get("BIG_ONLY")
    steps = int(os.environ.get("BIG_STEPS", 24))
    if only in (None, "long"):
        probe("long-S8192", LONG, 1, 8192, steps, multi_precision=True)
    if only in (None, "big"):
        # fp32 masters don't fit at 1.59B — masterless + SR
        probe("big-1.59B", BIG, 1, 2048, steps, multi_precision=False)

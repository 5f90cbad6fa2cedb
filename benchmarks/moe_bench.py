"""MoE dispatch benchmark: tokens/s for an FFN stack — dense vs MoE
(einsum vs sort dispatch), expert-count and capacity-factor sweeps.

Iso-FLOPs comparison: a top-2 MoE applies 2 experts per token, so a
dense FFN of width F and a top-2 MoE with per-expert width F/2 spend
the same matmul FLOPs per token; the measured gap is routing overhead
(gate + dispatch/combine). The dense [N, E, C] mask costs O(N*E*C*H)
bandwidth and grows with E at fixed capacity_factor; the sort path is
O(N*k*H) + an O(N*k log) sort (moe.py MoELayer.dispatch_mode).

Methodology: K train steps (fwd+bwd+SGD) in ONE lax.scan dispatch via
jit.to_static multi_step, run-length differencing to cancel the host
dispatch+fetch cost (same as bench.py). Prints one JSON line per row.

``--cpu`` runs a TIMED sort-vs-einsum comparison at E=32 on the CPU
backend (sized up from the default off-TPU mechanics check, which is
too small to time): one measured point for the claim that sort
dispatch's O(N·k·H) traffic beats the dense mask's O(N·E·C·H) as E
grows — a count of bytes, not a device speed; the TPU sweep is not
measured.

ref: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
(the reference's NCCL all-to-all MoE layer; no published perf numbers).
"""
from __future__ import annotations

import json
import time

import numpy as np


def build_model(mode, h, f_dense, e, cf, layers, dispatch):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.fleet.meta_parallel.moe import MoELayer

    paddle.seed(0)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm = nn.LayerNorm(h)
            if mode == "dense":
                self.fc1 = nn.Linear(h, f_dense)
                self.fc2 = nn.Linear(f_dense, h)
                self.moe = None
            else:
                # iso-FLOPs: top-2 x (F/2)-wide experts == dense F
                self.moe = MoELayer(
                    d_model=h, d_hidden=f_dense // 2, num_experts=e,
                    top_k=2, capacity_factor=cf, dispatch_mode=dispatch)

        def forward(self, x):
            y = self.norm(x)
            if self.moe is None:
                import paddle_tpu.nn.functional as F

                y = self.fc2(F.gelu(self.fc1(y)))
            else:
                y = self.moe(y)
            return x + y

    class Stack(nn.Layer):
        def __init__(self):
            super().__init__()
            self.blocks = nn.LayerList([Block() for _ in range(layers)])

        def forward(self, x):
            aux = None
            for b in self.blocks:
                x = b(x)
                if b.moe is not None:
                    aux = b.moe.l_aux if aux is None else aux + b.moe.l_aux
            self._aux = aux
            return x

    return Stack()


def measure(model, batch_tokens, h, steps, on_tpu, ks=None):
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt

    opt = popt.SGD(learning_rate=1e-3, parameters=model.parameters())

    def step(x):
        out = model(x)
        loss = (out * out).mean()
        if getattr(model, "_aux", None) is not None:
            loss = loss + 0.01 * model._aux
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = paddle.jit.to_static(step, layers=[model], optimizers=[opt])
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch_tokens, 1, h).astype(np.float32))
    if on_tpu:
        x = x.astype("bfloat16")
        model.bfloat16()

    np.asarray(compiled(x)._data)  # create opt state / carry structure
    k1, k2 = ks if ks is not None else ((4, steps) if on_tpu else (1, 3))
    np.asarray(compiled.multi_step(x, steps=k1)._data)
    np.asarray(compiled.multi_step(x, steps=k2)._data)

    def timed(k):
        best = float("inf")
        for _ in range(3 if on_tpu else 1):
            t0 = time.perf_counter()
            loss = compiled.multi_step(x, steps=k)
            np.asarray(loss._data)
            best = min(best, time.perf_counter() - t0)
        return best

    dt = max(timed(k2) - timed(k1), 1e-9)
    return batch_tokens * (k2 - k1) / dt, 1000 * dt / (k2 - k1)


def cpu_dispatch_point():
    """The measured CPU point for the O(N·k·H)-vs-O(N·E·C·H) dispatch
    claim (round-5 verdict Next #8): einsum vs sort at E=32, sized so
    the timed region is dominated by dispatch work, not noise."""
    import jax

    dev = jax.devices()[0]
    H, F, TOKENS, LAYERS = 128, 512, 4096, 2
    E, CF = 32, 1.25
    results = {}
    for dispatch in ("einsum", "sort"):
        model = build_model("moe", H, F, E, CF, LAYERS, dispatch)
        tps, step_ms = measure(model, TOKENS, H, 0, False, ks=(2, 8))
        results[dispatch] = (tps, step_ms)
        print(json.dumps({
            "row": "moe_cpu_point", "e": E, "cf": CF, "dispatch": dispatch,
            "tokens_per_sec": round(tps, 1), "step_ms": round(step_ms, 3),
            "h": H, "f_dense": F, "tokens": TOKENS, "layers": LAYERS,
            "device": getattr(dev, "device_kind", str(dev)),
        }), flush=True)
    print(json.dumps({
        "row": "moe_cpu_sort_vs_einsum_speedup", "e": E,
        "value": round(results["sort"][0] / results["einsum"][0], 3),
        "unit": "x (sort tokens/s / einsum tokens/s)",
        "sort_faster": results["sort"][0] > results["einsum"][0],
    }), flush=True)


def main():
    import argparse

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="timed sort vs einsum dispatch at E=32 on CPU")
    if ap.parse_args().cpu:
        cpu_dispatch_point()
        return

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        H, F, TOKENS, LAYERS, STEPS = 1024, 5632, 8192, 4, 48
    else:  # mechanics check
        H, F, TOKENS, LAYERS, STEPS = 32, 64, 256, 2, 3

    rows = [
        ("dense", dict(e=0, cf=0.0, dispatch="-")),
        ("moe", dict(e=8, cf=1.25, dispatch="einsum")),
        ("moe", dict(e=8, cf=1.25, dispatch="sort")),
        ("moe", dict(e=32, cf=1.25, dispatch="einsum")),
        ("moe", dict(e=32, cf=1.25, dispatch="sort")),
        ("moe", dict(e=8, cf=1.0, dispatch="sort")),
        ("moe", dict(e=8, cf=2.0, dispatch="sort")),
    ]
    for mode, cfg in rows:
        model = build_model(mode, H, F, cfg["e"], cfg["cf"], LAYERS,
                            cfg["dispatch"])
        tps, step_ms = measure(model, TOKENS, H, STEPS, on_tpu)
        print(json.dumps({
            "row": mode, **cfg, "tokens_per_sec": round(tps, 1),
            "step_ms": round(step_ms, 3), "h": H, "f_dense": F,
            "tokens": TOKENS, "layers": LAYERS,
            "device": getattr(dev, "device_kind", str(dev)),
        }), flush=True)


if __name__ == "__main__":
    main()

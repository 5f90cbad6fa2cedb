"""Measured pipeline bubble fraction: V-sweep and microbatch sweep
(the "Pipeline bubble" table in pipeline_parallel.py's docstring). Run:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/pipeline_bubble_sweep.py

Model: utilization = M*V / T ticks where T = ((M-1)//S)*S*V + (V-1)*S
+ ((M-1)%S) + S; measured wall time per step vs the M*V useful ticks
gives the empirical bubble. (VERDICT #8: attach numbers to the
ZeroBubble refusal.)"""
import time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as popt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet.meta_parallel import LayerDesc, PipelineLayer, PipelineParallel


class Block(nn.Layer):
    def __init__(self, h):
        super().__init__()
        self.fc1 = nn.Linear(h, 4 * h)
        self.fc2 = nn.Linear(4 * h, h)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


S, H, MB = 4, 256, 8
rows = []
for V in (1, 2, 4):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"pp_degree": S}
    for M in (4, 8, 16, 32):
        strategy.pipeline_configs = {"accumulate_steps": M}
        hcg = fleet.init(strategy=strategy)
        paddle.seed(0)
        pipe = PipelineLayer(
            layers=[LayerDesc(Block, H) for _ in range(S * V)] + [nn.Linear(H, 8)],
            num_stages=S, num_virtual_pipeline_stages=V,
            loss_fn=lambda lo, y: F.cross_entropy(lo, y),
        )
        pp = PipelineParallel(pipe, hcg, strategy)
        opt = popt.SGD(learning_rate=0.01, parameters=pipe.parameters())
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(M * MB, H).astype(np.float32))
        y = paddle.to_tensor(rng.randint(0, 8, (M * MB,)).astype(np.int64))
        pp.train_batch((x, y), opt)  # compile
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            loss = pp.train_batch((x, y), opt)
            float(loss)
            best = min(best, time.perf_counter() - t0)
        T = ((M - 1) // S) * S * V + (V - 1) * S + ((M - 1) % S) + S
        sched_bubble = 1 - (M * V) / T
        rows.append((V, M, T, best * 1e3, best * 1e3 / (M * V), sched_bubble))
        import paddle_tpu.distributed as dist
        dist.destroy_process_group()
        fleet.set_hybrid_communicate_group(None)

print(f"{'V':>2} {'M':>3} {'ticks':>5} {'step_ms':>8} {'ms/chunk':>9} {'sched_bubble':>12}")
for V, M, T, ms, mpc, bub in rows:
    print(f"{V:>2} {M:>3} {T:>5} {ms:>8.1f} {mpc:>9.2f} {bub:>12.3f}")

# empirical bubble: per-useful-chunk time inflation vs the V,M -> inf limit
base = {V: min(r[4] for r in rows if r[0] == V) for V in (1, 2, 4)}
print("\nempirical bubble (1 - best_ms_per_chunk / ms_per_chunk):")
for V, M, T, ms, mpc, bub in rows:
    print(f"V={V} M={M}: measured {1 - base[V]/mpc:.3f} vs schedule model {bub - min(rr[5] for rr in rows if rr[0]==V):.3f} (rel)")

"""Fault-tolerant-training recovery bench: how long a
killed-and-relaunched rank takes to get back to training, RAM tier vs
disk tier.

Three measured columns over the same model state:

- **snapshot overhead** — what one in-RAM snapshot costs the train
  thread (reference capture; no serialization) and what one peer
  publish costs end to end (serialize + CRC frame + store put);
- **RAM-tier recovery** — a fresh process-equivalent rig restoring
  from the peer-replicated snapshot: ``resume()`` fetch + verify +
  deserialize + rebind;
- **disk-tier recovery** — the same rig restoring from the newest
  ``AutoCheckpoint`` directory (scan + CRC verify + unpickle + rebind).

The point of the two-tier design is the ratio: peer RAM must be
decisively cheaper than disk for the Gemini-style architecture to pay
its replication cost. On this CPU harness the store is in-process
(MemKVStore) so the RAM column is an upper bound on protocol overhead,
not a network measurement — the multi-host column (TCP store, real
pod) is not measured.

``--model`` picks mlp (default, instant) or llama (LlamaConfig.tiny —
a transformer-shaped state dict). ``--steps``/``--interval`` shape the
run. Emits one JSON line per row plus a summary table.

Run: PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/trainfault_bench.py
"""
import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np

# the sharded column needs a 2-way mesh; force host vdevs before the
# first jax backend query (no-op when a harness already set the flag)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2"
                               ).strip()

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as popt
from paddle_tpu.distributed.store import MemKVStore
from paddle_tpu.incubate.checkpoint.auto_checkpoint import AutoCheckpoint
from paddle_tpu.training import PeerReplicator, TrainingSupervisor

ap = argparse.ArgumentParser()
ap.add_argument("--model", choices=["mlp", "llama"], default="mlp")
ap.add_argument("--steps", type=int, default=20)
ap.add_argument("--interval", type=int, default=5)
ap.add_argument("--repeat", type=int, default=5,
                help="recovery timing repetitions (median reported)")
args = ap.parse_args()


def build(ckpt_dir=None, store=None, tag="bench"):
    paddle.seed(0)
    if args.model == "llama":
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        model = LlamaForCausalLM(LlamaConfig.tiny())
        vocab = model.config.vocab_size

        def step_fn(batch):
            x = paddle.to_tensor(batch)
            logits = model(x)
            loss = F.cross_entropy(
                logits[:, :-1].reshape([-1, vocab]),
                paddle.to_tensor(batch[:, 1:].reshape(-1)))
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.RandomState(7)
        data = [rng.randint(0, vocab, (2, 32)).astype(np.int64)
                for _ in range(64)]
    else:
        model = nn.Sequential(nn.Linear(64, 256), nn.ReLU(),
                              nn.Linear(256, 64))

        def step_fn(batch):
            x, y = paddle.to_tensor(batch[0]), paddle.to_tensor(batch[1])
            loss = F.mse_loss(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.RandomState(7)
        data = [(rng.randn(16, 64).astype(np.float32),
                 rng.randn(16, 64).astype(np.float32))
                for _ in range(64)]
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def batch_fn(i):
        return data[(i - 1) % len(data)]

    ac = None
    if ckpt_dir is not None:
        ac = AutoCheckpoint(ckpt_dir, layers=[model], optimizers=[opt],
                            save_interval_steps=args.interval,
                            async_save=False)
    peer = PeerReplicator(store, 0, 1, tag=tag) if store is not None \
        else None
    return TrainingSupervisor(
        step_fn, batch_fn, layers=[model], optimizers=[opt],
        snapshot_interval=args.interval, peer=peer, auto_checkpoint=ac)


def build_sharded(ckpt_dir=None, store=None, tag="bench_sh"):
    """The pod-scale rig (ISSUE 16): stage-``os`` group-sharded
    optimizer state over a ("sharding", 2) mesh, supervisor in
    ``sharded_state`` mode — the peer tier ships per-rank SHARD
    payloads through ``distributed/checkpoint/reshard`` (gather +
    coverage-checked combine on resume) instead of one whole-state
    pickle, while the disk tier stays whole-state AutoCheckpoint."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.distributed.collective import Group
    from paddle_tpu.distributed.sharding import group_sharded_parallel

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(64, 256), nn.ReLU(),
                          nn.Linear(256, 64))
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    mesh = Mesh(np.array(jax.devices()[:2]), ("sharding",))
    model, opt, _ = group_sharded_parallel(
        model, opt, "os", group=Group([0, 1], "sharding", mesh=mesh))

    def step_fn(batch):
        x, y = paddle.to_tensor(batch[0]), paddle.to_tensor(batch[1])
        loss = F.mse_loss(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(7)
    data = [(rng.randn(16, 64).astype(np.float32),
             rng.randn(16, 64).astype(np.float32))
            for _ in range(64)]

    def batch_fn(i):
        return data[(i - 1) % len(data)]

    ac = None
    if ckpt_dir is not None:
        ac = AutoCheckpoint(ckpt_dir, layers=[model], optimizers=[opt],
                            save_interval_steps=args.interval,
                            async_save=False)
    peer = PeerReplicator(store, 0, 1, tag=tag) if store is not None \
        else None
    return TrainingSupervisor(
        step_fn, batch_fn, layers=[model], optimizers=[opt],
        snapshot_interval=args.interval, peer=peer, auto_checkpoint=ac,
        sharded_state=True,
        state_layout={"world": 1, "mesh": {"sharding": 2}})


# headline value per row kind — what the regression sentinel grades
# (all are latencies: down-is-good polarity from the _s suffix)
_ROW_HEADLINE = {"overhead": "step_s", "recovery": "ram_tier_s",
                 "sharded_recovery": "ram_tier_s"}


def emit(row):
    """One framed row through the shared obs ledger writer (ISSUE 15):
    the ``BENCH_ROW {json}`` stdout contract is unchanged (every row
    key stays top-level); the record also lands in BENCH_LEDGER."""
    from paddle_tpu.obs.regress import bench_record

    kind = row.get("row", "row")
    headline = _ROW_HEADLINE.get(kind)
    bench_record(row.get("bench", "trainfault"),
                 f"trainfault_{kind}_{headline}" if headline else
                 f"trainfault_{kind}",
                 row.get(headline) if headline else None,
                 "s", line_prefix="BENCH_ROW ",
                 **{k: v for k, v in row.items() if k != "bench"})


def main():
    scratch = tempfile.mkdtemp(prefix="trainfault_bench_")
    store = MemKVStore()
    try:
        sup = build(ckpt_dir=scratch, store=store)
        n_params = sum(
            int(np.prod(p.shape)) for p in sup.layers[0].parameters())

        # steady-state step time (for context) + snapshot overheads
        t0 = time.perf_counter()
        sup.run(args.steps)
        step_s = (time.perf_counter() - t0) / args.steps
        t0 = time.perf_counter()
        sup._take_snapshot(args.steps)
        sup.peer.drain()
        snap_plus_publish_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = sup._capture(args.steps)
        capture_s = time.perf_counter() - t0
        payload = sup._serialize(state)
        emit({"bench": "trainfault", "row": "overhead",
              "model": args.model, "params": n_params,
              "step_s": round(step_s, 6),
              "ram_capture_s": round(capture_s, 6),
              "snapshot_plus_peer_publish_s":
                  round(snap_plus_publish_s, 6),
              "payload_bytes": len(payload)})

        # recovery timings: fresh rig each repetition, like a relaunch
        def timed_resume(**kw):
            rig = build(**kw)
            t0 = time.perf_counter()
            start = rig.resume()
            dt = time.perf_counter() - t0
            assert start == args.steps + 1, (start, kw)
            return dt

        ram = sorted(timed_resume(store=store) for _ in range(args.repeat))
        disk = sorted(timed_resume(ckpt_dir=scratch)
                      for _ in range(args.repeat))
        ram_s = ram[len(ram) // 2]
        disk_s = disk[len(disk) // 2]
        emit({"bench": "trainfault", "row": "recovery",
              "model": args.model, "params": n_params,
              "ram_tier_s": round(ram_s, 6),
              "disk_tier_s": round(disk_s, 6),
              "disk_over_ram": round(disk_s / max(ram_s, 1e-9), 2)})
        print(f"\n{args.model} ({n_params:,} params): "
              f"step {step_s * 1e3:.2f} ms | RAM capture "
              f"{capture_s * 1e6:.0f} us | peer publish (sync) "
              f"{snap_plus_publish_s * 1e3:.2f} ms | payload "
              f"{len(payload) / 1e6:.2f} MB")
        print(f"recovery: RAM tier {ram_s * 1e3:.2f} ms vs disk tier "
              f"{disk_s * 1e3:.2f} ms ({disk_s / max(ram_s, 1e-9):.1f}x)")
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    # sharded kill-and-resume column (ISSUE 16): same two tiers, but
    # the state is group-sharded and the RAM tier restores through the
    # reshard gather/combine path — the shape the pod-scale elastic
    # resume (tests/test_elastic_shard.py) exercises across real
    # process boundaries
    sh_scratch = tempfile.mkdtemp(prefix="trainfault_sh_")
    sh_store = MemKVStore()
    try:
        from paddle_tpu.distributed.checkpoint import reshard

        sup = build_sharded(ckpt_dir=sh_scratch, store=sh_store)
        sup.run(args.steps)
        sup._take_snapshot(args.steps)
        sup.peer.drain()
        payload = sup._serialize(sup._capture(args.steps))
        n_sharded = reshard.sharded_leaf_count(payload)

        def timed_sharded(**kw):
            rig = build_sharded(**kw)
            t0 = time.perf_counter()
            start = rig.resume()
            dt = time.perf_counter() - t0
            assert start == args.steps + 1, (start, kw)
            return dt

        ram = sorted(timed_sharded(store=sh_store)
                     for _ in range(args.repeat))
        disk = sorted(timed_sharded(ckpt_dir=sh_scratch)
                      for _ in range(args.repeat))
        ram_s = ram[len(ram) // 2]
        disk_s = disk[len(disk) // 2]
        emit({"bench": "trainfault", "row": "sharded_recovery",
              "model": "mlp", "shard_degree": 2,
              "sharded_leaves": n_sharded,
              "payload_bytes": len(payload),
              "ram_tier_s": round(ram_s, 6),
              "disk_tier_s": round(disk_s, 6),
              "disk_over_ram": round(disk_s / max(ram_s, 1e-9), 2)})
        print(f"sharded recovery (os over 2-way mesh, {n_sharded} "
              f"sharded leaves): RAM tier {ram_s * 1e3:.2f} ms vs disk "
              f"tier {disk_s * 1e3:.2f} ms "
              f"({disk_s / max(ram_s, 1e-9):.1f}x)")
    finally:
        shutil.rmtree(sh_scratch, ignore_errors=True)


if __name__ == "__main__":
    main()

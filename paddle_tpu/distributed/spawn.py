"""paddle.distributed.spawn (ref: python/paddle/distributed/spawn.py).

Single-node multi-process launcher: forks ``nprocs`` Python processes
each running ``func(*args)`` with the rank env set. On TPU hardware one
process drives all chips, so nprocs defaults to 1; nprocs>1 is the
CPU TEST topology: each child gets JAX_PLATFORMS=cpu (said in a log
line), and JAX_PLATFORMS=tpu with nprocs>1 is refused — no chips are
mapped to ranks, so the children would fight over the same chip.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import sys
from typing import Optional, Sequence

__all__ = ["spawn"]


def _worker(func, args, rank: int, nprocs: int, env: dict):
    os.environ.update(env)
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["PADDLE_LOCAL_RANK"] = str(rank)
    func(*args)


def require_cpu_topology(nprocs: int, who: str) -> None:
    """Several processes on one host: refuse the TPU, and say that the
    ranks are pinned to the CPU (shared with ``distributed.launch``)."""
    want = os.environ.get("JAX_PLATFORMS", "")
    if "tpu" in want.lower().split(","):
        raise RuntimeError(
            f"{who}: {nprocs} processes on one host with "
            f"JAX_PLATFORMS={want!r} refused — that is the CPU test "
            "topology; no chips are mapped to ranks, so they would all "
            "open the same chip. Use one process on a TPU host (it "
            "drives every local chip).")
    print(f"[{who}] {nprocs} processes on one host is the CPU test "
          "topology: every rank runs with JAX_PLATFORMS=cpu",
          file=sys.stderr)


def spawn(func, args=(), nprocs: int = 1, join: bool = True,
          daemon: bool = False, **options):
    """ref: spawn.py spawn — returns the context (list of processes)
    when join=False, else joins and raises on child failure."""
    env = {}
    if nprocs > 1:
        require_cpu_topology(nprocs, "spawn")
        env["JAX_PLATFORMS"] = "cpu"
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(
            target=_worker, args=(func, tuple(args), rank, nprocs, env),
            daemon=daemon,
        )
        p.start()
        procs.append(p)
    if not join:
        return procs
    for p in procs:
        p.join()
    failed = [(i, p.exitcode) for i, p in enumerate(procs) if p.exitcode != 0]
    if failed:
        raise RuntimeError(f"spawned processes failed (rank, exitcode): {failed}")
    return procs

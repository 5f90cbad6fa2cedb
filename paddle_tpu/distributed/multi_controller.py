"""Multi-controller execution: one Python process per host.

The single-controller model (one process drives the whole mesh, GSPMD
inserts collectives) covers a v5p pod driven from one host. A REAL pod
is multi-controller: every host runs the same program and JAX's
coordination service (the TCPStore/rendezvous equivalent, SURVEY §5.8)
stitches the per-host device sets into one global mesh. The reference
proves this path by spawning actual trainer processes and comparing
losses (ref: test/legacy_test/test_dist_base.py:952,
test/collective/test_communication_api_base.py:28); this module is the
framework-side half of that contract:

- :func:`initialize_from_env` — calls ``jax.distributed.initialize``
  from the env the launcher (``distributed/launch``) wires
  (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
  ``JAX_PROCESS_ID``, with the reference's ``PADDLE_MASTER`` /
  ``PADDLE_TRAINERS_NUM`` / ``PADDLE_GLOBAL_RANK`` as fallbacks).
  ``init_parallel_env`` calls it first, so a launcher-started worker
  needs no direct jax.distributed use (ref:
  python/paddle/distributed/parallel.py:957 init_parallel_env's
  TCPStore + init_gloo bring-up).
- eager trainer-level collectives — outside jit, each process holds
  only its local value; a collective here builds a global array over a
  one-device-per-process ``world`` mesh
  (``jax.make_array_from_process_local_data``), runs the XLA collective
  under a jitted ``shard_map`` (gloo on CPU hosts, ICI/DCN on TPU), and
  returns the result fully replicated so every process can read it.
  This is what ``dist.all_reduce(t)`` means between real trainer
  processes (the reference's gloo/NCCL eager path,
  ref: python/paddle/distributed/communication/all_reduce.py).

Contract (same as every multi-controller framework): all processes
must reach the same collective calls in the same order; shapes and
dtypes must match across processes.
"""
from __future__ import annotations

import functools
import os
import pickle
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "initialize_from_env",
    "active",
    "process_mesh",
    "eager_all_reduce",
    "eager_all_gather",
    "eager_broadcast",
    "eager_p2p",
    "eager_ppermute",
    "eager_send",
    "eager_recv",
    "eager_all_gather_object",
]

_initialized_here = False


def initialize_from_env(force: bool = False) -> bool:
    """Bring up JAX's coordination service from launcher-set env.

    Returns True when a multi-process runtime is (now) active. No-op
    for single-process runs and when already initialized. Reads
    ``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``
    (set by ``paddle_tpu.distributed.launch``) with the reference's
    ``PADDLE_MASTER``/``PADDLE_TRAINERS_NUM``/``PADDLE_GLOBAL_RANK``
    accepted as fallbacks.
    """
    global _initialized_here
    if _initialized_here and not force:
        return True
    if jax.distributed.is_initialized():
        # the worker brought the service up itself (the previously
        # documented contract) — honor it rather than double-initialize
        _initialized_here = True
        return True
    nproc = int(
        os.environ.get("JAX_NUM_PROCESSES")
        or os.environ.get("PADDLE_TRAINERS_NUM")
        or "1"
    )
    if nproc <= 1:
        return False
    coord = (
        os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("PADDLE_MASTER")
    )
    if not coord:
        raise RuntimeError(
            "multi-process run (JAX_NUM_PROCESSES="
            f"{nproc}) without JAX_COORDINATOR_ADDRESS/PADDLE_MASTER; "
            "start workers via paddle_tpu.distributed.launch"
        )
    pid = int(
        os.environ.get("JAX_PROCESS_ID")
        or os.environ.get("PADDLE_GLOBAL_RANK")
        or "0"
    )
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=nproc, process_id=pid
    )
    _initialized_here = True
    return True


def active() -> bool:
    """True when more than one controller participates in the mesh."""
    return jax.process_count() > 1


@functools.lru_cache(maxsize=1)
def process_mesh() -> Mesh:
    """The ``(world, local)`` carrier mesh for trainer-level eager
    collectives: axis 0 is the process rank, axis 1 that process's
    local devices. Using ALL devices (not one per process) matters —
    interleaving executables over a device subset with later full-mesh
    programs confuses XLA-CPU's gloo pair bookkeeping (observed as
    'Received data size doesn't match expected size' in the NEXT
    program); keeping every multi-process executable on the full device
    set avoids it, and on a real pod it means the control-plane
    collectives ride the same ICI links as compute."""
    per = {}
    for d in jax.devices():
        per.setdefault(d.process_index, []).append(d)
    rows = [per[i] for i in sorted(per)]
    width = min(len(r) for r in rows)
    return Mesh(np.array([r[:width] for r in rows]), ("world", "local"))


def _global_input(x) -> jax.Array:
    """[nproc, *x.shape] global array: slot p holds process p's value
    (replicated across p's local devices)."""
    x = np.asarray(x)
    mesh = process_mesh()
    sh = NamedSharding(mesh, PartitionSpec("world"))
    return jax.make_array_from_process_local_data(
        sh, x[None], (jax.process_count(), *x.shape)
    )


@functools.lru_cache(maxsize=256)
def _compiled(kind: str, shape, dtype, extra):
    """One jitted shard_map per (collective, shape, dtype, params)."""
    mesh = process_mesh()
    spec = PartitionSpec("world")

    def body(lx):
        v = lx[0]  # this process's slot
        if kind == "sum":
            return lax.psum(v, "world")
        if kind == "max":
            return lax.pmax(v, "world")
        if kind == "min":
            return lax.pmin(v, "world")
        if kind == "prod":
            return jnp.prod(lax.all_gather(v, "world"), axis=0)
        if kind == "avg":
            return lax.pmean(v, "world")
        if kind == "gather":
            return lax.all_gather(v, "world")
        if kind == "bcast":
            return lax.all_gather(v, "world")[extra]
        if kind == "p2p":
            src, dst = extra
            moved = lax.ppermute(v, "world", perm=[(src, dst)])
            return lax.all_gather(moved, "world")
        if kind == "perm":
            moved = lax.ppermute(v, "world", perm=list(extra))
            return lax.all_gather(moved, "world")
        raise ValueError(kind)

    # check_vma=False: all_gather/ppermute outputs ARE replicated but
    # the static varying-manual-axes check cannot infer it
    fn = jax.shard_map(body, mesh=mesh, in_specs=spec,
                    out_specs=PartitionSpec(), check_vma=False)
    return jax.jit(fn)


_RECORD_OPS = {
    "sum": "all_reduce[sum]", "max": "all_reduce[max]",
    "min": "all_reduce[min]", "prod": "all_reduce[prod]",
    "avg": "all_reduce[avg]", "gather": "all_gather",
    "bcast": "broadcast", "p2p": "p2p_sendrecv", "perm": "ppermute",
}


def _record(op: str, x=None, peer=None, detail: str = "") -> None:
    """Append a signature to the collective flight recorder BEFORE the
    op executes (issue order is what the cross-rank contract and the
    watchdog's hang dump compare; recording first means a hang still
    shows the op this rank is stuck in)."""
    from .communication import flight_recorder as _fr

    shape: tuple = ()
    dtype = ""
    if x is not None:
        # read metadata off the array when it has it — np.asarray on a
        # device array would materialize the whole buffer to host just
        # for .shape/.dtype
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            shape, dtype = tuple(x.shape), str(x.dtype)
        else:
            a = np.asarray(x)
            shape, dtype = tuple(a.shape), str(a.dtype)
    _fr.record(op, shape=shape, dtype=dtype, group="world", peer=peer,
               detail=detail)


def _run(kind: str, x, extra=None) -> np.ndarray:
    x = np.asarray(x)
    _record(_RECORD_OPS.get(kind, kind), x,
            detail="" if extra is None else f"extra={extra}")
    out = _compiled(kind, x.shape, str(x.dtype), extra)(_global_input(x))
    return np.asarray(out)  # fully replicated → readable on every host


def eager_all_reduce(x, op_kind: str) -> np.ndarray:
    """op_kind in {sum, max, min, prod, avg}; returns the reduced value."""
    return _run(op_kind, x)


def eager_all_gather(x) -> np.ndarray:
    """[nproc, *x.shape] — rank order."""
    return _run("gather", x)


def eager_broadcast(x, src: int) -> np.ndarray:
    return _run("bcast", x, extra=int(src))


def eager_ppermute(x, perm) -> np.ndarray:
    """[nproc, ...] post-permute view (callers index their own slot);
    all processes must pass the same perm."""
    return _run("perm", x, extra=tuple((int(a), int(b)) for a, b in perm))


def eager_p2p(x, src: int, dst: int) -> np.ndarray:
    """The value process ``src`` holds lands at ``dst``; returns the
    post-transfer [nproc, ...] view (callers index their own slot).
    Both endpoints (and only they need meaningful data) must call this
    with the same (src, dst)."""
    return _run("p2p", x, extra=(int(src), int(dst)))


# -- true point-to-point (coordination-service KV store) ----------------
# The mesh collectives above require EVERY process to participate; the
# reference's send/recv contract involves only the two endpoints (a
# bystander rank 2 must be free to proceed). These ride the coordination
# service's key-value store — the TCPStore equivalent — so they are
# genuine p2p. Per-(src,dst) sequence counters keep repeated transfers
# matched; both endpoints advance their own copy of the pair counter.
_p2p_seq: dict = {}


def _kv_client():
    from jax._src import distributed as _dist

    client = _dist.global_state.client
    if client is None:
        raise RuntimeError(
            "coordination service not initialized; call "
            "init_parallel_env() (jax.distributed.initialize) first")
    return client


def eager_send(x, dst: int) -> None:
    me = jax.process_index()
    _record("send", x, peer=int(dst))
    seq = _p2p_seq[(me, dst)] = _p2p_seq.get((me, dst), 0) + 1
    arr = np.ascontiguousarray(np.asarray(x))
    _kv_client().key_value_set_bytes(
        f"ptpu_p2p/{me}/{dst}/{seq}", pickle.dumps(arr))


def eager_recv(src: int, timeout_ms: int = 600_000,
               deadline=None) -> np.ndarray:
    """``deadline`` (seconds or a utils.retries.Deadline) caps the wait
    below ``timeout_ms`` — callers splitting one job budget across a
    recv sequence thread it here and the blocking get can never
    outlive it (the DDL001 discipline)."""
    me = jax.process_index()
    if deadline is not None:
        from ..utils.retries import Deadline

        dl = Deadline.coerce(deadline)
        dl.check(f"eager_recv(src={src})")
        timeout_ms = int(min(float(timeout_ms),
                             dl.timeout(timeout_ms / 1000.0) * 1000.0))
    _record("recv", peer=int(src))
    # the pair counter commits only AFTER a successful receive: a
    # timed-out get followed by a retry must wait on the SAME seq the
    # sender published, not permanently skip past it (pair desync)
    seq = _p2p_seq.get((src, me), 0) + 1
    key = f"ptpu_p2p/{src}/{me}/{seq}"
    client = _kv_client()
    payload = client.blocking_key_value_get_bytes(key, timeout_ms)
    _p2p_seq[(src, me)] = seq
    client.key_value_delete(key)
    return pickle.loads(payload)


def eager_all_gather_object(obj) -> list:
    """Pickle-based object gather (ref: all_gather_object): two rounds —
    gather byte lengths, pad to max, gather payloads, unpickle."""
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    lengths = eager_all_gather(np.array([payload.size], np.int64))[:, 0]
    width = int(lengths.max())
    padded = np.zeros(width, np.uint8)
    padded[: payload.size] = payload
    rows = eager_all_gather(padded)
    return [
        pickle.loads(rows[r, : int(lengths[r])].tobytes())
        for r in range(rows.shape[0])
    ]

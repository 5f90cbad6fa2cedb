"""Parallel environment + DataParallel.

TPU-native redesign of the reference's dygraph parallel runtime
(ref: python/paddle/distributed/parallel.py:207 DataParallel, :957
init_parallel_env). On TPU there is no per-rank process + NCCL reducer:
one controller drives a device mesh and GSPMD inserts the gradient
all-reduce when inputs are sharded over the ``dp`` axis and parameters
are replicated. DataParallel therefore reduces to (a) replicating
parameters on the mesh, (b) constraining input/activation sharding to
the dp axis, and (c) keeping the reference's API (scale_loss, no_sync,
state_dict passthrough) so user code ports unchanged. The bucketed
EagerReducer (ref: collective/reducer.cc) has no equivalent because XLA
already fuses/schedules gradient collectives.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..base.tensor import Tensor
from . import collective as _collective
from .collective import Group, init_default_group, is_initialized


class ParallelEnv:
    """Env-derived parallel info (ref: parallel.py ParallelEnv)."""

    def __init__(self):
        self.rank = jax.process_index()
        self.world_size = jax.process_count()
        self.device_id = 0
        self.nranks = self.world_size
        self.local_rank = self.rank
        self.trainer_endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")
        self.current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def dev_id(self):
        return self.device_id


def init_parallel_env(mesh: Optional[jax.sharding.Mesh] = None) -> Group:
    """Initialize the default process group over the device mesh.

    Multi-host: when the worker was started by
    ``paddle_tpu.distributed.launch`` (or the reference's env surface is
    present), this first brings up JAX's coordination service — the
    TCPStore/rendezvous equivalent (SURVEY §5.8) — via
    ``multi_controller.initialize_from_env``; then every host sees the
    global mesh and this returns the world group
    (ref: python/paddle/distributed/parallel.py:957 init_parallel_env).
    """
    from . import multi_controller as _mc

    _mc.initialize_from_env()
    if not is_initialized():
        init_default_group(mesh)
    return _collective._get_global_group()


def get_world_size(group: Optional[Group] = None) -> int:
    """World size in the unit the active mode's collectives use:
    multi-controller → TRAINER (process) count, matching the eager
    collectives and the reference (world_size == number of trainer
    processes); single-controller → device count (each device is an
    SPMD rank). Passing the DEFAULT (world) group explicitly reports
    the same unit as passing no group — the two spellings must never
    disagree (2 vs 4 in a 2-process x 2-device run). A non-default
    subgroup still reports its device-level ``nranks``."""
    from . import multi_controller as _mc

    if group is not None:
        if _mc.active() and _is_default_group(group):
            return jax.process_count()
        return group.nranks
    if _mc.active():
        return jax.process_count()
    if is_initialized():
        return _collective._get_global_group().nranks
    return jax.device_count()


def _is_default_group(group: Group) -> bool:
    if not is_initialized():
        return False
    try:
        return group is _collective._get_global_group()
    except Exception:  # noqa: BLE001 — no global group yet
        return False


def get_rank(group: Optional[Group] = None) -> int:
    """Host-side rank (process index). The per-shard SPMD rank inside a
    trace is ``communication.get_rank_in_trace``."""
    if group is not None:
        return group.rank
    return jax.process_index()


def shard_map(fn, mesh=None, in_specs=None, out_specs=None, check_vma=False):
    """Run ``fn`` SPMD over the mesh with Tensor-aware in/outs.

    The TPU-native equivalent of launching one process per rank: inside
    ``fn`` every paddle_tpu op sees the per-shard local view and the
    collective API (all_reduce, ...) is live on the mesh axes.
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        g = init_parallel_env()
        mesh = g.mesh

    def wrapped(*arrs):
        ins = [Tensor(a, _internal=True) for a in arrs]
        out = fn(*ins)
        return jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else t,
            out,
            is_leaf=lambda x: isinstance(x, Tensor),
        )

    smapped = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=in_specs if in_specs is not None else P(mesh.axis_names[0]),
        out_specs=out_specs if out_specs is not None else P(mesh.axis_names[0]),
        check_vma=check_vma,
    )

    def call(*tensors):
        arrs = [t._data if isinstance(t, Tensor) else jnp.asarray(t) for t in tensors]
        out = smapped(*arrs)
        return jax.tree_util.tree_map(lambda a: Tensor(a, _internal=True), out)

    return call


class DataParallel:
    """paddle.DataParallel parity (ref: parallel.py:207).

    Wraps a Layer: parameters are replicated over the dp mesh axis and
    inputs get a dp-sharding constraint, so under jit GSPMD computes
    per-shard grads and all-reduces them — semantically identical to the
    reference's bucketed allreduce, scheduled by XLA instead of hooks.
    """

    def __init__(
        self,
        layers,
        strategy=None,
        comm_buffer_size: int = 25,
        last_comm_buffer_size: int = 1,
        find_unused_parameters: bool = False,
        group: Optional[Group] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        dp_axis: Optional[str] = None,
    ):
        self._layers = layers
        self.find_unused_parameters = find_unused_parameters
        self.group = group if group is not None else init_parallel_env(mesh)
        self.mesh = mesh if mesh is not None else self.group.mesh
        self.dp_axis = dp_axis or self.group.axis_name
        self._grad_sync_enabled = True
        self._replicate_params()

    # -- parameter placement ------------------------------------------
    def _replicate_params(self):
        """Broadcast params across dp ranks (ref: parallel.py
        sync_params_buffers) = replicated NamedSharding on the mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.mesh is None or np.prod(list(self.mesh.shape.values())) == 1:
            return
        repl = NamedSharding(self.mesh, P())
        for p in self._layers.parameters():
            if isinstance(p._data, jax.Array) and not isinstance(p._data, jax.core.Tracer):
                p._data = jax.device_put(p._data, repl)
        for _, b in self._layers.named_buffers():
            if isinstance(b._data, jax.Array) and not isinstance(b._data, jax.core.Tracer):
                b._data = jax.device_put(b._data, repl)

    def _shard_input(self, t: Tensor) -> Tensor:
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.dp_axis, *([None] * (t.ndim - 1))) if t.ndim else P()
        sh = NamedSharding(self.mesh, spec)
        if isinstance(t._data, jax.core.Tracer):
            from ..base import tape

            return tape.apply(lambda x: jax.lax.with_sharding_constraint(x, sh), t, op_name="dp_shard")
        return Tensor(jax.device_put(t._data, sh), stop_gradient=t.stop_gradient, _internal=True)

    def forward(self, *inputs, **kwargs):
        if self.mesh is not None and np.prod(list(self.mesh.shape.values())) > 1:
            inputs = tuple(
                self._shard_input(x) if isinstance(x, Tensor) else x for x in inputs
            )
        return self._layers(*inputs, **kwargs)

    __call__ = forward

    # -- reference API passthrough ------------------------------------
    def scale_loss(self, loss):
        """Grad averaging happens via mean-loss over the global batch;
        identity, kept for API parity."""
        return loss

    def apply_collective_grads(self):
        pass  # GSPMD inserts the collectives

    @contextlib.contextmanager
    def no_sync(self):
        """Within this context grads accumulate locally (parity; under
        GSPMD each microbatch grad is already a global mean, so local
        accumulation is the same arithmetic)."""
        self._grad_sync_enabled = False
        try:
            yield
        finally:
            self._grad_sync_enabled = True

    def parameters(self, include_sublayers=True):
        return self._layers.parameters(include_sublayers)

    def named_parameters(self, prefix="", include_sublayers=True):
        return self._layers.named_parameters(prefix, include_sublayers)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)

    load_dict = set_state_dict

    def train(self):
        self._layers.train()
        return self

    def eval(self):
        self._layers.eval()
        return self

    def __getattr__(self, name):
        return getattr(self.__dict__["_layers"], name)

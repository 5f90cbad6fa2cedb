"""Tensor-parallel (model-parallel) layers.

TPU-native redesign of the reference's mpu layers
(ref: python/paddle/distributed/fleet/layers/mpu/mp_layers.py:47
VocabParallelEmbedding, :334 ColumnParallelLinear, :541
RowParallelLinear, :742 ParallelCrossEntropy). The reference splits the
weight across ranks and hand-codes identity/allreduce PyLayers
(mp_ops.py); here each layer holds the FULL logical weight annotated
with a GSPMD sharding over the ``mp`` mesh axis — XLA partitions the
matmul and inserts the all-reduce/all-gather on ICI. Numerics are
therefore bit-identical to the serial layer by construction, and the
collective schedule is the compiler's (overlapped), not hook-driven.

The ``tp_axis`` parameter metadata is the contract with distributed
wrappers/FSDP placement (consumed by TensorParallel and
__graft_entry__.dryrun_multichip).
"""
from __future__ import annotations

from typing import Optional

import jax

import paddle_tpu.nn as nn
from paddle_tpu.nn import functional as F


def _resolve_mesh_axis(mp_group):
    """(mesh, axis_name) from an explicit group or the active HCG."""
    from ...base.topology import get_hybrid_communicate_group

    if mp_group is not None:
        return mp_group.mesh, mp_group.axis_name
    hcg = get_hybrid_communicate_group()
    if hcg is not None:
        return hcg.mesh, "mp"
    return None, None


def _constrain(t, mesh, spec):
    """Apply a GSPMD sharding constraint through the tape (differentiable,
    works eagerly and under jit).

    Resolved lazily against the CURRENT abstract mesh when one is active
    (e.g. inside the pipeline's partial-manual shard_map, where dp/pp are
    Manual and mp stays Auto) so the constraint's mesh axis types always
    match the context; falls back to the layer's concrete mesh."""
    if mesh is None:
        return t
    from paddle_tpu.base import tape

    def f(x):
        am = jax.sharding.get_abstract_mesh()
        use = mesh if am.empty else am
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(use, spec)
        )

    return tape.apply(f, t, op_name="sharding_constraint")


def mark_as_sequence_parallel_parameter(param):
    param.is_sequence_parallel = True


class _MpLayerBase:
    """Mixin resolving the mp mesh/axis once at construction."""

    def _init_mp(self, mp_group):
        self.model_parallel_group = mp_group
        self._mesh, self._mp_axis = _resolve_mesh_axis(mp_group)
        self.world_size = (
            mp_group.nranks
            if mp_group is not None
            else (self._mesh.shape[self._mp_axis] if self._mesh is not None else 1)
        )
        self.is_mp = self.world_size > 1


class VocabParallelEmbedding(nn.Layer, _MpLayerBase):
    """Embedding with the vocab dim sharded over mp (ref: mp_layers.py:47)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self._init_mp(mp_group)
        if self.is_mp and num_embeddings % self.world_size != 0:
            raise ValueError(
                f"vocab size {num_embeddings} must divide mp degree {self.world_size}"
            )
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr
        )
        self.weight.tp_axis = 0
        self.weight.is_distributed = self.is_mp

    def forward(self, x):
        out = F.embedding(x, self.weight)
        if self.is_mp:
            out = _constrain(
                out, self._mesh, jax.sharding.PartitionSpec()
            )  # gathered/replicated activations (reference allreduces masked partials)
        return out


class ColumnParallelLinear(nn.Layer, _MpLayerBase):
    """Linear with out_features sharded over mp (ref: mp_layers.py:334).

    gather_output=False leaves the activation mp-sharded on the last dim
    (feeding a RowParallelLinear); True replicates it (XLA all-gather).
    """

    def __init__(
        self,
        in_features,
        out_features,
        weight_attr=None,
        has_bias=None,
        gather_output=True,
        fuse_matmul_bias=False,
        mp_group=None,
        name=None,
    ):
        super().__init__()
        self._init_mp(mp_group)
        if self.is_mp and out_features % self.world_size != 0:
            raise ValueError(
                f"out_features {out_features} must divide mp degree {self.world_size}"
            )
        self.gather_output = gather_output
        self.weight = self.create_parameter(shape=[in_features, out_features], attr=weight_attr)
        self.weight.tp_axis = 1
        self.weight.is_distributed = self.is_mp
        self.bias = None
        if has_bias:  # reference treats None as falsy (mp_layers.py:386)
            self.bias = self.create_parameter(shape=[out_features], is_bias=True)
            self.bias.tp_axis = 0
            self.bias.is_distributed = self.is_mp

    def forward(self, x):
        from jax.sharding import PartitionSpec as P

        y = F.linear(x, self.weight, self.bias)
        if self.is_mp:
            if self.gather_output:
                y = _constrain(y, self._mesh, P())
            else:
                spec = P(*([None] * (y.ndim - 1) + [self._mp_axis]))
                y = _constrain(y, self._mesh, spec)
        return y


class RowParallelLinear(nn.Layer, _MpLayerBase):
    """Linear with in_features sharded over mp (ref: mp_layers.py:541).

    input_is_parallel=True expects the incoming activation mp-sharded on
    its last dim (the ColumnParallelLinear(gather_output=False) layout);
    the partial products are summed by an XLA all-reduce.
    """

    def __init__(
        self,
        in_features,
        out_features,
        weight_attr=None,
        has_bias=True,
        input_is_parallel=False,
        fuse_matmul_bias=False,
        mp_group=None,
        name=None,
    ):
        super().__init__()
        self._init_mp(mp_group)
        if self.is_mp and in_features % self.world_size != 0:
            raise ValueError(
                f"in_features {in_features} must divide mp degree {self.world_size}"
            )
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(shape=[in_features, out_features], attr=weight_attr)
        self.weight.tp_axis = 0
        self.weight.is_distributed = self.is_mp
        self.bias = None
        if has_bias:
            # bias is applied after the reduction; replicated
            self.bias = self.create_parameter(shape=[out_features], is_bias=True)

    def forward(self, x):
        from jax.sharding import PartitionSpec as P

        if self.is_mp and self.input_is_parallel:
            spec = P(*([None] * (x.ndim - 1) + [self._mp_axis]))
            x = _constrain(x, self._mesh, spec)
        y = F.linear(x, self.weight, self.bias)
        if self.is_mp:
            y = _constrain(y, self._mesh, P())  # summed partials, replicated
        return y


class ParallelCrossEntropy(nn.Layer, _MpLayerBase):
    """Softmax-CE over vocab-sharded logits (ref: mp_layers.py:742).

    The reference runs a masked local softmax + two allreduces; GSPMD
    derives the same schedule from the logits' sharding, so this is the
    standard numerically-stable CE with a vocab-dim constraint.
    """

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self._init_mp(mp_group)
        self.ignore_index = ignore_index

    def forward(self, input, label):
        from jax.sharding import PartitionSpec as P

        if self.is_mp:
            spec = P(*([None] * (input.ndim - 1) + [self._mp_axis]))
            input = _constrain(input, self._mesh, spec)
        return F.cross_entropy(
            input, label, reduction="none", ignore_index=self.ignore_index
        )

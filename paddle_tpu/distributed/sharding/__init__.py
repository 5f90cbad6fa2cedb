"""Group-sharded data parallelism (ZeRO stages 1/2/3).

TPU-native redesign of the reference's GroupSharded stack
(ref: python/paddle/distributed/sharding/group_sharded.py:41
group_sharded_parallel; fleet/meta_parallel/sharding/
group_sharded_stage2.py, group_sharded_stage3.py:85; and the stage-1
DygraphShardingOptimizer, fleet/meta_optimizers/dygraph_optimizer/
dygraph_sharding_optimizer.py:44).

The reference implements each stage with explicit bookkeeping: rank
partitioning of the param list, broadcast of updated shards, grad
reduce-scatter hooks, param all-gather/release pairs around each layer
(stage 3). On TPU none of that choreography is hand-written — a stage is
a *placement policy* and GSPMD derives the choreography:

- stage 1 (``os``): optimizer accumulators get a NamedSharding over the
  ``sharding`` mesh axis. XLA keeps the update math local to each shard.
- stage 2 (``os_g``): additionally, gradients are constrained to the
  same sharded layout inside the compiled train step, which makes the
  backward's final collective a reduce-scatter instead of an all-reduce
  (the stage-2 win in the reference's hook machinery).
- stage 3 (``p_g_os``): additionally, the parameters themselves are
  placed sharded; GSPMD inserts all-gathers right before use and frees
  the gathered buffers after (the reference's forward/backward hook
  pairs in GroupShardedStage3._register_forward_hooks).

Because each stage is only a layout change, numerics are identical to
plain DP by construction — tests assert loss parity on a multi-device
CPU mesh (test strategy: test/collective/fleet/
dygraph_group_sharded_stage3.py pattern).
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["group_sharded_parallel", "save_group_sharded_model"]

_LEVELS = ("os", "os_g", "p_g_os")


def _sharding_mesh_axis(group=None):
    """Resolve (mesh, axis_name) for the sharding group.

    Priority: explicit ``group`` (a collective.Group carries its mesh +
    axis) → the fleet hybrid topology's sharding axis → a fresh 1-D mesh
    over all visible devices.
    """
    if group is not None and getattr(group, "mesh", None) is not None:
        return group.mesh, group.axis_name
    from ..fleet.base.topology import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    if hcg is not None and hcg.get_sharding_parallel_world_size() > 1:
        return hcg.mesh, "sharding"
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("sharding",))
    return mesh, "sharding"


def _spec_on(arr, mesh: Mesh) -> list:
    """Per-dim spec entries ``arr`` already carries on ``mesh`` (e.g.
    the ``mp`` placement ``fleet.distributed_model`` made); all-None
    for a tracer, a host array or an array on another mesh."""
    sharding = getattr(arr, "sharding", None)
    if isinstance(sharding, NamedSharding) and sharding.mesh == mesh:
        spec = list(sharding.spec)
        return spec + [None] * (arr.ndim - len(spec))
    return [None] * arr.ndim


def _shard_spec(shape, mesh: Mesh, axis: str, base=None) -> PartitionSpec:
    """``base`` (the layout the tensor already has, default replicated)
    plus ``axis`` on the first dim it divides: a dim no other mesh axis
    holds if there is one, else stacked onto a held dim — so sharding
    COMPOSES with tensor parallelism instead of replacing it. 0-d or
    indivisible tensors keep ``base`` (the reference pads flat buffers
    instead — ref group_sharded_utils.py; with per-tensor layout,
    skipping the indivisible ones costs only those tensors'
    replication). Tensors big enough that replication forfeits a real
    memory win get a warning instead of silently replicating."""
    import warnings

    sizes = dict(mesh.shape)
    size = sizes[axis]
    spec = list(base) if base is not None else [None] * len(shape)

    def names(entry):
        return () if entry is None else (
            entry if isinstance(entry, tuple) else (entry,))

    if any(axis in names(e) for e in spec):
        return PartitionSpec(*spec)
    held = [int(np.prod([sizes[n] for n in names(e)])) for e in spec]
    order = [i for i, h in enumerate(held) if h == 1] \
        + [i for i, h in enumerate(held) if h > 1]
    for i in order:
        if shape[i] % (held[i] * size) == 0 and shape[i] >= held[i] * size:
            spec[i] = names(spec[i]) + (axis,) if held[i] > 1 else axis
            break
    else:
        numel = 1
        for d in shape:
            numel *= d
        if numel >= 1 << 16:  # small biases/scalars replicate silently
            warnings.warn(
                f"group sharding: tensor of shape {tuple(shape)} has no "
                f"axis divisible by the sharding degree {size}; it will "
                "be REPLICATED on every shard (no memory saving). Pad "
                "the dimension (e.g. vocab) to a multiple of the degree "
                "to shard it.",
                stacklevel=3,
            )
    return PartitionSpec(*spec)


def _place(arr, mesh: Mesh, axis: str, spec=None):
    """Put ``arr`` on ``mesh`` with ``spec`` (default: what it already
    has there plus ``axis``)."""
    from ...utils.jax_compat import global_device_put

    if spec is None:
        spec = _shard_spec(arr.shape, mesh, axis, _spec_on(arr, mesh))
    sharding = NamedSharding(mesh, spec)
    if isinstance(arr, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(arr, sharding)
    return global_device_put(arr, sharding)


def group_sharded_parallel(
    model,
    optimizer,
    level: str,
    scaler=None,
    group=None,
    offload: bool = False,
    sync_buffers: bool = False,
    buffer_max_size: int = 2**23,
    segment_size: int = 2**20,
    sync_comm: bool = False,
    dp_group=None,
    exclude_layer=None,
):
    """Wrap model/optimizer/scaler for group-sharded training.

    ref: python/paddle/distributed/sharding/group_sharded.py:41. The
    buffer/segment knobs are accepted for parity; XLA's allocator and
    fusion subsume grad bucketing, so they are no-ops here.

    Returns ``(model, optimizer, scaler)`` like the reference.
    """
    if level not in _LEVELS:
        raise ValueError(f"level must be one of {_LEVELS}, got {level!r}")
    if offload:
        # host-offloaded optimizer state: meaningful on GPU (pinned
        # memory); on TPU HBM↔host streaming would serialize the update.
        raise NotImplementedError(
            "offload=True is not supported on TPU; optimizer state is "
            "sharded over the mesh instead (same memory win, no PCIe)"
        )
    mesh, axis = _sharding_mesh_axis(group)

    # each parameter's target layout: whatever it already has on the
    # mesh (the tensor-parallel placement of fleet.distributed_model)
    # PLUS the sharding axis. Its optimizer state and gradient follow
    # the same layout — inside the traced step the parameter is a
    # tracer with no layout to read, so the specs are fixed here.
    specs = {
        p.name: _shard_spec(p._data.shape, mesh, axis,
                            _spec_on(p._data, mesh))
        for p in model.parameters()
    }

    def like_param(arr, param):
        if param is not None and tuple(arr.shape) == tuple(param.shape):
            return _place(arr, mesh, axis, specs.get(param.name))
        return _place(arr, mesh, axis)

    # stage 1: shard optimizer state (all levels include it)
    optimizer._accum_placement_fn = (
        lambda arr, param=None, name=None: like_param(arr, param)
    )
    by_name = {p.name: p for p in model.parameters()}
    for store in optimizer._accumulators.values():
        for key in store:
            store[key] = like_param(store[key], by_name.get(key))

    # stage 2: constrain grads to the sharded layout inside the step
    if level in ("os_g", "p_g_os"):
        optimizer._grad_placement_fn = like_param

    # stage 3: shard the parameters themselves (FSDP)
    if level == "p_g_os":
        for p in model.parameters():
            if not isinstance(p._data, jax.core.Tracer):
                p._data = _place(p._data, mesh, axis, specs[p.name])

    model._group_sharded_level = level
    model._group_sharded_mesh = (mesh, axis)
    return model, optimizer, scaler


def save_group_sharded_model(model, output: str, optimizer=None):
    """Gather sharded state to host and save (ref:
    group_sharded.py:168 save_group_sharded_model).

    Single-controller JAX arrays are globally addressable, so the
    "gather" is jnp → np; files follow paddle.save conventions:
    ``output/model.pdmodel`` + ``output/model.pdopt``.
    """
    import os

    from ... import framework

    os.makedirs(output, exist_ok=True)
    framework.io.save(model.state_dict(), os.path.join(output, "model.pdmodel"))
    if optimizer is not None:
        framework.io.save(optimizer.state_dict(), os.path.join(output, "model.pdopt"))

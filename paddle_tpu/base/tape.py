"""Eager autograd tape.

TPU-native redesign of the reference's dygraph autograd engine
(GradNodeBase graph + RunBackward, ref: paddle/fluid/eager/backward.cc:105,
grad_node_info.h:197). Instead of per-op hand-written grad kernels, every
op records a ``jax.vjp`` closure on a tape. Because jax arrays are
immutable values, this tape works identically in two regimes:

- **eager**: ops execute immediately on device; ``loss.backward()`` walks
  the tape calling the stored vjp closures (each is itself jax-traceable).
- **inside a jit trace** (paddle_tpu.jit): the same Python code runs on
  tracers; the tape composes vjp closures symbolically and XLA fuses the
  whole forward+backward into one program — this is how the framework gets
  "dygraph UX, static-graph performance" without a bespoke IR (the
  reference needed PIR + SOT for this; here jaxpr is the IR).

Topological ordering uses monotone node ids: inputs are always created
before outputs, so descending-id order is a valid reverse-topological
order (replaces the in-degree BFS of backward.cc:23).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import tree_util

from . import amp_state
from . import dtype as dtypes
from .flags import flag

_node_counter = itertools.count()


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_grad_state = _GradState()


def is_grad_enabled() -> bool:
    return _grad_state.enabled


class set_grad_enabled(contextlib.ContextDecorator):
    """paddle.set_grad_enabled parity; usable as ctx manager or decorator."""

    def __init__(self, mode: bool):
        self.mode = bool(mode)
        self.prev = None

    def __enter__(self):
        self.prev = _grad_state.enabled
        _grad_state.enabled = self.mode
        return self

    def __exit__(self, *exc):
        _grad_state.enabled = self.prev
        return False


class no_grad(set_grad_enabled):
    """paddle.no_grad parity (ref: python/paddle/base/dygraph/base.py)."""

    def __init__(self):
        super().__init__(False)


class enable_grad(set_grad_enabled):
    def __init__(self):
        super().__init__(True)


class TapeNode:
    """One recorded op: a vjp closure + edges to its differentiable inputs.

    Mirrors GradNodeBase (ref: fluid/eager/grad_node_info.h:197): ``inputs``
    are the Edges, ``vjp_fn`` is ``operator()``, out_avals/out_treedef
    describe the forward outputs so missing cotangents can be zero-filled
    (GradTensorHolder's job in the reference).
    """

    __slots__ = (
        "id",
        "name",
        "vjp_fn",
        "fwd_fn",
        "inputs",
        "out_avals",
        "out_treedef",
        "__weakref__",
    )

    def __init__(self, vjp_fn, inputs, out_avals, out_treedef, name="", fwd_fn=None):
        self.id = next(_node_counter)
        self.name = name
        self.vjp_fn = vjp_fn
        # fwd_fn: closure over the op's constants taking the diff primals;
        # used under create_graph to re-derive the vjp as an explicit
        # function of (cotangents, primals) so double-grad sees the edge.
        self.fwd_fn = fwd_fn
        self.inputs = inputs  # tuple of Tensors (strong refs, like TensorWrapper)
        self.out_avals = out_avals  # list[(shape, dtype)]
        self.out_treedef = out_treedef

    def __repr__(self):
        return f"TapeNode({self.name or 'op'}#{self.id})"


def _is_tensor(x) -> bool:
    from .tensor import Tensor

    return isinstance(x, Tensor)


def _differentiable(x) -> bool:
    return not x.stop_gradient and dtypes.is_floating_point(x.dtype) or (
        not x.stop_gradient and dtypes.is_complex(x.dtype)
    )


def apply(fn: Callable, *args, op_name: str = "", **kwargs):
    """Run ``fn`` (a jnp/lax-level function) on Tensor/array args, recording
    a tape node when differentiation is required.

    This is the single dispatch point every op wrapper goes through — the
    analogue of the generated ``*_ad_func`` layer (ref:
    fluid/eager/auto_code_generator/generator/eager_gen.py:767), with
    jax.vjp standing in for generated GradNodes.
    """
    from .tensor import Tensor

    flat, treedef = tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)

    # AMP: per-op input casting at the single dispatch point (the
    # reference does this in every generated ad_func; ref eager_gen.py
    # AMP block). cast itself dispatches through apply with
    # op_name="cast", which amp_state maps to None — no recursion.
    amp_target = amp_state.cast_target(op_name)
    if amp_target is not None:
        flat = [
            x.astype(amp_target)
            if isinstance(x, Tensor)
            and dtypes.is_floating_point(x.dtype)
            and np.dtype(x.dtype) != amp_target
            and np.dtype(x.dtype) != np.dtype(np.float64)
            else x
            for x in flat
        ]

    raw = [x._data if isinstance(x, Tensor) else x for x in flat]

    diff_idx: List[int] = []
    if _grad_state.enabled:
        diff_idx = [
            i
            for i, x in enumerate(flat)
            if isinstance(x, Tensor) and _differentiable(x)
        ]

    if not diff_idx:
        fargs, fkwargs = tree_util.tree_unflatten(treedef, raw)
        out = fn(*fargs, **fkwargs)
        return _wrap_outputs(out, node=None, op_name=op_name)

    def closure(*xs):
        buf = list(raw)
        for i, x in zip(diff_idx, xs):
            buf[i] = x
        cargs, ckwargs = tree_util.tree_unflatten(treedef, buf)
        return fn(*cargs, **ckwargs)

    primals = [raw[i] for i in diff_idx]
    out, vjp_fn = jax.vjp(closure, *primals)

    out_leaves, out_treedef = tree_util.tree_flatten(out)
    out_avals = [(np.shape(o), np.result_type(o)) for o in out_leaves]
    node = TapeNode(
        vjp_fn,
        tuple(flat[i] for i in diff_idx),
        out_avals,
        out_treedef,
        name=op_name or getattr(fn, "__name__", "op"),
        fwd_fn=closure,
    )
    node_ref = weakref.ref(node)
    for inp in node.inputs:
        inp._consumer_nodes.append(node_ref)
    return _wrap_outputs(out, node=node, op_name=op_name)


# Observers at the single dispatch point: callables (op_name, out_leaves)
# invoked on every op's raw outputs, and callables () invoked at each
# run_backward entry (training-step ticks). Empty lists cost one truthiness
# check per op. amp.debugging's operator-stats collector and tensor
# checker register here (the reference instruments its generated ad_func
# layer; ref python/paddle/amp/debugging.py:534 collect_operator_stats).
_op_observers: List[Callable] = []
_backward_tick_callbacks: List[Callable] = []


def _wrap_outputs(out, node, op_name=""):
    from .tensor import Tensor

    if flag("check_nan_inf"):
        _check_nan_inf(out, op_name)

    leaves, treedef = tree_util.tree_flatten(out)
    if _op_observers:
        for obs in list(_op_observers):
            obs(op_name, leaves)
    wrapped = []
    for i, leaf in enumerate(leaves):
        t = Tensor(leaf, stop_gradient=node is None, _internal=True)
        if node is not None:
            t._grad_node = node
            t._out_index = i
        wrapped.append(t)
    if flag("benchmark"):
        for leaf in leaves:
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
    return tree_util.tree_unflatten(treedef, wrapped)


def _check_nan_inf(out, op_name):
    """FLAGS_check_nan_inf parity (ref: fluid/eager/nan_inf_utils.cc).

    Only runs eagerly (skipped under trace where values are abstract).
    """
    import jax.core as jcore

    for leaf in tree_util.tree_leaves(out):
        if isinstance(leaf, jcore.Tracer):
            return
        arr = np.asarray(leaf)
        if arr.dtype.kind in "fc" and not np.isfinite(arr).all():
            msg = f"NaN/Inf detected in output of op '{op_name or 'unknown'}'"
            if flag("check_nan_inf_level") == 0:
                raise FloatingPointError(msg)
            print("WARNING:", msg)


# ---------------------------------------------------------------------------
# Backward engine (RunBackward parity, ref: fluid/eager/backward.cc:105)
# ---------------------------------------------------------------------------


def _zeros_cotangent(aval):
    shape, dt = aval
    if np.issubdtype(dt, np.inexact) or dt == dtypes.bfloat16:
        import jax.numpy as jnp

        return jnp.zeros(shape, dt)
    return np.zeros(shape, jax.dtypes.float0)


def _collect_reachable(roots) -> Dict[int, TapeNode]:
    nodes: Dict[int, TapeNode] = {}
    stack = [t._grad_node for t in roots if t._grad_node is not None]
    while stack:
        n = stack.pop()
        if n.id in nodes:
            continue
        nodes[n.id] = n
        for inp in n.inputs:
            if inp._grad_node is not None and inp._grad_node.id not in nodes:
                stack.append(inp._grad_node)
    return nodes


def run_backward(
    tensors: Sequence,
    grad_tensors: Optional[Sequence] = None,
    retain_graph: bool = False,
    *,
    inputs: Optional[Sequence] = None,
    create_graph: bool = False,
):
    """Reverse-walk the tape from ``tensors``.

    When ``inputs`` is given, returns the cotangents for exactly those
    tensors (paddle.grad semantics); otherwise accumulates into ``.grad``
    of every reachable leaf (loss.backward semantics).

    Cotangents flow as *Tensors* and each vjp closure is invoked through
    :func:`apply`, so with ``create_graph=True`` the backward pass itself
    is recorded on the tape — higher-order autodiff (double grad, the
    reference's ``general_grad.h`` path) falls out of the same mechanism.
    """
    import jax.numpy as jnp

    from .tensor import Tensor

    for cb in list(_backward_tick_callbacks):
        cb()

    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    if len(grad_tensors) != len(tensors):
        raise ValueError("grad_tensors length mismatch")

    # cotangent store keyed by (node_id, out_index); values are Tensors
    cots: Dict[Tuple[int, int], Any] = {}
    # grads for explicitly requested inputs (paddle.grad)
    want: Dict[int, Any] = {}
    want_ids = {id(t) for t in inputs} if inputs is not None else set()

    def _accumulate(t: Tensor, g: Tensor):
        if g is None or (
            isinstance(g, np.ndarray) and g.dtype == jax.dtypes.float0
        ):
            return
        if not isinstance(g, Tensor):
            g = Tensor(g, stop_gradient=not create_graph, _internal=True)
        for hook in t._grad_hooks:
            res = hook(g)
            if res is not None:
                g = res
        if id(t) in want_ids:
            want[id(t)] = g if id(t) not in want else want[id(t)] + g
        if t._grad_node is not None:
            key = (t._grad_node.id, t._out_index)
            cots[key] = g if key not in cots else cots[key] + g
            if t._retain_grads and inputs is None:
                t._grad = g if t._grad is None else t._grad + g
        elif getattr(t, "_piecewise_carry", False):
            # a cotangent reached a tensor carried across a piecewise
            # graph-break split: eager execution would have continued
            # into the prefix's graph, but the carry is a materialized
            # array with no history — silently stopping here would train
            # wrong. Raising demotes the split to whole-function eager
            # (StaticFunction catches any piecewise-path exception).
            raise RuntimeError(
                "backward reached a value carried across a piecewise "
                "graph-break split; the autograd graph cannot span the "
                "compiled prefix"
            )
        elif inputs is None and not t.stop_gradient:
            # leaf accumulation (GradNodeAccumulation parity)
            t._grad = g if t._grad is None else t._grad + g

    with set_grad_enabled(create_graph):
        for t, g in zip(tensors, grad_tensors):
            if g is None:
                if t.size != 1:
                    raise RuntimeError(
                        "grad can be implicitly created only for scalar outputs; "
                        f"got shape {t.shape}"
                    )
                g = Tensor(
                    jnp.ones(t._data.shape, t._data.dtype),
                    stop_gradient=not create_graph,
                    _internal=True,
                )
            _accumulate(t, g if isinstance(g, Tensor) else Tensor(g, _internal=True))

        nodes = _collect_reachable(tensors)
        for node in sorted(nodes.values(), key=lambda n: n.id, reverse=True):
            out_cots = []
            any_seeded = False
            for i, aval in enumerate(node.out_avals):
                c = cots.pop((node.id, i), None)
                if c is None:
                    c = _zeros_cotangent(aval)  # raw zeros; constant to vjp
                else:
                    any_seeded = True
                out_cots.append(c)
            if not any_seeded:
                # dead branch not on the path from roots: its inputs
                # will never receive a contribution from this node
                continue
            if node.vjp_fn is None:
                raise RuntimeError(
                    "Trying to backward through the graph a second time; "
                    "set retain_graph=True if needed."
                )
            cot_tree = tree_util.tree_unflatten(node.out_treedef, out_cots)
            if create_graph and node.fwd_fn is not None:
                # re-derive the vjp with primals as explicit args so the
                # cotangent→primal edges land on the tape (double grad)
                fwd_fn = node.fwd_fn

                def grad_call(ct, *prims, _fwd=fwd_fn):
                    _, vjp = jax.vjp(_fwd, *prims)
                    return tuple(vjp(ct))

                in_cots = apply(
                    grad_call, cot_tree, *node.inputs, op_name=f"grad_{node.name}"
                )
            else:
                vjp_fn = node.vjp_fn
                in_cots = apply(
                    lambda ct: tuple(vjp_fn(ct)), cot_tree, op_name=f"grad_{node.name}"
                )
            if not retain_graph and not create_graph:
                node.vjp_fn = None  # free residuals
            for inp, g in zip(node.inputs, in_cots):
                _accumulate(inp, g)

    if inputs is not None:
        return [want.get(id(t)) for t in inputs]
    return None

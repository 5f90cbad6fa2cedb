"""paddle_tpu.jit — compiled execution (to_static / save / load).

TPU-native replacement for the reference's two dynamic-to-static
front-ends (AST transforms + SOT bytecode tracing, ref:
python/paddle/jit/dy2static/program_translator.py, jit/sot/) and the
PIR + StandaloneExecutor stack below them. Here the IR is the jaxpr and
the executor is XLA: the eager tape (base/tape.py) already composes
under ``jax.jit`` tracing, so ``to_static`` only needs to
**functionalize the mutable state**:

    params/buffers of the Layers + optimizer accumulators + RNG keys
    are read into a pytree, threaded through a pure function, jitted
    with donation (old buffers freed in-place), and written back after
    each call.

One XLA program then contains forward + backward + optimizer update —
fused, MXU-scheduled, with zero per-op Python overhead (the reference
needed C++ codegen for the same reason, SURVEY §3.1).

Sharding: StaticFunction accepts ``state_shardings``/``arg_shardings``
(jax.sharding.NamedSharding) so hybrid-parallel strategies (DP/TP/
sharding-1/2/3) compile onto a device mesh — paddle_tpu.distributed
builds on this entry point.
"""
from __future__ import annotations

import functools
import inspect
import os
import pickle
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util

from .. import obs as _obs
from ..base import random as _random
from ..base.tensor import Tensor

__all__ = ["to_static", "not_to_static", "StaticFunction", "save", "load", "TranslatedLayer", "enable_to_static", "dy2static"]

from . import dy2static  # noqa: E402  (control-flow conversion submodule)

_jit_enabled = [True]


class _PiecewiseUnsafe(RuntimeError):
    """A piecewise split was built but is unsafe at runtime (carried
    non-jaxable value, or autograd would span the compiled prefix)."""


def enable_to_static(flag: bool = True):
    """ref: paddle.jit.enable_to_static — globally fall back to eager."""
    _jit_enabled[0] = bool(flag)


def _leg(name, call, **args):
    """A span of one leg of a compiled call, child of its ``to_static.call``
    span (``obs/trace.py``: the ring, and a profiler trace's host plane)."""
    return _obs.span("to_static." + name, parent=call, tid="to_static", **args)


def _is_tensor(x):
    return isinstance(x, Tensor)


class StaticFunction:
    """A compiled callable threading framework state through jax.jit.

    ref counterpart: dy2static StaticFunction + partial_program
    (program_translator.py) — but state capture replaces program capture.
    """

    def __init__(
        self,
        fn: Callable,
        layers: Sequence = (),
        optimizers: Sequence = (),
        scalers: Sequence = (),
        donate_state: bool = True,
        state_shardings=None,
        in_shardings=None,
        static_argnums: Tuple[int, ...] = (),
        full_graph: bool = True,
        carry_args: bool = False,
    ):
        functools.update_wrapper(self, fn, updated=[])
        from ..nn.layer.layers import Layer

        if isinstance(layers, Layer):
            layers = [layers]
        self._layers = list(layers)
        self._optimizers = list(optimizers)
        self._scalers = list(scalers)
        # auto-discovery is DEFERRED to the first call: a module-level
        # @to_static decorator usually runs before the model/optimizer
        # globals it references even exist
        self._orig_fn = fn
        self._needs_discovery = not self._layers and not self._optimizers
        # the explicitly-passed state survives any guard-triggered
        # rediscovery verbatim (only DISCOVERED bindings are guarded)
        self._explicit_state = (list(self._layers), list(self._optimizers),
                                list(self._scalers))
        # captured-state guard (ROADMAP 5a / reference SOT guard.py):
        # populated by _auto_discover with (kind, key, id) entries for
        # every DISCOVERED global/closure binding; revalidated cheaply
        # per call so rebinding a captured Layer/Optimizer triggers
        # rediscovery+retrace (or raises) instead of silently threading
        # the stale capture's state
        self._capture_guard: List[Tuple[str, Any, int]] = []
        # dy2static: rewrite tensor-dependent if/while into runtime
        # dispatch (lax select/while under trace, plain Python eagerly)
        from . import dy2static as _d2s

        self._fn = _d2s.convert(fn)
        self._donate_state = donate_state
        self._state_shardings = state_shardings
        self._in_shardings = in_shardings
        self._static_argnums = tuple(static_argnums)
        self._cells: List[Tensor] = []
        self._piecewise = None  # set after a successful graph-break split
        self._split_depth = 0  # recursion guard for nested splits
        self._accum_layouts: List[Any] = []  # set by every _read_state
        self._jit_cache: Dict[Any, Any] = {}  # arg_treedef -> jitted pure fn
        self._last_lowered = None
        self._pure_runs = 0  # pure() executions == jax trace count
        # what the compiled path tells paddle_tpu.obs: the spans of one
        # call share the id "<qualname>:<call index>"
        self._qualname = getattr(fn, "__qualname__", None) or repr(fn)
        self._n_calls = 0
        self._n_leaves = 0  # arrays threaded as state, as last traced
        self._call_span = None  # the open to_static.call span, for pure()
        # optimizers whose step() actually ran in the traced step (set
        # during tracing); only these get host-side step corrections
        self._stepped_optimizers: List[Any] = []
        # full_graph=False: a graph break demotes this function to
        # piecewise eager execution instead of raising (SOT semantics)
        self._full_graph = bool(full_graph)
        self._fallback_eager = False
        # piecewise-suffix functions: their args are values carried
        # across a graph-break split — mark the traced wrappers so the
        # tape can detect autograd reaching across the split
        self._carry_args = bool(carry_args)

    # -- discovery ------------------------------------------------------
    def _auto_discover(self, fn):
        """Find Layers/Optimizers in the function's closure + the module
        globals its bytecode actually LOADS (the SOT front-end does this
        at bytecode level; here dis + a direct object scan suffice).
        Runs at first call, not decoration, so globals defined after the
        decorator are seen. Optimizer wrappers (any ``_inner_opt``
        chain) are recognized and deduplicated against their innermost
        optimizer — threading the same state twice would double-donate
        its buffers."""
        import dis

        from ..amp.grad_scaler import AmpScaler
        from ..nn.layer.layers import Layer
        from ..optimizer.optimizer import Optimizer

        candidates: List[Any] = []
        sources: List[Tuple[str, Any]] = []  # parallel (kind, key) per
        # candidate — "closure" keys are cell indexes, "global" keys are
        # names; "self" is bound-method state (not rebindable, no guard)
        if fn_closure := getattr(fn, "__closure__", None):
            for i, c in enumerate(fn_closure):
                try:
                    contents = c.cell_contents
                except ValueError:  # still-empty cell
                    continue
                if contents is not None:
                    candidates.append(contents)
                    sources.append(("closure", i))
        if hasattr(fn, "__self__"):
            candidates.append(fn.__self__)
            sources.append(("self", None))
        # module-level step functions reference their model/optimizer as
        # GLOBALS, not closure cells; scan exactly the names loaded via
        # LOAD_GLOBAL (co_names alone also contains attribute names),
        # recursing into nested defs/lambdas/comprehensions
        code = getattr(fn, "__code__", None)
        fn_globals = getattr(fn, "__globals__", None)
        if code is not None and fn_globals is not None:
            import types

            def load_global_names(co, out):
                for ins in dis.get_instructions(co):
                    if ins.opname == "LOAD_GLOBAL":
                        out.add(ins.argval)
                for const in co.co_consts:
                    if isinstance(const, types.CodeType):
                        load_global_names(const, out)
                return out

            for gname in load_global_names(code, set()):
                obj = fn_globals.get(gname)
                if obj is not None:
                    candidates.append(obj)
                    sources.append(("global", gname))

        def innermost(o):
            # unwrap _inner_opt chains (HybridParallelOptimizer around
            # DygraphShardingOptimizer around AdamW, etc.)
            seen = set()
            while not isinstance(o, Optimizer):
                if id(o) in seen:
                    return None
                seen.add(id(o))
                o = getattr(o, "_inner_opt", None)
                if o is None:
                    return None
            return o

        known_inner = {id(innermost(o)) for o in self._optimizers}
        self._capture_guard = []
        for obj, (kind, key) in zip(candidates, sources):
            stateful = False
            if isinstance(obj, Layer):
                stateful = True
                if obj not in self._layers:
                    self._layers.append(obj)
            elif isinstance(obj, AmpScaler):
                stateful = True
                if obj not in self._scalers:
                    self._scalers.append(obj)
            else:
                inner = innermost(obj)
                if inner is not None:
                    stateful = True
                    if id(inner) not in known_inner:
                        known_inner.add(id(inner))
                        self._optimizers.append(obj)
            # guard every rebindable binding that contributed state —
            # including dedup'd duplicates: rebinding ANY of them means
            # the traced capture no longer reflects the source
            if stateful and kind in ("closure", "global"):
                self._capture_guard.append((kind, key, id(obj)))

    # -- captured-state guard (ROADMAP 5a) -------------------------------
    def _captures_valid(self) -> bool:
        """O(#captures) identity check per call — the cheap half of the
        reference's per-trace guard chain (SOT ``guard.py``): True iff
        every discovered global/closure binding still holds the exact
        object captured at discovery time."""
        fn = self._orig_fn
        for kind, key, oid in self._capture_guard:
            if kind == "closure":
                try:
                    cur = fn.__closure__[key].cell_contents
                except (ValueError, IndexError, TypeError):
                    return False
            else:
                cur = fn.__globals__.get(key)
            if id(cur) != oid:
                return False
        return True

    def _revalidate_captures(self) -> bool:
        """Retrace-or-raise on a stale capture: a rebound Layer/config
        triggers full rediscovery (new cells, cleared jit cache — the
        next call retraces against the CURRENT objects); a binding that
        no longer holds any stateful object raises, because executing
        the old compiled state thread would silently train the corpse
        of the rebound model. Returns True when a rebind was detected
        and state was rebuilt."""
        if not self._capture_guard or self._captures_valid():
            return False
        had_cells = bool(self._cells)
        explicit_l, explicit_o, explicit_s = self._explicit_state
        self._layers = list(explicit_l)
        self._optimizers = list(explicit_o)
        self._scalers = list(explicit_s)
        self._cells = []
        self._auto_discover(self._orig_fn)
        self._collect_cells()
        self._jit_cache.clear()
        self._last_lowered = None
        if had_cells and not self._cells:
            # leave the function RECOVERABLE: the next call after the
            # user rebinds a valid object must rediscover from scratch
            # (an empty guard would otherwise skip revalidation and
            # bake the late rebind's parameters in as constants)
            self._needs_discovery = True
            raise RuntimeError(
                "to_static captured-state guard: a Layer/Optimizer this "
                "compiled function captured was rebound and no stateful "
                "replacement was found at the same binding — the traced "
                "program would silently run with stale parameters. "
                "Rebind a compatible object or rebuild the "
                "StaticFunction.")
        return True

    def _collect_cells(self):
        cells, seen = [], set()

        def add(t):
            if t is not None and id(t) not in seen:
                seen.add(id(t))
                cells.append(t)

        for l in self._layers:
            for _, p in l.named_parameters():
                add(p)
            for _, b in l.named_buffers():
                add(b)
        for o in self._optimizers:
            for p in o._parameter_list:
                add(p)
        self._cells = cells

    # -- state threading ------------------------------------------------
    def _accum_layout(self, o):
        """Deterministic POSITIONAL order for the optimizer's accumulator
        pytree: parameter-list position first, then extras by key.

        Threading the raw name-keyed dicts would let jax's dict-key sort
        define the traced program's structure — and auto tensor names
        ("tensor_<n>", a process-global counter) make that ordering
        depend on how many tensors the process happened to create
        ("tensor_9" sorts AFTER "tensor_10"). Two multi-controller ranks
        whose user code created different tensor counts (e.g. one rank
        calls send, the other recv) would then trace DIFFERENTLY-ORDERED
        programs and their XLA collectives would pair up mismatched
        (observed as gloo "Received data size doesn't match expected
        size"). Positional order is rank-invariant."""
        pos = {p.name: i for i, p in enumerate(o._parameter_list)}
        layout = []
        for aname in sorted(o._accumulators):
            store = o._accumulators[aname]
            keys = sorted(
                store, key=lambda k: (0, pos[k]) if k in pos else (1, k))
            layout.append((aname, keys))
        return layout

    def _read_state(self):
        self._accum_layouts = [
            self._accum_layout(o) for o in self._optimizers]
        return {
            "cells": [c._data for c in self._cells],
            "accums": [
                [[o._accumulators[an][k] for k in keys]
                 for an, keys in lay]
                for o, lay in zip(self._optimizers, self._accum_layouts)
            ],
            "scalers": [
                (s._scale, s._good_steps, s._bad_steps, s._found_inf)
                for s in self._scalers
            ],
            "rng": _random.default_generator().get_state(),
            "tracker": _random.get_rng_state_tracker().get_states_dict(),
        }

    def _write_state(self, state):
        for c, arr in zip(self._cells, state["cells"]):
            c._data = arr
        for o, lay, acc in zip(
                self._optimizers, self._accum_layouts, state["accums"]):
            o._accumulators = {
                an: dict(zip(keys, vals))
                for (an, keys), vals in zip(lay, acc)
            }
        for sc, vals in zip(self._scalers, state.get("scalers", [])):
            sc._scale, sc._good_steps, sc._bad_steps, sc._found_inf = vals
        _random.default_generator().set_state(state["rng"])
        _random.get_rng_state_tracker().set_states_dict(state["tracker"])

    # -- the pure function ----------------------------------------------
    def _make_pure(self, arg_treedef, n_out_hint=None):
        def pure(state, lrs, flat_args):
            # host-side trace marker: pure() only executes while jax is
            # TRACING (cached executions replay the compiled program).
            # __call__ uses this to know whether the optimizer's host
            # step counter already advanced — inferring from "first call
            # with this treedef" misses jax-level retraces (e.g. the
            # second call, once lazily-created accumulators change the
            # state pytree), which double-counted _global_step.
            self._pure_runs += 1
            self._n_leaves = len(tree_util.tree_leaves(state))
            # a trace-time span on purpose: how long the program's own
            # Python runs under jax's tracer (dy2static, the tape, the
            # optimizer's per-leaf loop), as against lowering/compiling.
            # A with-block here and not a wrapper: see __call__ on frames
            with _leg("trace", self._call_span, fn=self._qualname):
                steps_before = [o._global_step for o in self._optimizers]
                self._write_state(state)
                for o, lr in zip(self._optimizers, lrs):
                    o._lr_override = lr
                try:
                    wrapped = [
                        Tensor(a, stop_gradient=True, _internal=True)
                        if isinstance(a, (jax.Array, np.ndarray)) or hasattr(a, "dtype")
                        else a
                        for a in flat_args
                    ]
                    if self._carry_args:
                        for w in wrapped:
                            if isinstance(w, Tensor):
                                w._piecewise_carry = True
                    args, kwargs = tree_util.tree_unflatten(arg_treedef, wrapped)
                    try:
                        out = self._fn(*args, **kwargs)
                    except (
                        jax.errors.ConcretizationTypeError,  # incl. bool conv
                        jax.errors.TracerArrayConversionError,
                        jax.errors.TracerIntegerConversionError,
                    ) as e:
                        from . import dy2static as _d2s

                        raise _d2s.graph_break_error(e) from e
                finally:
                    for o in self._optimizers:
                        o._lr_override = None
                # which optimizers actually stepped during the traced run:
                # only those get host-side step-count corrections (a merely
                # READ optimizer, e.g. get_lr() logging, must not advance)
                self._stepped_optimizers = [
                    o for o, s0 in zip(self._optimizers, steps_before)
                    if o._global_step > s0
                ]
                new_state = self._read_state()
                return tree_util.tree_map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out, is_leaf=_is_tensor
                ), new_state

        return pure

    # -- call -----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if not _jit_enabled[0] or self._fallback_eager:
            return self._orig_fn(*args, **kwargs)
        if self._piecewise is not None:
            # a later call can still prove unsafe (the break may bind a
            # different type on another branch): restore and demote
            # instead of leaking the internal error mid-training-loop
            snap = self._snapshot_host_state()  # O(#params) host refs —
            # negligible next to a train step, and the price of making
            # any late failure restorable
            try:
                return self._piecewise(*args, **kwargs)
            except Exception as why:
                import warnings

                self._restore_host_state(snap)
                warnings.warn(
                    "to_static(full_graph=False): piecewise capture "
                    f"became unsafe ({why}); demoting to whole-function "
                    "eager execution.", stacklevel=2)
                self._piecewise = None
                self._fallback_eager = True
                return self._orig_fn(*args, **kwargs)
        # the compiled path: one to_static.call span from here to the
        # return, its legs as children (obs/trace.py; with a profiler
        # session running they are pt: events beside the device's ops).
        # All of it stays in THIS frame and what it needs is kept on the
        # object: while jax traces, this frame lies under every traced
        # op, and one more frame there (or a larger one) shifts where
        # the interpreter's frame stack crosses a chunk boundary, which
        # made set-up's tracing a quarter slower (PERF.md, PR 24)
        self._n_calls += 1
        runs_before = self._pure_runs
        with _obs.span("to_static.call", tid="to_static",
                       trace_id=f"{self._qualname}:{self._n_calls}",
                       fn=self._qualname) as call:
            self._call_span = call  # pure() parents its trace span on it
            # jax's lowering and compile events inside this call become
            # its to_static.lower / to_static.compile legs (obs/compile.py)
            _obs.compile.call_opened(call)
            try:
                with _leg("revalidate", call):
                    if self._needs_discovery:
                        self._auto_discover(self._orig_fn)
                        self._needs_discovery = False
                    else:
                        self._revalidate_captures()
                    if not self._cells:
                        self._collect_cells()

                flat, arg_treedef = tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)
                flat_arrays = [a._data if isinstance(a, Tensor) else a for a in flat]

                with _leg("read_state", call):
                    state = self._read_state()
                    lrs = [jnp.asarray(o.get_lr(), jnp.float32) for o in self._optimizers]

                jitted = self._jit_cache.get(arg_treedef)
                if jitted is None:
                    jit_kwargs = {}
                    if self._donate_state:
                        jit_kwargs["donate_argnums"] = (0,)
                    # the pure function gets no local of its own: this
                    # frame keeps its size (see above; PERF.md §6)
                    jitted = jax.jit(self._make_pure(arg_treedef), **jit_kwargs)
                    self._jit_cache[arg_treedef] = jitted
                steps_before = [o._global_step for o in self._optimizers]
                try:
                    with _leg("dispatch", call):
                        out_arrays, new_state = jitted(state, lrs, flat_arrays)
                except dy2static.GraphBreakError as e:
                    if self._full_graph:
                        raise
                    # from here on this call is not a compiled one: the
                    # span says so, and its readers leave it out
                    call.args["fallback"] = True
                    # SOT semantics (ref jit/sot opcode_executor.py:305,1594):
                    # split the function at the breaking statement — prefix and
                    # suffix stay COMPILED (their own StaticFunctions), the
                    # breaking statement runs eagerly each call. Only when no
                    # safe split exists does the whole function demote to
                    # per-op eager. The failed trace wrote tracers into the
                    # threaded state; roll it back first.
                    self._write_state(state)
                    self._sanitize_grads()
                    for o, s0 in zip(self._optimizers, steps_before):
                        o._global_step = s0
                    import warnings

                    if self._split_depth < 3:
                        piecewise = self._build_piecewise(e)
                        if piecewise is not None:
                            snap = self._snapshot_host_state()
                            try:
                                out = piecewise(*args, **kwargs)
                            except Exception as why:
                                # ANY failure in the split path (unsafe carry,
                                # tape truncation, a Tensor where the break
                                # expected a python int, ...) demotes: restore
                                # the snapshot so a prefix that already stepped
                                # the optimizer isn't applied twice, then rerun
                                # eagerly — genuine user errors re-raise from
                                # the eager path with clean state
                                self._restore_host_state(snap)
                                warnings.warn(
                                    "to_static(full_graph=False): piecewise "
                                    f"capture unsafe ({why}); falling back to "
                                    "whole-function eager execution.",
                                    stacklevel=2)
                            else:
                                info = piecewise._info
                                warnings.warn(
                                    "to_static(full_graph=False): graph break at "
                                    f"line {info['line']} ({info['stmt']!r}) — "
                                    "piecewise capture: prefix and suffix run "
                                    "compiled; only the breaking statement runs "
                                    "eagerly each call (host side effects "
                                    "re-execute; carried locals: "
                                    f"{info['carry1']}).",
                                    stacklevel=2)
                                self._piecewise = piecewise
                                return out
                    warnings.warn(
                        "to_static(full_graph=False): graph break — falling back "
                        f"to piecewise eager execution for "
                        f"{getattr(self._orig_fn, '__qualname__', self._orig_fn)}. "
                        f"Reason: {e}",
                        stacklevel=2,
                    )
                    self._fallback_eager = True
                    return self._orig_fn(*args, **kwargs)
                trace_runs = self._pure_runs - runs_before
                self._last_lowered = jitted
                with _leg("write_state", call):
                    self._write_state(new_state)
                    self._sanitize_grads()
                    # host-side step counters: this call represents exactly ONE
                    # step for each optimizer that actually steps in the traced
                    # program; tracing already advanced _global_step once per
                    # pure() execution (0 on cached calls, 1 per [re]trace)
                    if trace_runs != 1:
                        for o in self._stepped_optimizers:
                            o._global_step += 1 - trace_runs
                if trace_runs:
                    # what the compiler reckons the executable needs, on
                    # the call that compiled it (jax's caches hold it by
                    # now: a lookup, no trace or compile; obs/compile.py)
                    call.args["memory"] = _obs.compile.executable_memory(
                        jitted, state, lrs, flat_arrays)
                return tree_util.tree_map(
                    lambda a: Tensor(a, _internal=True) if isinstance(a, jax.Array) else a, out_arrays
                )
            finally:
                self._call_span = None
                _obs.compile.call_closed()
                call.args.update(traces=self._pure_runs - runs_before,
                                 leaves=self._n_leaves)

    # -- host-state snapshot (piecewise trial safety) --------------------
    def _snapshot_host_state(self):
        """Shallow snapshot of every host-visible training state the
        compiled prefix could commit — jax arrays are immutable, so
        reference copies suffice. Used to make a piecewise attempt
        atomic: if it proves unsafe mid-call, restore and re-run eagerly
        (otherwise a prefix that already stepped the optimizer would
        step AGAIN in the eager rerun)."""
        return {
            "cells": [c._data for c in self._cells],
            "accums": [
                {an: dict(store) for an, store in o._accumulators.items()}
                for o in self._optimizers
            ],
            "steps": [o._global_step for o in self._optimizers],
            "scalers": [
                (s._scale, s._good_steps, s._bad_steps, s._found_inf)
                for s in self._scalers
            ],
            "rng": _random.default_generator().get_state(),
            "tracker": _random.get_rng_state_tracker().get_states_dict(),
        }

    def _restore_host_state(self, snap):
        for c, arr in zip(self._cells, snap["cells"]):
            c._data = arr
        for o, acc, st in zip(self._optimizers, snap["accums"],
                              snap["steps"]):
            o._accumulators = acc
            o._global_step = st
        for s, vals in zip(self._scalers, snap["scalers"]):
            s._scale, s._good_steps, s._bad_steps, s._found_inf = vals
        _random.default_generator().set_state(snap["rng"])
        _random.get_rng_state_tracker().set_states_dict(snap["tracker"])
        self._sanitize_grads()

    def _build_piecewise(self, err):
        """Build the split execution path after a graph break.

        Splits ``_orig_fn`` at the breaking top-level statement
        (dy2static.split_at_break): prefix and suffix compile as their
        own StaticFunctions sharing this one's layers/optimizers (state
        threads through each), the breaking statement runs eagerly per
        call — host control flow and side effects re-execute naturally,
        so no guards are needed. Returns None when no safe split exists.
        Runtime safety: carried values must be jax-able, and when the
        break/suffix differentiates, no carried tensor may still require
        grad (the tape cannot span a compiled prefix); violations raise
        _PiecewiseUnsafe and the caller demotes to whole-eager.
        """
        import warnings

        code = self._orig_fn.__code__
        src_file = getattr(code, "co_filename", None)
        src_base = getattr(
            inspect.unwrap(self._orig_fn), "__code__", code).co_firstlineno
        # try every same-file frame, deepest first: a break inside a
        # same-file helper maps outside this function's body, but the
        # shallower CALL-SITE frame still splits cleanly. Frames from
        # dy2static-converted code carry lines RELATIVE to the function
        # start — translate via co_firstlineno.
        parts = None
        for f, ln in getattr(err, "frames", ()):
            if f == src_file:
                line = ln
            elif f == f"<dy2static:{src_file}>":
                # converted THIS function: relative lineno
                line = src_base + ln - 1
            else:
                continue
            parts = dy2static.split_at_break(self._orig_fn, line)
            if parts is not None:
                break
        if parts is None:
            return None
        pre_fn, brk_fn, suf_fn, info = parts
        # donate_state=False: the demote-to-eager path restores a
        # snapshot of the pre-call state arrays; donation would delete
        # them inside the prefix's jit and poison both the restore and
        # the eager rerun
        kwargs = dict(layers=self._layers, optimizers=self._optimizers,
                      scalers=self._scalers, donate_state=False,
                      full_graph=False)
        pre_sf = StaticFunction(pre_fn, **kwargs)
        suf_sf = StaticFunction(suf_fn, carry_args=True, **kwargs)
        pre_sf._split_depth = suf_sf._split_depth = self._split_depth + 1
        grad_hazard = info["grad_hazard"]

        def _check_carry(carry, stage, marked):
            for k, v in carry.items():
                if isinstance(v, Tensor):
                    if grad_hazard:
                        raise _PiecewiseUnsafe(
                            f"{stage} carries tensor {k!r} across the "
                            "split while the code after the break uses "
                            "autograd — a materialized carry has no grad "
                            "history, so backward/step would silently "
                            "miss it")
                    # runtime backstop for INDIRECT autograd the static
                    # token scan can't see (a helper that differentiates):
                    # the tape raises if a cotangent ever reaches a
                    # carry-marked tensor, and the piecewise caller
                    # demotes (base/tape.py run_backward)
                    v._piecewise_carry = True
                    marked.append(v)
                elif not isinstance(v, (int, float, bool, complex,
                                        np.ndarray, jax.Array, type(None))):
                    raise _PiecewiseUnsafe(
                        f"{stage} carries non-tensor value {k!r} of type "
                        f"{type(v).__name__}")

        def piecewise(*args, **kw):
            marked = []
            try:
                carry = pre_sf(*args, **kw)
                _check_carry(carry, "prefix", marked)
                carry2 = brk_fn(carry)
                _check_carry(carry2, "break", marked)
                return suf_sf(carry2)
            finally:
                # the break may bind LONG-LIVED objects (a parameter,
                # a buffer) to a carried local — the mark must not
                # outlive the call or later ordinary backward()s
                # through that tensor would raise forever
                for t in marked:
                    t._piecewise_carry = False

        piecewise._info = info
        piecewise._prefix_sf, piecewise._suffix_sf = pre_sf, suf_sf
        return piecewise

    def _sanitize_grads(self):
        for c in self._cells:
            g = c._grad
            if g is not None and isinstance(g._data, jax.core.Tracer):
                c._grad = None
            c._grad_node = None
            c._consumer_nodes = []

    # -- multi-step: K train steps in ONE device dispatch ----------------
    def multi_step(self, *stacked_args, steps: Optional[int] = None, lr_schedule=None):
        """Run K steps under a single ``lax.scan`` dispatch.

        Each leaf of ``stacked_args`` must carry a leading axis of length
        K (per-step data), or pass un-stacked args with ``steps=K`` to
        reuse the same batch each step. One dispatch = no per-step host
        round-trip — essential on high-latency links and the idiom the
        reference approximates with dataloader prefetch + async executors
        (SURVEY §3.1). Call the function normally once first so lazy
        state (optimizer accumulators) exists and the carry structure is
        stable.

        LR semantics: by default the current learning rate is held
        constant across the K steps (host-side LRScheduler.step() cannot
        run inside the scan). Pass ``lr_schedule`` — a length-K array, or
        a list of them (one per optimizer) — to vary the LR per step.

        Returns the K-stacked outputs.
        """
        if self._fallback_eager or self._piecewise is not None:
            raise RuntimeError(
                "multi_step requires full-graph capture, but this "
                "function hit a graph break (full_graph=False) and runs "
                "piecewise; fix the break or use full_graph=True"
            )
        if not self._cells:
            raise RuntimeError(
                "multi_step requires one regular call first (to create "
                "optimizer state and cache the carry structure)"
            )
        if self._revalidate_captures():
            # a rebound capture breaks multi_step's contract (the scan
            # carry needs lazily-created state — e.g. a fresh
            # optimizer's accumulators — to exist BEFORE tracing); the
            # rediscovery above already rebuilt cells and cleared the
            # jit cache, the caller just has to warm up again
            raise RuntimeError(
                "multi_step: a captured Layer/Optimizer was rebound "
                "since the warm-up call; call the function once again "
                "before scanning"
            )
        if steps is not None:
            stacked_args = tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    (a._data if isinstance(a, Tensor) else jnp.asarray(a))[None],
                    (steps,) + tuple((a._data if isinstance(a, Tensor) else jnp.asarray(a)).shape),
                ),
                stacked_args,
                is_leaf=_is_tensor,
            )
        flat, arg_treedef = tree_util.tree_flatten((stacked_args, {}), is_leaf=_is_tensor)
        flat_arrays = [a._data if isinstance(a, Tensor) else jnp.asarray(a) for a in flat]
        n = len(flat_arrays[0]) if flat_arrays else (steps or 0)

        if lr_schedule is None:
            lrs_stacked = [
                jnp.full((n,), o.get_lr(), jnp.float32) for o in self._optimizers
            ]
        else:
            if not isinstance(lr_schedule, (list, tuple)):
                lr_schedule = [lr_schedule]
            if len(lr_schedule) != len(self._optimizers):
                raise ValueError(
                    f"lr_schedule needs {len(self._optimizers)} entries, "
                    f"got {len(lr_schedule)}"
                )
            lrs_stacked = [jnp.asarray(s, jnp.float32).reshape(n) for s in lr_schedule]

        state = self._read_state()

        # key includes shapes/dtypes: a new K retraces inside the same
        # jax.jit, and the trace runs optimizer.step() once host-side —
        # the step-count correction below must see that as a trace
        abstract = tuple((tuple(a.shape), str(a.dtype)) for a in flat_arrays)
        key = ("__multi_step__", arg_treedef, abstract, n)
        jitted = self._jit_cache.get(key)
        if jitted is None:
            pure = self._make_pure(arg_treedef)

            def scanned(state, lrs_stacked, flat_stacked):
                def body(st, xs):
                    lrs_t, data = xs
                    out, new_st = pure(st, list(lrs_t), list(data))
                    return new_st, out

                new_state, outs = jax.lax.scan(
                    body, state, (tuple(lrs_stacked), tuple(flat_stacked))
                )
                return outs, new_state

            jitted = jax.jit(
                scanned, donate_argnums=(0,) if self._donate_state else ()
            )
            self._jit_cache[key] = jitted
        # the same call span as __call__'s, with the legs this path has
        self._n_calls += 1
        runs_before = self._pure_runs
        with _obs.span("to_static.call", tid="to_static",
                       trace_id=f"{self._qualname}:{self._n_calls}",
                       fn=self._qualname) as call:
            self._call_span = call
            _obs.compile.call_opened(call)
            try:
                with _leg("dispatch", call):
                    outs, new_state = jitted(state, lrs_stacked, flat_arrays)
                trace_runs = self._pure_runs - runs_before
                with _leg("write_state", call):
                    self._write_state(new_state)
                    self._sanitize_grads()
                    # host-side step counter: this call represents n steps
                    # for each optimizer that steps in the traced program;
                    # tracing already advanced _global_step once per pure()
                    # execution (scan traces its body at least once)
                    correction = n - trace_runs
                    if correction:
                        for o in self._stepped_optimizers:
                            o._global_step += correction
                if trace_runs:
                    call.args["memory"] = _obs.compile.executable_memory(
                        jitted, state, lrs_stacked, flat_arrays)
                return tree_util.tree_map(
                    lambda a: Tensor(a, _internal=True) if isinstance(a, jax.Array) else a, outs
                )
            finally:
                self._call_span = None
                _obs.compile.call_closed()
                call.args.update(traces=self._pure_runs - runs_before,
                                 leaves=self._n_leaves)

    # -- inspection -----------------------------------------------------
    def concrete_program(self):
        return self._last_lowered


def to_static(
    function=None,
    input_spec=None,
    build_strategy=None,
    backend=None,
    layers=(),
    optimizers=(),
    scalers=(),
    full_graph=True,
    **kwargs,
):
    """Compile a function or a Layer (ref: paddle.jit.to_static, jit/api.py).

    - ``to_static(layer)`` → layer with compiled ``forward``.
    - ``to_static(fn, layers=[...], optimizers=[...])`` → compiled train
      step; layer params, optimizer state and RNG are threaded and
      donated automatically. If not given, Layers/Optimizers are
      auto-discovered from the function closure.
    - ``full_graph`` (ref: jit/api.py:271 — True selects the AST
      whole-graph translator, False the SOT bytecode tracer with
      graph-break fallback): True (default) raises an actionable error
      on an unconvertible construct; False demotes the function to
      piecewise eager execution at the first graph break — each op
      still runs XLA-compiled via the tape's per-op dispatch (the
      limit case of SOT's subgraph stitching), with fusion/donation/
      ``multi_step`` forfeited. The fallback is per-function and
      emits a one-time warning naming the breaking construct.
    """
    from ..nn.layer.layers import Layer

    def decorate(obj):
        if isinstance(obj, Layer):
            sf = StaticFunction(obj.forward, layers=[obj],
                                full_graph=full_graph, **kwargs)
            obj.forward = sf
            return obj
        return StaticFunction(
            obj, layers=layers, optimizers=optimizers, scalers=scalers,
            full_graph=full_graph, **kwargs
        )

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    """ref: paddle.jit.not_to_static — marker for eager-only functions."""
    fn._not_to_static = True
    return fn


# ---------------------------------------------------------------------------
# save / load (ref: python/paddle/jit/api.py jit.save / jit.load,
# serialization format replaced by jax.export StableHLO + state pickle)
# ---------------------------------------------------------------------------


def save(layer, path, input_spec=None, **config):
    """Save a Layer (or StaticFunction-wrapped Layer) for inference.

    Produces ``{path}.pdiparams`` (pickled numpy state dict) and
    ``{path}.pdmodel`` (serialized StableHLO via jax.export when an
    input_spec is given, else a marker requiring the Python class on
    load). ref: jit/api.py save → TranslatedLayer.
    """
    from ..nn.layer.layers import Layer

    if not isinstance(layer, Layer):
        raise TypeError("jit.save expects a Layer")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    state = {k: np.asarray(v._data) for k, v in layer.state_dict().items()}
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump(state, f, protocol=4)

    exported_bytes = None
    if input_spec is not None:
        # functionalize forward over (params, x) and AOT-export
        params_names = [k for k, _ in layer.named_parameters()]
        buffers_names = [k for k, _ in layer.named_buffers()]

        def pure_forward(param_arrays, buffer_arrays, *xs):
            for (k, p), a in zip(layer.named_parameters(), param_arrays):
                p._data = a
            for (k, b), a in zip(layer.named_buffers(), buffer_arrays):
                b._data = a
            layer.eval()
            out = layer(*[Tensor(x, _internal=True) for x in xs])
            return tree_util.tree_map(
                lambda t: t._data if isinstance(t, Tensor) else t, out, is_leaf=_is_tensor
            )

        from jax import export as jax_export

        param_arrays = [p._data for _, p in layer.named_parameters()]
        buffer_arrays = [b._data for _, b in layer.named_buffers()]
        specs = []
        for s in input_spec:
            shape = s.shape if hasattr(s, "shape") else s[0]
            dtype = getattr(s, "dtype", None) or (s[1] if isinstance(s, (tuple, list)) and len(s) > 1 else "float32")
            from ..base import dtype as _dt

            specs.append(jax.ShapeDtypeStruct(tuple(shape), _dt.canonical_dtype(dtype)))
        was_training = layer.training
        try:
            exp = jax_export.export(jax.jit(pure_forward))(
                [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in param_arrays],
                [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in buffer_arrays],
                *specs,
            )
            exported_bytes = exp.serialize()
        finally:
            # export tracing rebinds p._data to tracers and flips the
            # layer to eval; restore both so the live layer keeps working
            for (_, p), a in zip(layer.named_parameters(), param_arrays):
                p._data = a
                p._grad_node = None
                p._consumer_nodes = []
            for (_, b), a in zip(layer.named_buffers(), buffer_arrays):
                b._data = a
            if was_training:
                layer.train()

    meta = {
        "format": "paddle_tpu.jit.v1",
        "class": type(layer).__name__,
        "param_names": [k for k, _ in layer.named_parameters()],
        "buffer_names": [k for k, _ in layer.named_buffers()],
        "exported": exported_bytes,
    }
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(meta, f, protocol=4)


class TranslatedLayer:
    """Inference-only callable loaded by jit.load (ref:
    python/paddle/jit/translated_layer.py)."""

    def __init__(self, exported, params, buffers):
        from jax import export as jax_export

        self._exp = jax_export.deserialize(exported)
        self._params = params
        self._buffers = buffers
        # data-input arity = exported args minus the params/buffers trees
        # (the inference Predictor sizes its feed slots from this)
        n_state = len(tree_util.tree_leaves((params, buffers)))
        self.num_inputs = max(len(self._exp.in_avals) - n_state, 1)

    def __call__(self, *xs):
        arrays = [x._data if isinstance(x, Tensor) else jnp.asarray(x) for x in xs]
        out = self._exp.call(self._params, self._buffers, *arrays)
        return tree_util.tree_map(lambda a: Tensor(a, _internal=True), out)

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only (AOT-exported)")


def load(path, **config):
    """Load a jit.save'd model. Returns a TranslatedLayer when an
    exported program is present, else the raw state dict."""
    with open(path + ".pdmodel", "rb") as f:
        meta = pickle.load(f)
    with open(path + ".pdiparams", "rb") as f:
        state = pickle.load(f)
    if meta.get("exported"):
        params = [jnp.asarray(state[k]) for k in meta["param_names"]]
        buffers = [jnp.asarray(state[k]) for k in meta["buffer_names"]]
        return TranslatedLayer(meta["exported"], params, buffers)
    return state


# -- parity sweep (ref: python/paddle/jit/__init__.py remaining) ------------
_ignored_modules: list = []


def ignore_module(modules):
    """ref: jit/api.py ignore_module — modules whose functions to_static
    leaves untranslated. jax.jit traces values, not source, so nothing
    needs rewriting; the list is recorded for introspection parity."""
    if not isinstance(modules, (list, tuple)):
        modules = [modules]
    _ignored_modules.extend(modules)


def set_code_level(level=100, also_to_stdout=False):
    """ref: jit/dy2static set_code_level — dy2static transformed-code
    dump verbosity. There is no source transform here (value tracing);
    maps onto the VLOG level so jit-path logging can be raised."""
    from ..base import flags as _flags

    _flags.set_flags({"log_level": int(level)})


def set_verbosity(level=0, also_to_stdout=False):
    """ref: jit/dy2static set_verbosity — same mapping as
    set_code_level."""
    from ..base import flags as _flags

    _flags.set_flags({"log_level": int(level)})

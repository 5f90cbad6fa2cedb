"""What a compiling call did: jax's lowering and compile events as spans
of the obs timeline, with the persistent cache's verdict.

jax publishes, through the public ``jax.monitoring`` listeners, one time
span for each lowering of a jaxpr to an MLIR module
(``/jax/core/compile/jaxpr_to_mlir_module_duration``) and one for each
backend compile (``/jax/core/compile/backend_compile_duration``), both
with the function's name, and, inside a compile, what the persistent
cache did with the request (``/jax/compilation_cache/...``). The ONE
listener of the repository, registered when this module is imported (no
logger is touched), turns them into finished spans of ``obs.ring()``:

- ``to_static.lower`` / ``to_static.compile`` while a ``to_static.call``
  is open on the thread (``paddle_tpu.jit`` says so through
  :func:`call_opened` / :func:`call_closed`): children of that call,
  beside its other legs, named and placed after it (the call's own
  prefix and ``tid``; this module knows neither);
- ``xla.lower`` / ``xla.compile`` otherwise (an eager op, a model's
  construction, a user's own ``jax.jit``): no parent, ``tid="compile"``.

``args`` of either: ``fun`` (jax's ``fun_name``); a compile span also
``cache`` — ``"hit"``, ``"miss"`` (the request used the persistent cache
and found nothing) or ``"off"`` (it did not use it: the cache is
disabled, or has no directory) — and, on a hit,
``retrieval_s`` (the cache's read) and ``saved_s`` (jax's
``compile_time_saved_sec``: what the stored compile had cost, less the
read), so a warm run says what the cold compile of its program costs.
jax reports no read time on a miss. ``jaxpr_trace_duration`` events (one
per nested jit: thousands a trace) are dropped at the listener's first
line; ``to_static.trace`` times the program's own Python.

:func:`executable_memory` reads what the compiler reckons an executable
needs; ``paddle_tpu.jit`` puts it on the call that compiled it.
``analysis.recompile_guard`` keeps its own seam (jax's compile log).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

from jax import config, monitoring

from .trace import Span, record_span

__all__ = ["call_opened", "call_closed", "executable_memory"]

_LEGS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_USED_CACHE = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


class _Thread(threading.local):
    """A thread's open ``to_static.call`` spans, innermost last, and what
    the cache has said of the compile in flight (jax compiles on the
    calling thread, and reports the cache's verdict before the compile's
    own time span)."""

    def __init__(self):
        self.calls: List[Span] = []
        self.cache: Dict = {}


_THREAD = _Thread()


def call_opened(call: Span) -> None:
    _THREAD.calls.append(call)


def call_closed() -> None:
    _THREAD.calls.pop()


def _on_event(event: str, **_) -> None:
    if event == _USED_CACHE:
        # jax 0.9.0 says this of every request while the cache is enabled,
        # with or without a directory to keep it in: none is "off"
        if config.jax_compilation_cache_dir:
            _THREAD.cache["cache"] = "miss"
    elif event == _CACHE_HIT:
        _THREAD.cache["cache"] = "hit"


def _on_duration(event: str, seconds: float, **_) -> None:
    key = _SECONDS.get(event)
    if key is not None:
        _THREAD.cache[key] = seconds


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    leg = _LEGS.get(event)
    if leg is None:  # jaxpr_trace_duration, mostly
        return
    args = {"fun": kw.get("fun_name")}
    if leg == "compile":
        args.update({"cache": "off"}, **_THREAD.cache)
        _THREAD.cache = {}
    calls = _THREAD.calls
    if calls:  # a leg of the open call: "<its prefix>.lower" on its track
        call = calls[-1]
        record_span(call.name.rpartition(".")[0] + "." + leg, start,
                    end - start, parent=call, tid=call.tid, **args)
    else:
        record_span("xla." + leg, start, end - start, tid="compile", **args)


monitoring.register_event_listener(_on_event)
monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_time_span_listener(_on_time_span)


def executable_memory(jitted, *args) -> Optional[Dict[str, int]]:
    """Bytes the compiler reckons the executable of ``jitted(*args)``
    needs: ``argument``, ``output``, ``alias`` (outputs that reuse a
    donated argument), ``temp`` and ``code``; it holds ``argument +
    output - alias + temp + code`` at its peak. Called after
    ``jitted(*args)`` has run, so jax finds trace, lowering and
    executable in its caches: nothing is traced or compiled again (a
    donated argument still says its shape). ``None`` on a backend that
    has no such analysis."""
    stats = jitted.lower(*args).compile().memory_analysis()
    if stats is None:
        return None
    return {
        "argument": int(stats.argument_size_in_bytes),
        "output": int(stats.output_size_in_bytes),
        "alias": int(stats.alias_size_in_bytes),
        "temp": int(stats.temp_size_in_bytes),
        "code": int(stats.generated_code_size_in_bytes),
    }

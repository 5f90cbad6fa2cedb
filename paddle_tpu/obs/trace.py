"""Per-request distributed tracing: Dapper-style spans in a bounded
per-process ring, exportable as Chrome trace-event JSON (loadable in
Perfetto / ``chrome://tracing``), stitchable across processes.

Model:

- A **trace** is one request's journey, identified by ``trace_id`` — a
  random 16-hex id minted at admission and carried on ``GenRequest``,
  the cluster wire records, and the disagg handoff payload header, so
  a decode-worker span parents correctly across the process boundary.
- A **span** is one named leg (``admission``, ``route``, ``prefill``,
  ``handoff_send``, ``handoff_recv``, ``decode``, ``dispatch``,
  ``harvest``) with a start time, a duration, and a parent span id.
  Use the :func:`span` context manager for synchronous legs and the
  explicit :func:`start_span`/:func:`finish_span` pair for async legs
  (the overlap copy ring issues a dispatch span at submit time and
  finishes it at harvest, possibly many steps later);
  :func:`record_span` records a leg someone else timed, from its start
  and duration (``obs/compile.py``: jax's lowering and compile events).
- An **instant** is a zero-duration event (watchdog escalation,
  rollback, chaos injection) that lands on the same timeline as the
  request spans.

Recording is a deque append — bounded (``TraceRing``), allocation-light,
and togglable: :func:`set_enabled(False)` turns every record into a
no-op while keeping id propagation intact, which is what the
``serving_throughput.py --obs`` A/B measures. Timestamps are wall-clock
(``time.time()``) so per-worker ring dumps from different processes
merge on one axis; :func:`stitch_traces` unions dumps and
:func:`export_chrome_trace` renders either a single ring or a stitched
set.

**Two sinks, one primitive.** The ring's clock is this process's own; a
device trace's events are on the profiler's clock. So the context-manager
form :func:`span` also enters a ``jax.profiler.TraceAnnotation`` named
``pt:<span name>`` (``pt:to_static.call``, ``pt:route``) for the span's
lifetime, carrying ``trace_id`` / ``span_id`` / ``parent_id`` as stats.
While a profiler session runs (``paddle_tpu.profiler.Profiler``, or
``jax.profiler.start_trace``) the span is then an event of the xplane's
host plane, beside the device's ``XLA Ops`` line and on its clock, so a
device gap can be laid under the program span that was open; open the
trace directory in TensorBoard's profile plugin or Perfetto and look for
the ``pt:`` events. With no session the annotation costs a flag check.
This is the only place in ``paddle_tpu`` that opens a ``TraceAnnotation``.
The ring itself is dumped with ``python -m paddle_tpu.obs trace``.
"""
from __future__ import annotations

import collections
import json
import os
import random
import threading
import time
from typing import Dict, Iterable, List, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "TraceRing",
    "new_trace_id",
    "span",
    "start_span",
    "finish_span",
    "record_span",
    "instant",
    "trace_ctx",
    "ring",
    "set_enabled",
    "enabled",
    "set_process_label",
    "export_chrome_trace",
    "stitch_traces",
]


# ids need uniqueness, not unpredictability: the random module's C
# PRNG (urandom-seeded at import, reseeded after fork below) skips the
# per-span os.urandom syscall — ids are minted on the serving hot path
_ID_RNG = random.Random()


def new_trace_id() -> str:
    return "%016x" % _ID_RNG.getrandbits(64)


def _new_span_id() -> str:
    return "%012x" % _ID_RNG.getrandbits(48)


class Span:
    """One in-flight or finished span. Mutable on purpose: async legs
    hold the object open across steps and attach result args at
    finish."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id",
                 "dur", "ph", "tid", "args", "_t0")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], tid: str, args: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self._t0 = time.perf_counter()
        self.dur = None
        self.ph = "X"
        self.tid = tid
        self.args = args

    @property
    def ts(self) -> float:
        # wall-clock start derived from the per-process anchor: one
        # clock read per span instead of two, still mergeable across
        # process rings (drift over a serve window is visualization-
        # negligible)
        return _WALL0 + self._t0

    def ctx(self) -> dict:
        """The carryable context: what rides a wire record / handoff
        header so the far side can parent its spans under this one."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def to_dict(self) -> dict:
        return {
            "name": self.name, "trace_id": self.trace_id,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "ts": self.ts, "dur": self.dur, "ph": self.ph,
            "proc": _PROC_LABEL, "pid": _PID, "tid": self.tid,
            "args": self.args,
        }


class TraceRing:
    """Bounded ring of FINISHED events (spans + instants)."""

    def __init__(self, capacity: int = 8192):
        self._ring = collections.deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self.n_recorded = 0

    @property
    def n_dropped(self) -> int:
        # derived, not tracked: keeps record() to an append + counter
        return max(0, self.n_recorded - self._ring.maxlen)

    def record(self, event) -> None:
        # lock-free hot path: deque.append is GIL-atomic (maxlen evicts
        # inside the same bytecode op) and the counter is advisory —
        # the lock guards only the dump/clear snapshots. Accepts a dict
        # OR a finished Span — Spans materialize lazily at dump() so
        # the serving step never pays the 11-key dict build
        self._ring.append(event)
        self.n_recorded += 1

    def dump(self) -> List[dict]:
        with self._lock:
            return [e.to_dict() if isinstance(e, Span) else e
                    for e in self._ring]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.n_recorded = 0

    def __len__(self) -> int:
        return len(self._ring)


_RING = TraceRing()
_ENABLED = True
# wall-clock anchor for Span.ts: ts = _WALL0 + perf_counter()
_WALL0 = time.time() - time.perf_counter()
_PID = os.getpid()
_PROC_LABEL = f"pid{_PID}"


def _refork():  # keep cached pid + id stream honest in forked workers
    global _PID, _PROC_LABEL
    old, _PID = _PID, os.getpid()
    if _PROC_LABEL == f"pid{old}":
        _PROC_LABEL = f"pid{_PID}"
    _ID_RNG.seed(os.urandom(16))


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_refork)


def ring() -> TraceRing:
    return _RING


def set_enabled(on: bool) -> bool:
    """Toggle span/instant RECORDING (id propagation stays on so a
    re-enable mid-request still stitches). Returns the previous
    state."""
    global _ENABLED
    prev, _ENABLED = _ENABLED, bool(on)
    return prev


def enabled() -> bool:
    return _ENABLED


def set_process_label(label: str) -> None:
    """Name this process's track in exported timelines (e.g. the
    disagg worker id instead of a bare pid)."""
    global _PROC_LABEL
    _PROC_LABEL = str(label)


def trace_ctx(obj) -> Optional[dict]:
    """Extract a carryable trace context from a Span, a context dict,
    or an object with ``trace_id``/``span_id`` attributes (GenRequest);
    None when the object carries no trace."""
    if obj is None:
        return None
    if isinstance(obj, Span):
        return obj.ctx()
    if isinstance(obj, dict):
        tid = obj.get("trace_id")
        return {"trace_id": tid, "span_id": obj.get("span_id")} \
            if tid else None
    tid = getattr(obj, "trace_id", None)
    if not tid:
        return None
    return {"trace_id": tid, "span_id": getattr(obj, "span_id", None)}


def _resolve_parent(trace_id, parent):
    ctx = trace_ctx(parent)
    if ctx is not None:
        return ctx["trace_id"], ctx.get("span_id")
    return trace_id, None


def start_span(name: str, *, trace_id: Optional[str] = None,
               parent=None, tid: str = "main", **args) -> Span:
    """Open a span. ``parent`` may be a Span, a carried context dict,
    or any object with trace_id/span_id attributes; when it carries a
    trace the span joins it, otherwise ``trace_id`` (or a fresh id) is
    used. Always returns a usable Span — recording is decided at
    finish time. The ``start_span`` / ``finish_span`` pair is for
    lifetimes that do not nest (a dispatch issued in one engine step
    and harvested in a later one), which a profiler annotation cannot
    express: these spans reach the ring only, never the device trace.
    Use :func:`span` for anything that should be seen beside the
    device's ops."""
    ptrace, pspan = _resolve_parent(trace_id, parent)
    return Span(name, ptrace or new_trace_id(), _new_span_id(), pspan,
                tid, args)


def finish_span(sp: Optional[Span], **args) -> Optional[Span]:
    """Close a span and record it (when tracing is enabled). Extra
    kwargs merge into the span's args. Idempotent-ish: a second finish
    records a second event, so callers own at-most-once."""
    if sp is None:
        return None
    sp.dur = time.perf_counter() - sp._t0
    if args:
        sp.args.update(args)
    if _ENABLED:
        _RING.record(sp)
    return sp


def record_span(name: str, start: float, dur: float, *,
                trace_id: Optional[str] = None, parent=None,
                tid: str = "main", **args) -> Optional[Span]:
    """Record a span that is already over: ``start`` on the wall clock
    (``time.time()``), ``dur`` in seconds, both as someone else measured
    them (jax's monitoring events give a compile's start and end after
    it has ended). ``parent`` as for :func:`start_span`. The ring only:
    a profiler annotation cannot be opened in the past."""
    if not _ENABLED:
        return None
    sp = start_span(name, trace_id=trace_id, parent=parent, tid=tid, **args)
    sp._t0 = start - _WALL0
    sp.dur = dur
    _RING.record(sp)
    return sp


# prefix of every annotation this module writes into a profiler trace:
# a reader that filters host events by its own bare names (``harvest``,
# ``train.step``) never picks up a program span of the same name
ANNOTATION_PREFIX = "pt:"


class _SpanCtx:
    __slots__ = ("_span", "_annotation")

    def __init__(self, sp: Span):
        self._span = sp
        self._annotation = None

    def __enter__(self) -> Span:
        # the second sink: an event on the profiler's clock, only while
        # a profiler session runs (is_enabled is a flag read)
        if _ENABLED and TraceAnnotation.is_enabled():
            sp = self._span
            self._annotation = TraceAnnotation(
                ANNOTATION_PREFIX + sp.name, trace_id=sp.trace_id,
                span_id=sp.span_id, parent_id=sp.parent_id or "")
            self._annotation.__enter__()
        return self._span

    def __exit__(self, exc_type, exc, tb):
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if exc_type is not None:
            self._span.args.setdefault("error", exc_type.__name__)
        finish_span(self._span)
        return False


def span(name: str, *, trace_id: Optional[str] = None, parent=None,
         tid: str = "main", **args) -> _SpanCtx:
    """Context-manager form for synchronous legs::

        with obs.span("route", parent=req) as sp:
            ...

    Records to the ring at exit and, while a profiler session runs,
    is a ``pt:<name>`` event of the device trace's host plane (see the
    module docstring)."""
    return _SpanCtx(start_span(name, trace_id=trace_id, parent=parent,
                               tid=tid, **args))


def instant(name: str, *, trace_id: Optional[str] = None, parent=None,
            tid: str = "main", **args) -> None:
    """Record a zero-duration event (watchdog/rollback/chaos markers)
    on the same timeline as the spans."""
    if not _ENABLED:
        return
    ptrace, pspan = _resolve_parent(trace_id, parent)
    sp = Span(name, ptrace or "", _new_span_id(), pspan, tid, args)
    sp.ph = "i"
    sp.dur = 0.0
    _RING.record(sp)


# ---------------------------------------------------------------------------
# Export / cross-process stitch


def stitch_traces(dumps: Iterable[List[dict]],
                  trace_id: Optional[str] = None) -> List[dict]:
    """Union per-worker ring dumps into one event list sorted by
    timestamp, optionally filtered to a single ``trace_id`` — the
    cross-process merge a 2-process disagg deployment needs to see one
    request's admission→handoff→decode tree on one timeline."""
    merged: List[dict] = []
    for d in dumps:
        for ev in d:
            if trace_id is None or ev.get("trace_id") == trace_id:
                merged.append(ev)
    merged.sort(key=lambda e: (e.get("ts", 0.0), e.get("span_id", "")))
    return merged


def export_chrome_trace(events: Optional[List[dict]] = None,
                        path: Optional[str] = None) -> List[dict]:
    """Render ring events (default: this process's ring) as Chrome
    trace-event JSON objects; optionally write ``{"traceEvents": ...}``
    to ``path`` for Perfetto. Span events use phase "X"
    (complete), instants phase "i"; trace/span/parent ids ride in
    ``args`` so the tree is reconstructable from the file alone."""
    if events is None:
        events = _RING.dump()
    procs: Dict[str, int] = {}
    tids: Dict[tuple, int] = {}
    out: List[dict] = []
    meta: List[dict] = []
    for ev in events:
        proc = str(ev.get("proc", ev.get("pid", 0)))
        if proc not in procs:
            procs[proc] = len(procs) + 1
            meta.append({"ph": "M", "name": "process_name",
                         "pid": procs[proc], "tid": 0,
                         "args": {"name": proc}})
        pid = procs[proc]
        tkey = (proc, str(ev.get("tid", "main")))
        if tkey not in tids:
            tids[tkey] = len([k for k in tids if k[0] == proc]) + 1
            meta.append({"ph": "M", "name": "thread_name",
                         "pid": pid, "tid": tids[tkey],
                         "args": {"name": tkey[1]}})
        entry = {
            "name": ev["name"],
            "cat": "obs",
            "ph": ev.get("ph", "X"),
            "ts": ev["ts"] * 1e6,
            "pid": pid,
            "tid": tids[tkey],
            "args": {
                "trace_id": ev.get("trace_id"),
                "span_id": ev.get("span_id"),
                "parent_id": ev.get("parent_id"),
                **(ev.get("args") or {}),
            },
        }
        if entry["ph"] == "X":
            entry["dur"] = max(ev.get("dur") or 0.0, 0.0) * 1e6
        else:
            entry["s"] = "p"
        out.append(entry)
    doc = meta + out
    if path is not None:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({"traceEvents": doc,
                       "displayTimeUnit": "ms"}, fh)
    return doc

"""Profiler core (ref: python/paddle/profiler/profiler.py:346).

Since ISSUE 12 this is a thin adapter over :mod:`paddle_tpu.obs`: every
:class:`RecordEvent` is an ``obs.span`` (so user annotations land on the
same Perfetto timeline as the serving/request spans and, through the
span's own ``pt:`` annotation, beside the device's ops in a profiler
trace) and step / event durations feed registry histograms readable via
``python -m paddle_tpu.obs dump``. :class:`Profiler` drives
``jax.profiler.start_trace`` / ``stop_trace``; while it records, every
``obs.span`` of the program (``pt:to_static.call`` and its legs among
them) is an event of the trace's host plane.
"""
from __future__ import annotations

import enum
import os
import time
from typing import Callable, Iterable, Optional, Union

from .. import obs as _obs
from ..obs.metrics import registry as _obs_registry

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
    "make_scheduler", "export_chrome_tracing",
]


class ProfilerState(enum.Enum):
    """ref: profiler.py ProfilerState."""

    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    """ref: profiler.py ProfilerTarget — GPU/XPU become the TPU target."""

    CPU = 0
    GPU = 1
    TPU = 1  # alias: the device tracer is one XLA trace


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """ref: profiler.py make_scheduler — same state machine."""

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        period = closed + ready + record
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


# the innermost active Profiler; RecordEvent spans report here so
# summary() can print the user-annotation table (ref:
# profiler_statistic.py UserDefined view)
_active_profiler = None


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """ref: profiler.py export_chrome_tracing — returns an
    on_trace_ready callback; the jax trace directory is TensorBoard's
    profile format (open via tensorboard --logdir or Perfetto)."""

    def handler(prof: "Profiler"):
        prof._exported_dir = dir_name

    handler._dir = dir_name
    return handler


class RecordEvent:
    """User span annotation (ref: profiler/utils.py RecordEvent): an
    ``obs.span`` named ``profiler:<name>`` — so it lands in the obs ring
    and, while a profiler session runs, in the device trace's host
    plane as ``pt:profiler:<name>`` — with the duration folded into the
    ``profiler_event_seconds`` histogram and the active Profiler's
    UserDefined summary."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ctx = None
        self.begin_ns = None
        self.end_ns = None

    def begin(self):
        self.begin_ns = time.perf_counter_ns()
        self._ctx = _obs.span(f"profiler:{self.name}", tid="profiler")
        self._ctx.__enter__()

    def end(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
            self.end_ns = time.perf_counter_ns()
            _obs_registry().histogram(
                "profiler_event_seconds", {"name": self.name},
                help="RecordEvent span durations").observe(
                    (self.end_ns - self.begin_ns) * 1e-9)
            if _active_profiler is not None:
                _active_profiler._events.append(
                    (self.name, self.end_ns - self.begin_ns))

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """ref: profiler.py:346 Profiler — start/stop/step/export surface.

    The XLA trace captures device + host activity between start and
    stop; scheduler transitions drive jax.profiler.start_trace /
    stop_trace so only RECORD windows hit the (expensive) tracer.
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None,
                 record_shapes: bool = False, profile_memory: bool = False,
                 timer_only: bool = False, emit_nvtx: bool = False,
                 custom_device_types=None, with_flops: bool = False):
        if scheduler is None:
            self._scheduler = _default_state_scheduler
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1
            )
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._dir = getattr(on_trace_ready, "_dir", None) or "./profiler_log"
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._exported_dir = None
        self._step_times = []
        self._last_step_t = None
        self._events = []  # completed RecordEvent spans (name, dur_ns)

    # -- lifecycle -----------------------------------------------------
    def start(self):
        global _active_profiler
        self._prev_active = _active_profiler  # stack discipline: an
        # inner profiler must not deregister the outer one on stop
        _active_profiler = self
        self._state = self._scheduler(self.step_num)
        self._transition()
        self._last_step_t = time.perf_counter()

    def stop(self):
        global _active_profiler
        if _active_profiler is self:
            _active_profiler = getattr(self, "_prev_active", None)
        if self._tracing:
            self._stop_trace()
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        self._state = ProfilerState.CLOSED

    def step(self, num_steps: int = 1):
        now = time.perf_counter()
        if self._last_step_t is not None:
            per = (now - self._last_step_t) / num_steps
            self._step_times.append(per)
            _obs_registry().histogram(
                "profiler_step_seconds",
                help="Profiler.step() inter-step wall time").observe(per)
        self._last_step_t = now
        self.step_num += num_steps
        new_state = self._scheduler(self.step_num)
        if new_state != self._state:
            self._state = new_state
            self._transition()

    def _transition(self):
        should_trace = self._state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN
        ) and not self._timer_only
        if should_trace and not self._tracing:
            self._start_trace()
        elif not should_trace and self._tracing:
            self._stop_trace()

    def _start_trace(self):
        import jax.profiler

        os.makedirs(self._dir, exist_ok=True)
        try:
            jax.profiler.start_trace(self._dir)
            self._tracing = True
        except RuntimeError:
            # tracer already active (nested profilers) — skip
            self._tracing = False

    def _stop_trace(self):
        import jax.profiler

        try:
            jax.profiler.stop_trace()
        finally:
            self._tracing = False
            self._exported_dir = self._dir

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting -----------------------------------------------------
    def _collect_trace_ops(self):
        """Aggregate the captured XLA trace's complete events into
        per-op statistics, grouped by execution lane.

        The jax tracer writes the TensorBoard profile format; the
        chrome-trace file inside it carries one complete ('ph':'X')
        event per executed op/kernel with its duration, and 'M'
        metadata events naming each pid's lane ('/device:TPU:0 ...',
        host threads, ...). This is the device-event source the
        reference aggregates in profiler_statistic.py.

        Returns {lane_label: {op_name: [count, total_us, max_us]}}.
        """
        import glob
        import gzip
        import json as _json

        trace_dir = self._exported_dir or self._dir
        paths = sorted(
            glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.trace.json.gz")),
            key=os.path.getmtime)
        if not paths:
            return {}
        with gzip.open(paths[-1], "rt") as f:
            events = _json.load(f).get("traceEvents", [])
        pid_label = {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                pid_label[e.get("pid")] = e.get("args", {}).get("name", "?")
        lanes = {}
        for e in events:
            if e.get("ph") != "X" or "dur" not in e:
                continue
            name = e.get("name", "?")
            if name.startswith(("$", "<")):
                # raw python source frames ("$file.py:123 fn") — the
                # table shows logical ops/kernels, like the reference's
                continue
            label = pid_label.get(e.get("pid"), "?")
            ops = lanes.setdefault(label, {})
            st = ops.setdefault(name, [0, 0.0, 0.0])
            st[0] += 1
            st[1] += float(e["dur"])
            st[2] = max(st[2], float(e["dur"]))
        return lanes

    @staticmethod
    def _print_table(title, rows, total_us, top_k):
        """rows: [(name, count, total_us, max_us)] — the reference's
        op-summary table shape (profiler_statistic.py _build_table)."""
        print(f"\n{'-' * 78}\n{title}\n{'-' * 78}")
        print(f"{'Name':<40} {'Calls':>6} {'Total(ms)':>10} "
              f"{'Avg(ms)':>9} {'Max(ms)':>9} {'Ratio':>6}")
        for name, count, tot, mx in rows[:top_k]:
            ratio = tot / total_us if total_us else 0.0
            shown = name if len(name) <= 40 else name[:37] + "..."
            print(f"{shown:<40} {count:>6} {tot / 1000:>10.3f} "
                  f"{tot / 1000 / max(count, 1):>9.3f} {mx / 1000:>9.3f} "
                  f"{ratio:>6.1%}")

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", top_k: int = 20):
        """Step-time overview + per-op device/host tables aggregated
        from the captured trace + user RecordEvent spans + a device
        memory view (ref: profiler/profiler_statistic.py — overview,
        op summary, UserDefined and memory views)."""
        import numpy as np

        if self._step_times:
            ts = np.asarray(self._step_times) * 1000.0
            print(
                f"Profiler summary over {len(ts)} steps: "
                f"mean {ts.mean():.3f} ms, p50 {np.percentile(ts, 50):.3f} ms, "
                f"p99 {np.percentile(ts, 99):.3f} ms"
                + (f"; trace exported to {self._exported_dir}"
                   if self._exported_dir else "")
            )
        else:
            print("Profiler: no steps recorded")

        if op_detail:
            lanes = self._collect_trace_ops()
            order = sorted_by or SortedKeys.GPUTotal
            key = {
                SortedKeys.GPUMax: lambda r: r[3],
                SortedKeys.CPUMax: lambda r: r[3],
                SortedKeys.GPUAvg: lambda r: r[2] / max(r[1], 1),
                SortedKeys.CPUAvg: lambda r: r[2] / max(r[1], 1),
            }.get(order, lambda r: r[2])
            # device lanes first (the tables that matter), then host
            def lane_rank(label):
                return (0 if "device" in label.lower()
                        or "tpu" in label.lower() else 1, label)

            for label in sorted(lanes, key=lane_rank):
                rows = sorted(
                    ((n, c, t, m) for n, (c, t, m) in lanes[label].items()),
                    key=key, reverse=True)
                total = sum(r[2] for r in rows)
                self._print_table(f"Op summary — {label}", rows, total,
                                  top_k)

        if self._events:
            agg = {}
            for name, dur_ns in self._events:
                st = agg.setdefault(name, [0, 0.0, 0.0])
                st[0] += 1
                st[1] += dur_ns / 1000.0
                st[2] = max(st[2], dur_ns / 1000.0)
            rows = sorted(((n, c, t, m) for n, (c, t, m) in agg.items()),
                          key=lambda r: r[2], reverse=True)
            self._print_table("UserDefined summary (RecordEvent)", rows,
                              sum(r[2] for r in rows), top_k)

        # memory view: live device telemetry (ref MemorySummary)
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
        except Exception:
            stats = {}
        if stats:
            used = stats.get("bytes_in_use", 0)
            peak = stats.get("peak_bytes_in_use", 0)
            limit = stats.get("bytes_limit", 0)
            print(f"\nDevice memory: in use {used / 2**20:.1f} MiB, "
                  f"peak {peak / 2**20:.1f} MiB"
                  + (f", limit {limit / 2**20:.1f} MiB" if limit else ""))

    def export(self, path: Optional[str] = None, format: str = "json"):
        return self._exported_dir


class SortedKeys(enum.Enum):
    """ref: profiler/profiler_statistic.py SortedKeys — summary sort
    orders."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(enum.Enum):
    """ref: profiler/profiler.py SummaryView — which summary tables to
    print."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """ref: profiler.py export_protobuf — on-trace-ready handler writing
    the profile under ``dir_name``. jax.profiler already emits xplane
    protobufs, so this is the same handler as export_chrome_tracing with
    the protobuf layout kept."""
    return export_chrome_tracing(dir_name, worker_name)


def load_profiler_result(filename: str):
    """ref: profiler.py load_profiler_result — load an exported trace.
    Returns the raw bytes of the xplane/trace file (the reference
    returns a ProfilerResult handle; the TPU trace is consumed by
    TensorBoard/Perfetto rather than an in-process reader)."""
    with open(filename, "rb") as f:
        return f.read()

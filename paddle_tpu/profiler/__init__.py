"""paddle_tpu.profiler — performance tracing.

ref: python/paddle/profiler/ — profiler.py:346 (Profiler with
ProfilerTarget/scheduler/on_trace_ready), utils.py (RecordEvent),
timer.py:394 (benchmark ips tracking).

TPU-native redesign: the device-side tracer is jax.profiler (XLA/TPU
trace via TensorBoard's profile plugin — the role kineto/CUPTI plays in
the reference); RecordEvent opens an ``obs.span``, whose ``pt:``
annotation puts user spans inside the device trace. The chrome-trace exporter
writes the TensorBoard profile directory; ``make_scheduler`` reproduces
the reference's CLOSED/READY/RECORD state machine.
"""
from .profiler import (
    SortedKeys,
    SummaryView,
    export_protobuf,
    load_profiler_result,
)  # noqa: F401
from .profiler import (  # noqa: F401
    Profiler,
    ProfilerState,
    ProfilerTarget,
    RecordEvent,
    export_chrome_tracing,
    make_scheduler,
)
from .timer import benchmark  # noqa: F401

__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "benchmark",
]

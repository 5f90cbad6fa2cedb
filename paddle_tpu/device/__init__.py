"""paddle_tpu.device — device management, streams/events, memory stats.

ref: python/paddle/device/ — __init__.py (set_device/get_device/
synchronize), cuda/ (Stream/Event, memory stats :places). TPU-native
mapping:

- Streams/events: XLA owns scheduling — there is exactly one compute
  stream per TPU core and the runtime orders collectives/compute for
  you (the latency-hiding scheduler). Stream/Event keep the reference
  API; recording an Event snapshots a marker array and
  ``synchronize``/``wait`` block on it (real device sync points).
- Memory stats come from jax's per-device allocator telemetry
  (device.memory_stats()), replacing the reference's
  StatAllocator counters (§2.10).
"""
from __future__ import annotations

from typing import Optional

import jax

from ..base.device import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    device_count,
    get_device,
    get_place,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    set_device,
)

__all__ = [
    "set_device", "get_device", "device_count", "synchronize", "Stream",
    "Event", "current_stream", "stream_guard", "max_memory_allocated",
    "max_memory_reserved", "memory_allocated", "memory_reserved",
    "empty_cache", "get_device_properties", "Place", "CPUPlace",
    "TPUPlace", "CUDAPlace",
]


def _jax_device(device=None) -> jax.Device:
    if device is None:
        return jax.devices()[0]
    if isinstance(device, jax.Device):
        return device
    if isinstance(device, Place):
        return device.jax_device()
    if isinstance(device, int):
        return jax.devices()[device]
    return jax.devices()[0]


def synchronize(device=None):
    """Block until all queued work on the device is done (ref:
    device/__init__.py synchronize — cudaDeviceSynchronize)."""
    d = _jax_device(device)
    import jax.numpy as jnp

    from ..distributed.communication.watchdog import watch

    # a trivial computation ordered after everything in-flight
    with watch(f"device.synchronize({d})"):
        jax.device_put(jnp.zeros(()), d).block_until_ready()


# ---------------------------------------------------------------------------
# memory stats (ref: device/cuda/__init__.py max_memory_allocated etc.)
# ---------------------------------------------------------------------------


def _stats(device=None) -> dict:
    d = _jax_device(device)
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    return int(_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    s = _stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    s = _stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    s = _stats(device)
    return int(s.get("peak_bytes_reserved", s.get("peak_bytes_in_use", 0)))


def empty_cache():
    """ref: device/cuda empty_cache — XLA's allocator has no user-facing
    cache flush; provided as a no-op for API parity."""


def get_device_properties(device=None):
    d = _jax_device(device)

    class _Props:
        name = getattr(d, "device_kind", str(d))
        total_memory = int(_stats(device).get("bytes_limit", 0))
        multi_processor_count = getattr(d, "core_count", 1)
        major, minor = 0, 0

        def __repr__(self):
            return (
                f"DeviceProperties(name='{self.name}', "
                f"total_memory={self.total_memory})"
            )

    return _Props()


# ---------------------------------------------------------------------------
# streams / events (ref: device/__init__.py Stream :797, Event :700)
# ---------------------------------------------------------------------------


class Event:
    """ref: device Event — record/query/synchronize. Recording captures
    a marker ordered after currently-queued work."""

    def __init__(self, device=None, enable_timing: bool = False,
                 blocking: bool = False, interprocess: bool = False):
        self._device = _jax_device(device)
        self._marker = None
        self._enable_timing = enable_timing
        self._t = None

    def record(self, stream: Optional["Stream"] = None):
        import time

        import jax.numpy as jnp

        self._marker = jax.device_put(jnp.zeros(()), self._device)
        if self._enable_timing:
            self._t = time.perf_counter()

    def query(self) -> bool:
        if self._marker is None:
            return True
        return self._marker.is_ready() if hasattr(self._marker, "is_ready") else True

    def synchronize(self):
        if self._marker is not None:
            self._marker.block_until_ready()

    def elapsed_time(self, end: "Event") -> float:
        if self._t is None or end._t is None:
            raise RuntimeError("events must be created with enable_timing=True")
        return (end._t - self._t) * 1000.0


class Stream:
    """ref: device Stream — on TPU there is one XLA compute stream per
    core; this object exists for API parity and to order host-side
    waits (wait_event/wait_stream/synchronize are real sync points)."""

    def __init__(self, device=None, priority: int = 2):
        self._device = _jax_device(device)
        self.priority = priority

    def wait_event(self, event: Event):
        event.synchronize()

    def wait_stream(self, stream: "Stream"):
        synchronize(stream._device)

    def record_event(self, event: Optional[Event] = None) -> Event:
        event = event or Event(self._device)
        event.record(self)
        return event

    def synchronize(self):
        synchronize(self._device)

    def query(self) -> bool:
        return True


_current_streams: dict = {}
_stream_override: Optional[Stream] = None


def current_stream(device=None) -> Stream:
    d = _jax_device(device)
    # a stream_guard override applies only to its own device
    if _stream_override is not None and (
        device is None or _stream_override._device.id == d.id
    ):
        return _stream_override
    if d.id not in _current_streams:
        _current_streams[d.id] = Stream(d)
    return _current_streams[d.id]


class stream_guard:
    """ref: device stream_guard — context selecting the ambient stream;
    single-stream on TPU, so this only swaps the handle."""

    def __init__(self, stream: Stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        global _stream_override
        self._prev = _stream_override
        _stream_override = self._stream
        return self._stream

    def __exit__(self, *exc):
        global _stream_override
        _stream_override = self._prev
        return False


# cuda-namespace parity (paddle.device.cuda.*) — maps to the TPU
class cuda:
    Stream = Stream
    Event = Event
    current_stream = staticmethod(current_stream)
    stream_guard = stream_guard
    synchronize = staticmethod(synchronize)
    max_memory_allocated = staticmethod(max_memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    memory_allocated = staticmethod(memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    empty_cache = staticmethod(empty_cache)
    get_device_properties = staticmethod(get_device_properties)

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def get_device_name(device=None):
        """ref: device/cuda get_device_name — the accelerator's name
        (here the TPU device kind, e.g. 'TPU v5 lite')."""
        props = get_device_properties(device)
        return getattr(props, "name", str(props))

    @staticmethod
    def get_device_capability(device=None):
        """ref: device/cuda get_device_capability — (major, minor). CUDA
        compute capability has no TPU analogue; the TPU generation is
        reported as (generation, 0), parsed from the device kind."""
        import re

        name = cuda.get_device_name(device)
        m = re.search(r"v(\d+)", str(name))
        return (int(m.group(1)), 0) if m else (0, 0)


class xpu:
    """paddle.device.xpu parity (ref: device/xpu/__init__.py — one
    public name; XPU has no TPU analogue, synchronize maps to the
    device barrier)."""

    synchronize = staticmethod(synchronize)


# -- parity sweep (ref: python/paddle/device/__init__.py remaining) ---------
from ..base.device import CPUPlace as _CPUPlace


class XPUPlace(_CPUPlace):
    """XPU has no TPU analogue; kept as a CPU place for ported code."""


class IPUPlace(_CPUPlace):
    """IPU has no TPU analogue; kept as a CPU place for ported code."""


def get_cudnn_version():
    """No cuDNN on TPU (ref device get_cudnn_version -> None when absent)."""
    return None


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    """XLA plays CINN's role; the CINN-specific API reports False."""
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    """Distributed is always built in (XLA collectives)."""
    return True


def is_compiled_with_custom_device(device_type: str) -> bool:
    """TPU is the 'custom device' of this build (ref custom_device query)."""
    return device_type == "tpu"


def get_all_device_type():
    import jax as _jax

    kinds = {"cpu"}
    try:
        kinds.update(d.platform for d in _jax.devices())
    except Exception:
        pass
    return sorted(kinds)


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    import jax as _jax

    return [f"{d.platform}:{d.id}" for d in _jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device() if not d.startswith(("cpu", "gpu"))]


def set_stream(stream=None):
    """XLA orders work per-device automatically; returns the current
    stream for parity (ref device set_stream)."""
    return current_stream()

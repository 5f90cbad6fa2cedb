"""Device / Place abstraction.

The reference's Place hierarchy (ref: paddle/phi/common/place.h:135) routes
kernels between CPU/GPU/XPU. On TPU via JAX there is one accelerator type
and XLA owns streams, so Place collapses to a thin wrapper over
``jax.Device`` used for API parity (``paddle.set_device`` /
``tensor.place``). No user-visible streams exist (TPU has no user streams;
XLA async dispatch replaces them) — the stream/event API in
``paddle_tpu.device`` is a documented no-op.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base place. Compares by (kind, index)."""

    kind = "undefined"

    def __init__(self, index: int = 0):
        self.index = index

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.index == other.index
        )

    def __hash__(self):
        return hash((self.kind, self.index))

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    # -- mapping to jax ---------------------------------------------------
    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self.kind]
        if not devs:
            # Fall back to default backend (e.g. asking for TPU on a CPU-only
            # test host): mirrors the reference's backend fallback rules
            # (ref: paddle/phi/core/kernel_factory.h fallback to CPU).
            devs = jax.devices()
        return devs[self.index % len(devs)]


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    """The TPU analogue of GPUPlace (ref: paddle/phi/common/place.h:135)."""

    kind = "tpu"


class CUDAPlace(TPUPlace):
    """Compat alias: code written against the reference's CUDAPlace maps to
    the accelerator place on TPU."""


class CUDAPinnedPlace(CPUPlace):
    """Compat alias: pinned host memory has no TPU analogue (transfers
    stage through the PJRT host buffer); behaves as CPUPlace."""


_current_device = [None]  # type: list


def set_device(device) -> Place:
    """paddle.set_device parity (ref: python/paddle/device/__init__.py).

    Accepts 'tpu', 'tpu:0', 'cpu', 'gpu' (alias of tpu), or a Place.
    """
    place = _parse_place(device)
    _current_device[0] = place
    return place


def get_device() -> str:
    p = get_place()
    return f"{p.kind}:{p.index}"


def get_place() -> Place:
    if _current_device[0] is None:
        _current_device[0] = _default_place()
    return _current_device[0]


@functools.lru_cache(maxsize=None)
def _accelerator_available() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def _default_place() -> Place:
    return TPUPlace(0) if _accelerator_available() else CPUPlace(0)


def _parse_place(device) -> Place:
    if isinstance(device, Place):
        return device
    if isinstance(device, jax.Device):
        return (TPUPlace if device.platform == "tpu" else CPUPlace)(device.id)
    s = str(device).lower()
    idx = 0
    if ":" in s:
        s, i = s.split(":", 1)
        idx = int(i)
    if s in ("tpu", "gpu", "cuda", "xpu", "npu"):
        return TPUPlace(idx)
    if s == "cpu":
        return CPUPlace(idx)
    raise ValueError(f"unknown device {device!r}")


def device_count() -> int:
    return len(jax.local_devices())


def is_compiled_with_cuda() -> bool:  # parity shim
    return False


def is_compiled_with_tpu() -> bool:
    return _accelerator_available()

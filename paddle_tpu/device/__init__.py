"""Device / Place layer.

TPU-native equivalent of the reference's Place/Backend machinery
(reference: paddle/phi/common/place.h:31-39, python/paddle/device/__init__.py:284
set_device). Here 'tpu' is the first-class backend; 'cpu' always exists; any
platform jax exposes (gpu, ...) is addressable through the same API.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CustomPlace",
    "set_device", "get_device", "get_all_devices", "device_count",
    "is_compiled_with_tpu", "jax_device", "current_jax_device",
    "synchronize",
]


class Place:
    """A (device_type, device_id) pair, resolvable to a concrete jax.Device."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> Optional[jax.Device]:
        return _resolve_jax_device(self.device_type, self.device_id)

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"


def CPUPlace(idx: int = 0) -> Place:
    return Place("cpu", idx)


def TPUPlace(idx: int = 0) -> Place:
    return Place("tpu", idx)


def CustomPlace(device_type: str, idx: int = 0) -> Place:
    """Counterpart of the reference's pluggable CustomPlace
    (paddle/phi/common/place.h:41 CustomRegisteredDeviceMap)."""
    return Place(device_type, idx)


def _platform_of(dev: jax.Device) -> str:
    return dev.platform.lower()


def _resolve_jax_device(device_type: str, device_id: int) -> Optional[jax.Device]:
    for d in jax.devices():
        if _platform_of(d) == device_type and d.id == device_id:
            return d
    # fall back to local index within the platform
    same = [d for d in jax.devices() if _platform_of(d) == device_type]
    if same and device_id < len(same):
        return same[device_id]
    if device_type == "cpu":
        try:
            return jax.devices("cpu")[device_id]
        except RuntimeError:
            return None
    return None


_state = threading.local()


def _default_place() -> Place:
    try:
        d = jax.devices()[0]
    except RuntimeError:
        return CPUPlace()
    return Place(_platform_of(d), d.id)


def set_device(device: str) -> Place:
    """paddle.device.set_device parity: 'tpu', 'tpu:0', 'cpu', ..."""
    if ":" in device:
        kind, _, idx = device.partition(":")
        place = Place(kind, int(idx))
    else:
        place = Place(device, 0)
    if place.jax_device() is None:
        raise ValueError(
            f"device '{device}' not available; visible platforms: "
            f"{sorted({_platform_of(d) for d in jax.devices()})}"
        )
    _state.place = place
    return place


def get_device() -> str:
    place = getattr(_state, "place", None) or _default_place()
    return f"{place.device_type}:{place.device_id}"


def current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        place = _default_place()
        _state.place = place
    return place


def current_jax_device() -> Optional[jax.Device]:
    return current_place().jax_device()


def jax_device(place=None) -> Optional[jax.Device]:
    if place is None:
        return current_jax_device()
    if isinstance(place, str):
        kind, _, idx = place.partition(":")
        place = Place(kind, int(idx or 0))
    return place.jax_device()


def get_all_devices():
    return [f"{_platform_of(d)}:{d.id}" for d in jax.devices()]


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        return len(jax.devices())
    return sum(1 for d in jax.devices() if _platform_of(d) == device_type)


def is_compiled_with_tpu() -> bool:
    return any(_platform_of(d) == "tpu" for d in jax.devices())


def synchronize(device=None):
    """Block until all outstanding device work completes
    (counterpart of paddle.device.synchronize)."""
    jax.effects_barrier()


def place_of_array(arr) -> Place:
    try:
        dev = list(arr.devices())[0]
        return Place(_platform_of(dev), dev.id)
    except Exception:
        return CPUPlace()


# -- streams & events -------------------------------------------------------
# parity: paddle.device.Stream/Event + stream_guard (python/paddle/device/
# __init__.py, device/cuda/streams.py). XLA owns real stream scheduling on
# TPU (one compute stream + DMA; the latency-hiding scheduler interleaves
# collectives), so these objects provide ORDERING semantics only: record/
# wait/synchronize map to effects barriers, and the "current stream" is a
# thread-local tag user code can branch on.

import threading as _threading
import time as _time


class Event:
    """parity: paddle.device.Event — records a point in the issue order."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._recorded = None
        self._enable_timing = enable_timing

    def record(self, stream=None):
        jax.effects_barrier()
        self._recorded = _time.perf_counter()

    def query(self) -> bool:
        return self._recorded is not None

    def synchronize(self):
        jax.effects_barrier()

    def elapsed_time(self, end_event) -> float:
        if self._recorded is None or end_event._recorded is None:
            raise RuntimeError("both events must be recorded")
        return (end_event._recorded - self._recorded) * 1000.0


class Stream:
    """parity: paddle.device.Stream — on TPU all work issues onto XLA's
    stream; wait_event/wait_stream/synchronize provide the ordering API."""

    def __init__(self, device=None, priority=2, blocking=False):
        self.device = device

    def wait_event(self, event: "Event"):
        event.synchronize()

    def wait_stream(self, stream: "Stream"):
        jax.effects_barrier()

    def record_event(self, event: "Event" = None) -> "Event":
        ev = event or Event()
        ev.record(self)
        return ev

    def synchronize(self):
        jax.effects_barrier()

    def query(self) -> bool:
        return True


_stream_tls = _threading.local()


def current_stream(device=None) -> Stream:
    cur = getattr(_stream_tls, "stream", None)
    if cur is None:
        cur = Stream(device)
        _stream_tls.stream = cur
    return cur


def set_stream(stream: Stream) -> Stream:
    prev = current_stream()
    _stream_tls.stream = stream
    return prev


class stream_guard:
    """parity: paddle.device.stream_guard context manager."""

    def __init__(self, stream: Stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)


class CUDAPlace(Place):
    """parity: paddle.CUDAPlace. This build targets TPU (CUDA disabled), so
    construction raises — matching the reference in a non-CUDA build
    (phi/common/place.h + is_compiled_with_cuda() checks) — while remaining
    a class so ``isinstance(place, paddle.CUDAPlace)`` works in ported
    code."""

    def __init__(self, idx: int = 0):
        raise RuntimeError(
            "CUDAPlace is unavailable: paddle_tpu is not compiled with "
            "CUDA. Use TPUPlace()/CPUPlace() instead.")


class CUDAPinnedPlace(Place):
    """parity: paddle.CUDAPinnedPlace (unavailable in a non-CUDA build)."""

    def __init__(self):
        raise RuntimeError(
            "CUDAPinnedPlace is unavailable: paddle_tpu is not compiled "
            "with CUDA.")


class XPUPlace(Place):
    """parity: paddle.XPUPlace (unavailable: no XPU in this build)."""

    def __init__(self, idx: int = 0):
        raise RuntimeError(
            "XPUPlace is unavailable: paddle_tpu is not compiled with XPU.")


class IPUPlace(Place):
    """parity: paddle.device.IPUPlace (unavailable: no IPU in this build)."""

    def __init__(self):
        raise RuntimeError(
            "IPUPlace is unavailable: paddle_tpu is not compiled with IPU.")


def get_all_device_type():
    """parity: device.get_all_device_type — device types visible to the
    runtime."""
    return sorted({_platform_of(d) for d in jax.devices()} | {"cpu"})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    return [f"{_platform_of(d)}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [s for s in get_available_device()
            if not s.startswith(("cpu", "gpu"))]


def get_cudnn_version():
    """parity: device.get_cudnn_version — None when CUDA is unavailable."""
    return None


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_distribute():
    return True


def is_compiled_with_custom_device(device_type: str) -> bool:
    """TPU rides the PJRT plugin mechanism — report it as the available
    custom device type."""
    return device_type in get_all_device_type()


from ._memory import (  # noqa: E402,F401
    empty_cache, max_memory_allocated, max_memory_reserved,
    memory_allocated, memory_reserved, reset_max_memory_allocated,
    reset_max_memory_reserved,
)
from . import cuda  # noqa: E402,F401
from . import xpu  # noqa: E402,F401

"""paddle.device analog.

Reference: python/paddle/device (set/get_device, Stream/Event,
stream_guard, synchronize, cuda.* memory stats). TPU-native: devices are
PJRT devices; "streams" map to JAX's async dispatch queue (one logical
stream per device — Stream/Event keep API parity and give real
happens-before via block_until_ready), and memory stats read PJRT's
allocator stats plus the native host-side stat registry.
"""
from __future__ import annotations

import contextlib
from typing import Optional

from ..core import native as _native


def _devices():
    import jax
    return jax.devices()


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu",
                                                          "tpu")]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in _devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if d.split(":")[0] not in ("cpu", "gpu", "tpu")]


def set_device(device: str):
    """Parity API: JAX owns placement; returns the canonical device str."""
    return device


def get_device() -> str:
    d = _devices()[0]
    return f"{d.platform}:{d.id}"


def device_count() -> int:
    return len(_devices())


def is_compiled_with_cuda():
    return False


def synchronize(device=None):
    """Block until all dispatched device work completes."""
    import jax
    (jax.device_put(0) + 0).block_until_ready()


class Event:
    """paddle.device.Event analog over async dispatch: record() captures the
    current tail of the dispatch queue; synchronize() waits for it."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._marker = None
        self._time_ns = None
        self.enable_timing = enable_timing

    def record(self, stream=None):
        import jax
        # a tiny op enqueued NOW: its completion == everything before it done
        self._marker = jax.device_put(0)
        if self.enable_timing:
            self._time_ns = _native.tracer_begin("device_event")

    def query(self) -> bool:
        if self._marker is None:
            return True
        return self._marker.is_ready()

    def synchronize(self):
        if self._marker is not None:
            self._marker.block_until_ready()
        if self._time_ns:
            _native.tracer_end(self._time_ns)

    def elapsed_time(self, end_event) -> float:
        return 0.0  # device-side timestamps come from the xplane profiler


class Stream:
    """paddle.device.Stream analog. XLA exposes one ordered async queue per
    device; Stream objects give API parity and wait_event/record ordering."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def record_event(self, event: Optional[Event] = None) -> Event:
        event = event or Event()
        event.record(self)
        return event

    def wait_event(self, event: Event):
        event.synchronize()

    def wait_stream(self, stream: "Stream"):
        synchronize()

    def synchronize(self):
        synchronize()

    def query(self):
        return True


_current_stream = Stream()


def current_stream(device=None) -> Stream:
    return _current_stream


@contextlib.contextmanager
def stream_guard(stream: Stream):
    """Parity context (one logical stream per device on this stack)."""
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    try:
        yield
    finally:
        _current_stream = prev


# -- memory stats (device.cuda.* parity, TPU-backed) -------------------------

def _pjrt_stats():
    import jax
    try:
        return jax.devices()[0].memory_stats() or {}
    except Exception:  # platform without memory_stats
        return {}


def memory_allocated(device=None) -> int:
    return int(_pjrt_stats().get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(_pjrt_stats().get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    s = _pjrt_stats()
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    return int(_pjrt_stats().get("peak_bytes_in_use", 0))


def empty_cache():
    return None


class cuda:
    """Namespace parity for paddle.device.cuda on the TPU stack."""
    Stream = Stream
    Event = Event
    current_stream = staticmethod(current_stream)
    stream_guard = staticmethod(stream_guard)
    synchronize = staticmethod(synchronize)
    device_count = staticmethod(device_count)
    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)
    empty_cache = staticmethod(empty_cache)


__all__ = ["set_device", "get_device", "device_count", "synchronize",
           "get_all_device_type", "get_all_custom_device_type",
           "get_available_device", "get_available_custom_device",
           "Stream", "Event", "current_stream", "stream_guard",
           "memory_allocated", "max_memory_allocated", "memory_reserved",
           "max_memory_reserved", "empty_cache", "cuda"]


from ..core.shims import XPUPlace  # noqa: E402


def get_cudnn_version():
    """No CUDA in this build (ref device.get_cudnn_version -> None when
    unavailable)."""
    return None


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    """XLA plays CINN's role (SURVEY.md N23); the CINN binary is absent."""
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_distribute():
    return True


def is_compiled_with_custom_device(device_type=None):
    return device_type == "tpu"


def set_stream(stream=None):
    """PJRT orders work per-device automatically; returns the prior stream
    handle for API parity."""
    return stream


class IPUPlace:
    def __init__(self, *a):
        raise RuntimeError("IPU is not available in the TPU build")

from . import topology  # noqa: E402  (ICI-aware device-manager tier)
__all__.append("topology")

"""Device API surface (``paddle.device`` parity).

Reference: ``python/paddle/device/__init__.py`` (set_device/get_device/
get_all_device_type/…) + ``device/cuda`` (Stream/Event/stream_guard,
memory stats). TPU-native design: PJRT/XLA owns streams, events, and memory
— dispatch is already async and ordered per device, so ``Stream``/``Event``
are real synchronization *facades* over that model (record/synchronize via
data-dependency barriers) rather than raw stream handles. Memory statistics
read PJRT's ``memory_stats()``.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional

import jax

from ..core.device import (  # noqa: F401
    device_count, get_all_devices, get_default_device, get_device,
    is_compiled_with_tpu, set_device, synchronize)

__all__ = [
    "set_device", "get_device", "get_all_devices", "device_count",
    "synchronize", "is_compiled_with_tpu", "get_all_device_type",
    "get_available_device", "get_device_properties", "Stream", "Event",
    "stream_guard", "current_stream", "tpu", "cuda",
]


def get_all_device_type() -> List[str]:
    kinds = []
    for d in jax.devices():
        if d.platform not in kinds:
            kinds.append(d.platform)
    return kinds


def get_available_device() -> List[str]:
    return get_all_devices()


def get_device_properties(device=None):
    """Device descriptor (ref ``paddle.device.cuda.get_device_properties``):
    returns the PJRT device object, which carries kind/id/memory stats."""
    if device is None:
        return get_default_device()
    if isinstance(device, int):
        return jax.devices()[device]
    from ..core.device import _parse, _platform_devices
    kind, idx = _parse(str(device))
    return _platform_devices(kind)[idx]


class Event:
    """Cross-stream sync point. ``record`` snapshots the tail of the work
    queued so far (the arrays produced since); ``synchronize`` blocks the
    host until that work is done."""

    def __init__(self, enable_timing: bool = False):
        self._marker = None
        self.enable_timing = enable_timing
        self._time = None

    def record(self, stream: "Stream" = None) -> None:
        import time
        dev = (stream.device if stream is not None else get_default_device())
        # A tiny device computation ordered after everything already queued
        # on this device; completing it proves the queue drained to here.
        self._marker = jax.device_put(0, dev)
        if self.enable_timing:
            self._time = time.perf_counter()

    def query(self) -> bool:
        if self._marker is None:
            return True
        return self._marker.is_ready()

    def synchronize(self) -> None:
        if self._marker is not None:
            self._marker.block_until_ready()


class Stream:
    """Execution-queue facade. XLA runs one ordered async queue per device;
    distinct Streams therefore share hardware but keep the paddle API
    (``wait_event``/``wait_stream``/``synchronize``) meaningful as
    synchronization scopes."""

    def __init__(self, device=None, priority: int = 2):
        if device is None:
            self.device = get_default_device()
        elif isinstance(device, jax.Device):
            self.device = device
        else:
            self.device = get_device_properties(device)
        self.priority = priority

    def wait_event(self, event: Event) -> None:
        event.synchronize()

    def wait_stream(self, stream: "Stream") -> None:
        stream.synchronize()

    def record_event(self, event: Optional[Event] = None) -> Event:
        event = event or Event()
        event.record(self)
        return event

    def synchronize(self) -> None:
        (jax.device_put(0, self.device) + 0).block_until_ready()


_current_stream: Optional[Stream] = None


def current_stream(device=None) -> Stream:
    global _current_stream
    if _current_stream is None or device is not None:
        return Stream(device)
    return _current_stream


@contextlib.contextmanager
def stream_guard(stream: Stream):
    """Scope under which ``current_stream()`` returns ``stream``."""
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    try:
        yield stream
    finally:
        _current_stream = prev


class _AcceleratorNamespace:
    """``paddle.device.cuda``-shaped namespace bound to the TPU backend —
    existing user code calling ``paddle.device.cuda.*`` keeps working."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count() -> int:
        return device_count("tpu")

    @staticmethod
    def synchronize(device=None) -> None:
        synchronize()

    @staticmethod
    def current_stream(device=None) -> Stream:
        return current_stream(device)

    @staticmethod
    def stream_guard(stream: Stream):
        return stream_guard(stream)

    @staticmethod
    def empty_cache() -> None:
        """PJRT pools device memory internally; XLA frees buffers on drop.
        Nothing to flush, kept for API parity."""

    @staticmethod
    def memory_stats(device=None) -> dict:
        dev = get_device_properties(device)
        try:
            return dict(dev.memory_stats() or {})
        except Exception:
            return {}

    @classmethod
    def memory_allocated(cls, device=None) -> int:
        return int(cls.memory_stats(device).get("bytes_in_use", 0))

    @classmethod
    def max_memory_allocated(cls, device=None) -> int:
        return int(cls.memory_stats(device).get("peak_bytes_in_use", 0))

    @classmethod
    def max_memory_reserved(cls, device=None) -> int:
        return int(cls.memory_stats(device).get("bytes_reservable_limit", 0))

    @classmethod
    def memory_reserved(cls, device=None) -> int:
        return int(cls.memory_stats(device).get("bytes_limit", 0))


tpu = _AcceleratorNamespace()
cuda = tpu  # accelerator alias: cuda-namespace calls land on the TPU backend


# -- compile-flag predicates + place shims (ref device/__init__.py) --------

def get_cudnn_version():
    """No CUDA in the TPU build (reference returns None when absent)."""
    return None


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    """XPU = the accelerator family slot; the TPU fills it here."""
    import jax
    return jax.default_backend() == "tpu"


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    """XLA is this build's tensor compiler (CINN's role)."""
    return True


def is_compiled_with_custom_device(device_type: str = None) -> bool:
    """PJRT is the custom-device plugin ABI; the TPU registers through
    it."""
    import jax
    try:
        return len(jax.devices()) > 0
    except Exception:
        return False


def get_all_custom_device_type():
    import jax
    try:
        return sorted({d.platform for d in jax.devices()})
    except Exception:
        return []


def get_available_custom_device():
    import jax
    try:
        return [str(d) for d in jax.devices()]
    except Exception:
        return []


class XPUPlace:
    """ref XPUPlace(dev_id) — accelerator placement token."""

    def __init__(self, dev_id: int = 0):
        self.dev_id = int(dev_id)

    def __repr__(self):
        return f"XPUPlace({self.dev_id})"


class IPUPlace:
    def __init__(self, *a, **k):
        raise NotImplementedError("IPU hardware is not part of this build")


def set_stream(stream=None):
    """Streams are XLA-managed; accepted for call-site parity."""
    return stream

"""Device management.

TPU-native equivalent of the reference's device/platform runtime
(``paddle/phi/backends/device_manager.h:133`` DeviceManager,
``python/paddle/device`` set_device/get_device): on JAX/PJRT devices are
enumerated by the runtime; there is no per-device context or stream zoo to
manage — XLA owns streams and memory. We expose paddle-style device strings
("tpu", "tpu:0", "cpu") mapped onto ``jax.devices()``.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import jax

__all__ = [
    "set_device", "get_device", "get_all_devices", "device_count",
    "is_compiled_with_tpu", "get_default_device", "synchronize",
]

_state = threading.local()

def _parse(device: str):
    device = device.lower().strip()
    if ":" in device:
        kind, _, idx = device.partition(":")
        return kind, int(idx)
    return device, 0


def _platform_devices(kind: str) -> List[jax.Device]:
    if kind in ("gpu", "xpu"):  # accelerator aliases all map to TPU here
        kind = "tpu"
    return [d for d in jax.devices() if d.platform == kind]


def get_all_devices() -> List[str]:
    out = []
    for d in jax.devices():
        out.append(f"{d.platform}:{d.id}")
    return out


def device_count(kind: str = "tpu") -> int:
    return len(_platform_devices(kind))


def is_compiled_with_tpu() -> bool:
    return device_count("tpu") > 0


def set_device(device: str) -> jax.Device:
    """paddle.set_device parity: select the default device for placement."""
    kind, idx = _parse(device)
    devs = _platform_devices(kind)
    if not devs:
        raise ValueError(f"No devices of kind {kind!r}; have {get_all_devices()}")
    if idx >= len(devs):
        raise ValueError(f"Device index {idx} out of range for {kind} "
                         f"({len(devs)} present)")
    _state.device = devs[idx]
    _state.name = f"{kind}:{idx}"
    jax.config.update("jax_default_device", devs[idx])
    return devs[idx]


def get_default_device() -> jax.Device:
    dev = getattr(_state, "device", None)
    if dev is None:
        dev = jax.devices()[0]
    return dev


def get_device() -> str:
    name = getattr(_state, "name", None)
    if name is None:
        d = jax.devices()[0]
        name = f"{d.platform}:{d.id}"
    return name


def synchronize() -> None:
    """Block until all dispatched work on the default device completes
    (ref: paddle.device.synchronize / cudaDeviceSynchronize)."""
    (jax.device_put(0, get_default_device()) + 0).block_until_ready()

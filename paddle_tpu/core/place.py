"""Place (device) taxonomy.

Reference analog: `phi::Place` hierarchy (`/root/reference/paddle/phi/common/place.h:48`).
On TPU there is ONE first-class accelerator place (TPUPlace) plus CPUPlace; streams
and contexts are implicit in XLA, so no DeviceContext pool is needed — `jax.Device`
plays that role.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base device identity."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.device_type, self._device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    def _to_jax_device(self):
        devs = [d for d in jax.devices() if _platform_matches(d.platform, self.device_type)]
        if not devs:
            # fall back to whatever the default backend is (e.g. CPU-only test env)
            devs = jax.devices()
        return devs[min(self._device_id, len(devs) - 1)]


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    device_type = "tpu"


# CUDA alias kept for API-compat with reference models that say "gpu"; resolves to TPU.
class CUDAPlace(TPUPlace):
    pass


def _platform_matches(platform: str, device_type: str) -> bool:
    return platform == device_type


_CURRENT_DEVICE = None


def set_device(device: str):
    """paddle.set_device('tpu') / ('tpu:0') / ('cpu')."""
    global _CURRENT_DEVICE
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu", "ipu": "tpu", "npu": "tpu"}.get(name, name)
    if name == "cpu":
        _CURRENT_DEVICE = CPUPlace()
    elif name == "tpu":
        _CURRENT_DEVICE = TPUPlace(idx)
    else:
        raise ValueError(f"Unsupported device {device!r}; use 'tpu[:i]' or 'cpu'")
    return _CURRENT_DEVICE


def get_device() -> str:
    p = _current_place()
    return p.device_type if p.device_type == "cpu" else f"{p.device_type}:{p.get_device_id()}"


def _current_place() -> Place:
    global _CURRENT_DEVICE
    if _CURRENT_DEVICE is None:
        _CURRENT_DEVICE = TPUPlace(0) if _accelerator_available() else CPUPlace()
    return _CURRENT_DEVICE


@functools.lru_cache(maxsize=1)
def _accelerator_available() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()


class CUDAPinnedPlace(CPUPlace):
    """Pinned host memory (parity shim: PJRT manages host staging buffers)."""


class NPUPlace(TPUPlace):
    """NPU alias kept for API compat; resolves to the accelerator place."""

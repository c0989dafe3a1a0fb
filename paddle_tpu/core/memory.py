"""Device memory stats + allocator flags.

Reference analog: AllocatorFacade stats surface
(/root/reference/paddle/fluid/memory/allocation/allocator_facade.h:43,
stat_allocator + paddle.device.cuda.{max_}memory_allocated) and the
FLAGS_fraction_of_gpu_memory_to_use / FLAGS_allocator_strategy gflags
(/root/reference/paddle/fluid/platform/flags.cc).

TPU-native: the allocator IS XLA's BFC; this module exposes its per-device
stats (PJRT memory_stats) and the pre-init sizing knobs
(XLA_PYTHON_CLIENT_MEM_FRACTION / _PREALLOCATE) through the paddle flag names.
"""
from __future__ import annotations

import os

import jax

__all__ = [
    "memory_stats", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "set_memory_fraction", "set_preallocate",
    "empty_cache", "device_memory_limit",
]


def _dev(device=None):
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    return device


def memory_stats(device=None) -> dict:
    """Raw per-device allocator stats (PJRT): bytes_in_use, peak_bytes_in_use,
    bytes_limit, num_allocs, ... Empty dict when the backend doesn't report
    (the CPU backend does not)."""
    stats = _dev(device).memory_stats()
    return dict(stats) if stats else {}


def memory_allocated(device=None) -> int:
    """Live bytes in the device allocator (reference:
    paddle.device.cuda.memory_allocated)."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the BFC pool (>= allocated)."""
    s = memory_stats(device)
    return int(s.get("pool_bytes", s.get("bytes_reserved", s.get("bytes_in_use", 0))))


def device_memory_limit(device=None) -> int:
    """The allocator's byte limit on this chip (0 if unknown)."""
    return int(memory_stats(device).get("bytes_limit", 0))


def set_memory_fraction(fraction: float) -> None:
    """FLAGS_fraction_of_gpu_memory_to_use analog: cap the XLA client pool.

    Must run before the backend initializes (same constraint as the
    reference's flag, which is read at allocator construction)."""
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"fraction must be in (0, 1]; got {fraction}")
    os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(fraction)


def set_preallocate(enable: bool) -> None:
    """FLAGS_allocator_strategy analog: preallocate pool vs grow on demand
    (auto_growth). XLA: XLA_PYTHON_CLIENT_PREALLOCATE."""
    os.environ["XLA_PYTHON_CLIENT_PREALLOCATE"] = "true" if enable else "false"


def empty_cache() -> None:
    """Best-effort release of cached compilations + garbage arrays
    (reference: paddle.device.cuda.empty_cache)."""
    jax.clear_caches()
    import gc

    gc.collect()

"""Shared kernel-dispatch helpers: one backend probe, one index scope."""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=1)
def on_tpu_backend() -> bool:
    """One predicate — every pallas gate must agree on what a TPU is."""
    return jax.default_backend() == "tpu"


def i32_index_scope():
    """Context for every pallas_call: the package enables x64 globally for
    Paddle dtype parity (paddle_tpu/__init__.py), which makes BlockSpec
    index-map constants i64 and fails Mosaic legalization ("func.return
    (i32, i64)"). Scoping x64 off keeps kernel index math i32."""
    return jax.enable_x64(False)


def vmem_nbytes(dims, itemsize: int) -> int:
    """Bytes a buffer of these dims occupies in VMEM: Mosaic tiles the
    last two dims ``(sublanes, 128 lanes)`` — 8 sublanes of 4-byte, 16 of
    2-byte, 32 of 1-byte elements — and allocates whole tiles, so a
    ``(n, 1, 128)`` fp32 buffer costs 8x its logical size. One model for
    the kernels' own VMEM gates and for kernelcheck's budget."""
    dims = [int(d) for d in dims]
    if dims:
        dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2:
        sub = max(1, 32 // itemsize)
        dims[-2] = -(-dims[-2] // sub) * sub
    n = itemsize
    for d in dims:
        n *= d
    return n

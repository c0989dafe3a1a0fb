"""Fused Adam/AdamW update as a bespoke Pallas TPU kernel.

Reference analog: paddle/phi/kernels/gpu/adam_kernel.cu (one fused CUDA
kernel reading p/g/m/v once and writing p/m/v once) and the fused
multi-tensor apply in operators/optimizers/. On TPU, XLA usually fuses the
update chain well, but it materializes m/bc1 and v/bc2 intermediates and
may split the chain at the rsqrt; this kernel pins the whole update to ONE
pass over HBM per buffer — the optimizer step is pure memory bandwidth, so
one read + one write per tensor is the floor. Pairs with the
fuse_all_reduce pass (static/executor.py): flat dtype-homogeneous buckets
give the kernel long rows to stream.

The math matches optimizers.Adam._apply_dense bit-for-bit in f32:
  m' = b1*m + (1-b1)*g ;  v' = b2*v + (1-b2)*g^2
  p' = p - lr * (m'/bc1) / (sqrt(v'/bc2) + eps)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _common

#: kernelcheck certificate for this module's pallas_call (lint PT011)
KERNELCHECK_CERTS = ("fused_adam",)

_LANE = 128
_ROWS_PER_BLOCK = 8  # (8, 128) f32 tile — the VPU-native block


def _adam_kernel(beta1, beta2, eps, sc_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref):
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    g = g_ref[...]
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * (g * g)
    upd = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    po_ref[...] = p_ref[...] - upd
    mo_ref[...] = m
    vo_ref[...] = v


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "eps",
                                             "interpret"))
def fused_adam_update(p, g, m, v, lr, bc1, bc2, *, beta1, beta2, eps,
                      interpret=False):
    """One-pass Adam update. p/g/m/v: same shape; lr/bc1/bc2: traced f32
    scalars; beta/eps static. Returns (new_p, new_m, new_v) in f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = p.shape
    n = p.size
    width = _LANE * 8  # 1024-lane rows: long sequential streams
    pad = (-n) % (width * _ROWS_PER_BLOCK)

    def prep(x):
        flat = x.reshape(-1).astype(jnp.float32)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(-1, width)

    P, G, M, V = prep(p), prep(g), prep(m), prep(v)
    rows = P.shape[0]
    grid = (rows // _ROWS_PER_BLOCK,)
    scalars = jnp.stack([lr, bc1, bc2]).astype(jnp.float32)

    block = pl.BlockSpec((_ROWS_PER_BLOCK, width), lambda i, _: (i, 0))
    out_shape = jax.ShapeDtypeStruct(P.shape, jnp.float32)
    with _common.i32_index_scope():
        new_p, new_m, new_v = pl.pallas_call(
            functools.partial(_adam_kernel, beta1, beta2, eps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[block] * 4, out_specs=[block] * 3,
            ),
            out_shape=[out_shape] * 3,
            interpret=interpret,
        )(scalars, P, G, M, V)

    def unprep(x):
        flat = x.reshape(-1)
        if pad:
            flat = flat[:n]
        return flat.reshape(shape)

    return unprep(new_p), unprep(new_m), unprep(new_v)


# gate: worth launching only for big buffers on a real TPU (small params are
# free under XLA fusion; pallas adds per-launch overhead)
_MIN_FUSED_SIZE = 1 << 16


def maybe_fused_adam(p, g, m, v, lr, bc1, bc2, *, beta1, beta2, eps):
    """Return (new_p, new_m, new_v) via the Pallas kernel, or None when the
    plain XLA path should run (CPU, small tensors, flag off, non-f32)."""
    from ..utils.flags import flag

    from ._common import on_tpu_backend

    if not flag("FLAGS_use_fused_optimizer", True):
        return None
    # TPU backends only: pltpu lowering fails elsewhere, and jit does not
    # cache the failure — a loose gate would re-trace and re-raise per step
    if not on_tpu_backend() or p.size < _MIN_FUSED_SIZE:
        return None
    if m.dtype != jnp.float32 or p.dtype != jnp.float32:
        return None
    if p.size % (_LANE * 8 * _ROWS_PER_BLOCK):
        # padding would copy all four inputs — the exact HBM traffic the
        # kernel exists to avoid; non-tileable sizes take the XLA path
        return None
    return fused_adam_update(p, g, m, v,
                             jnp.asarray(lr, jnp.float32),
                             jnp.asarray(bc1, jnp.float32),
                             jnp.asarray(bc2, jnp.float32),
                             beta1=float(beta1), beta2=float(beta2),
                             eps=float(eps))

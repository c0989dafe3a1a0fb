"""Decode attention of ONE new token a row over a paged pool, as a Pallas
kernel: the staging pipeline, written once, and its two instances.

One grid step a row, rows in order. ``ctx_lens`` and the page table are
scalar-prefetched; the pool stays in HBM and is copied by hand. Only a
row's LIVE pages move: the loop runs over the chunks of ``chunk_tokens``
positions that hold one the new token sees
(:func:`.ragged_paged_attention._live_span`, the same arithmetic the host
counts by), through two alternating staging buffers. A chunk's page
copies are started before the chunk before it is awaited, a buffer has one
semaphore and ONE wait a chunk, and a row's last chunk starts the NEXT
row's first, so no row waits for a cold copy. Every chunk is scored for
all heads at once and folded into a running (max, sum, accumulator), all
float32; the probabilities are rounded to the pool's dtype only as the
operand of the value product, and the division by the sum comes last.

What a call is given decides its instance at trace time:

- **no values pool** (:func:`.latent_paged_attention.mla_decode_kernel_call`,
  absorbed latent attention): every head scores against the same pool row
  and the value is the row's first ``out_width`` columns.
- **a values pool** (:func:`gqa_decode_attention`, grouped KV heads over a
  lane-dense pool ``[pages, page_size, kv_heads * head_dim]``): a chunk's
  K pages and V pages land in the two halves of one staging buffer under
  the buffer's one semaphore. The row's queries arrive BLOCK-DIAGONAL,
  ``[heads, kv_heads * head_dim]`` with head ``kv * g + j``'s values in
  columns ``kv * d .. kv * d + d - 1`` and exact zeros elsewhere, so one
  product over the whole row gives each head its own scores exactly, and
  the accumulator holds each head's output in its own ``d`` columns; the
  caller picks them off. No slice narrower than a 128-lane row appears in
  the kernel, whatever the head size (``kv_heads`` times the needed FLOPs,
  beside copies that bound the kernel anyway).
- **a loop** (``gqa_decode_attention(loop=, loops=)``, a model that runs
  its layers several times a token): the pools' rows hold every loop's
  keys side by side and the loop, a third scalar operand, picks the
  columns each page copy takes (``gqa_decode_attention_loop``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ragged_paged_attention as _rp
from ._common import i32_index_scope, vmem_nbytes

__all__ = ["decode_kernel_call", "gqa_decode_attention",
           "gqa_kernel_eligible", "gqa_chunk_pages", "chunk_pages_for",
           "LANES"]

#: kernelcheck certificates this module's Pallas kernel is registered
#: under (analysis/kernelcheck.py REGISTRY; lint rule PT011's contract)
KERNELCHECK_CERTS = ("mla_decode", "gqa_decode", "gqa_decode_window",
                     "gqa_decode_loop")

#: a pool row is a whole number of 128-lane rows for the compiled kernel
LANES = 128

#: tokens staged per DMA chunk of the grouped-head instance (a whole
#: number of pages). On the chip at granite-4.0-h-micro's shape (64 rows,
#: 32 heads over 8 of 64, bf16, 16-token pages; my chip run, PR 34), ms a
#: call and live pages over staged: rows of 257-1,536 tokens 128: 0.235,
#: 91%; 256: 0.184, 83%; 512: 0.175, 71%; 768: 0.178, 68%; every row
#: 153 tokens 0.110 / 0.078 / 0.116 / 0.169; every row full 0.515 /
#: 0.376 / 0.315 / 0.287. 256 is within 5% of the best on the serving
#: mix (0.2% of a decode step) and does not pay for short rows
_GQA_CHUNK_TOKENS = 256

_VMEM_GATE_BYTES = int((16 << 20) * 0.9)


def chunk_pages_for(page_size: int, pages_per_seq: int,
                    chunk_tokens: int) -> int:
    """Pages per DMA chunk: about ``chunk_tokens`` tokens, a divisor of
    the page table's width."""
    c = max(1, min(pages_per_seq, chunk_tokens // page_size))
    while pages_per_seq % c:
        c -= 1
    return c


def gqa_chunk_pages(page_size: int, pages_per_seq: int) -> int:
    """Pages a chunk of the grouped-head kernel at these shapes."""
    return chunk_pages_for(page_size, pages_per_seq, _GQA_CHUNK_TOKENS)


def _decode_kernel(page_size, pages_per_seq, chunk_pages, out_width, scale,
                   n_pools, window, looped, ctx_ref, tab_ref, *refs):
    """One row: its live pages through two staging buffers, chunk c + 1's
    copies started before chunk c is awaited, every chunk scored for all
    heads at once and folded into a running (max, sum, accumulator). The
    rows run in order and the pipeline runs through them: a row's last
    chunk starts the NEXT row's first (``slot_ref`` carries the buffer a
    row begins in). ``refs``: the ``n_pools`` pools in HBM (keys, then
    values if a second pool holds them), the output block, the staging
    buffer ``[2, n_pools * chunk_kv, width]``, its two semaphores and
    ``slot_ref``. ``window`` (None: every position): the new token sees
    itself and the ``window - 1`` positions before it; the loop then
    starts at the first chunk that holds one of them
    (:func:`.ragged_paged_attention._live_start`) and the positions behind
    the window are masked exactly, so a page the cache has freed there
    (its table column reads the null page) is neither copied nor read.
    ``looped``: a third scalar operand ``loop_ref`` comes before ``q_ref``,
    and a page's copy takes columns ``[loop * width, (loop + 1) * width)``
    of a pool row ``n * width`` wide (a model that runs its layers ``n``
    times a token keeps each loop's keys side by side in one page)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    loop_ref, refs = (refs[0], refs[1:]) if looped else (None, refs)
    q_ref, refs = refs[0], refs[1:]
    pools, (o_ref, kv_s, sems, slot_ref) = refs[:n_pools], refs[n_pools:]
    bi = pl.program_id(0)
    rows = pl.num_programs(0)
    chunk_kv = chunk_pages * page_size
    total_kv = pages_per_seq * page_size
    # the new token is in the pool already: positions 0 .. ctx are seen.
    # The clamp keeps a dead slot's garbage length inside the table.
    length = jax.lax.clamp(np.int32(1), ctx_ref[bi] + np.int32(1),
                           np.int32(total_kv))
    n_chunks, _ = _rp._live_span(length, chunk_kv, page_size, total_kv,
                                 ops=_rp._LAX)

    def _first(row):
        """The first chunk a row's loop stages, and its first position
        seen: (0, 0) without a window."""
        if window is None:
            return 0, 0
        return _rp._live_start(ctx_ref[row] + np.int32(1), chunk_kv, window,
                               total_kv, ops=_rp._LAX)

    c0, lo = _first(bi)

    def _source(pool_hbm, page):
        if loop_ref is None:
            return pool_hbm.at[page]
        width = kv_s.shape[-1]
        return pool_hbm.at[page, :, pl.ds(loop_ref[0] * width, width)]

    def _start(row, c, slot):
        # a chunk's page copies all signal the buffer's one semaphore
        for j in range(chunk_pages):
            page = tab_ref[row, c * chunk_pages + j]
            for p, pool_hbm in enumerate(pools):
                pltpu.make_async_copy(
                    _source(pool_hbm, page),
                    kv_s.at[slot, pl.ds(p * chunk_kv + j * page_size,
                                        page_size)],
                    sems.at[slot]).start()

    def _wait(slot):
        # ONE wait a chunk, for as many bytes as the whole buffer holds (a
        # wait a page cost a tenth of the kernel's time on the chip)
        pltpu.make_async_copy(kv_s.at[slot], kv_s.at[slot],
                              sems.at[slot]).wait()

    @pl.when(bi == 0)
    def _():
        slot_ref[0] = np.int32(0)
        _start(bi, c0, 0)

    slot0 = slot_ref[0]
    q = q_ref[0]                                   # (heads, width)
    heads = q.shape[0]

    def body(c, carry):
        m, l, acc = carry
        slot = (slot0 + c) % 2 if window is None else (slot0 + c - c0) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            _start(bi, c + 1, 1 - slot)

        @pl.when((c + 1 == n_chunks) & (bi + 1 < rows))
        def _():
            nxt = bi + 1
            _start(nxt, _first(nxt)[0], 1 - slot)

        _wait(slot)
        if n_pools == 1:
            k = kv_s[slot]                         # (chunk_kv, width)
            v = k[:, :out_width]
        else:
            k = kv_s[slot, :chunk_kv]
            v = kv_s[slot, chunk_kv:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + c * chunk_kv
        seen = pos < length if window is None \
            else (pos < length) & (pos >= lo)
        s = jnp.where(seen, s, np.float32(-1e30))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((heads, 1), np.float32(-1e30), jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    acc0 = jnp.zeros((heads, out_width), jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        np.int32(0) if window is None else c0, n_chunks, body,
        (m0, l0, acc0))
    slot_ref[0] = (slot0 + n_chunks) % 2 if window is None \
        else (slot0 + n_chunks - c0) % 2
    # the new token's own position is seen by every row, so l > 0
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def decode_kernel_call(q, k_pool, v_pool, page_table, ctx_lens, *,
                       out_width: int, scale: float, chunk_tokens: int,
                       name: str, interpret: bool = False,
                       window: int | None = None, loop=None):
    """The decode kernel. q [batch, heads, width]; k_pool [num_pages,
    page_size, width]; ``v_pool`` of the same shape, or None where the
    value is the key row's first ``out_width`` columns (then ``out_width <=
    width``; with a values pool ``out_width == width``). Returns [batch,
    heads, out_width] in q's dtype. ``name`` is the kernel's name in a
    trace. ``window``: the new token sees itself and the ``window - 1``
    positions before it (None: every position, and the kernel that traces
    is the one without the argument, operand for operand). ``loop`` (an
    int32 scalar, traced or not; None: no such operand, and the kernel
    that traces is the one without it, operand for operand): the pools'
    rows hold several loops' keys side by side, ``width`` columns a loop,
    and the kernel copies loop ``loop``'s columns of each live page in
    place (a scalar operand of the copy's source slice, no copy of a
    pool)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, width = q.shape
    ps, pps = k_pool.shape[1], page_table.shape[1]
    chunk = chunk_pages_for(ps, pps, chunk_tokens)
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    looped = loop is not None
    scalars = (ctx_lens.astype(jnp.int32), page_table.astype(jnp.int32))
    if looped:
        scalars += (jnp.reshape(loop, (1,)).astype(jnp.int32),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda bi, *_: (bi, 0, 0)),
            # the pools: manual DMA
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
        ],
        out_specs=pl.BlockSpec((1, heads, out_width),
                               lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, len(pools) * chunk * ps, width), k_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),     # the buffer a row begins in
        ])
    kernel = functools.partial(_decode_kernel, ps, pps, chunk, out_width,
                               float(scale), len(pools),
                               None if window is None else int(window),
                               looped)
    with i32_index_scope():  # kernel index math assumes int32 defaults
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, heads, out_width), q.dtype),
            # in order: a row starts the copies of the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(*scalars, q, *pools)


# ------------------------------------------------- grouped KV heads (GQA)
def _gqa_working_set(heads: int, width: int, chunk_kv: int,
                     itemsize: int) -> int:
    """Per-grid-step VMEM at the padded footprint: the two staging
    buffers of a chunk's keys and values, the q and output blocks
    (double-buffered), the logits and probabilities of one chunk and the
    float32 accumulator."""
    ws = vmem_nbytes((2, 2 * chunk_kv, width), itemsize)
    ws += 4 * vmem_nbytes((heads, width), itemsize)
    ws += 2 * vmem_nbytes((heads, chunk_kv), 4)
    ws += vmem_nbytes((heads, width), 4)
    return ws


def gqa_kernel_eligible(heads: int, kv_heads: int, head_dim: int,
                        page_size: int, pages_per_seq: int,
                        num_query_tokens: int = 1, *, itemsize: int = 2,
                        flat_pool: bool = True, on_tpu: bool = True,
                        flags_on: bool = True,
                        interpret: bool = False) -> tuple[bool, str]:
    """The one gate of the grouped-head decode kernel: ``(eligible,
    reason)``, the reason naming the first gate that blocks it."""
    if not flags_on:
        return False, "FLAGS_use_pallas_kernels is off"
    if not on_tpu and not interpret:
        return False, ("CPU backend: Pallas TPU kernels unavailable (set "
                       "FLAGS_ragged_interpret for the interpreter)")
    if num_query_tokens != 1:
        return False, (f"{num_query_tokens} new tokens a row: the grouped-"
                       "head kernel is a decode kernel (one token a row): "
                       "composite path")
    if not flat_pool:
        return False, ("a pool with a heads axis: the grouped-head kernel "
                       "copies pages of a lane-dense pool [pages, "
                       "page_size, kv_heads * head_dim]: composite path")
    if heads % kv_heads:
        return False, (f"{heads} query heads do not group over {kv_heads} "
                       "KV heads")
    width = kv_heads * head_dim
    if width % LANES and not interpret:
        return False, (f"pool row {width} is not whole {LANES}-lane rows: "
                       "composite path")
    chunk_kv = gqa_chunk_pages(page_size, pages_per_seq) * page_size
    ws = _gqa_working_set(heads, width, chunk_kv, itemsize)
    if ws > _VMEM_GATE_BYTES:
        return False, (f"VMEM working set {ws} B exceeds the "
                       f"{_VMEM_GATE_BYTES} B gate: composite path")
    return True, ""


def gqa_decode_attention(q, k_pool, v_pool, page_table, ctx_lens,
                         scale: float, *, interpret: bool = False,
                         window: int | None = None, loop=None,
                         loops: int = 1):
    """Attention of ONE new token a row (already written to the pools) of
    ``g`` query heads to each KV head. q ``[batch, kv_heads * g, 1, d]``
    (query head ``kv * g + j`` attends KV head ``kv``); pools ``[pages,
    page_size, kv_heads * d]``. Returns ``[batch, heads, 1, d]`` in q's
    dtype. The queries are laid out block-diagonally before the kernel and
    each head's own ``d`` columns of the kernel's output are picked off
    behind it: two small fusions. ``window``: a layer whose query sees
    itself and the ``window - 1`` tokens before it; the kernel stages the
    chunks of ``[max(0, ctx + 1 - window), ctx]`` only. ``loop`` (an int32
    scalar, traced or not) of ``loops``: pools ``[pages, page_size, loops *
    kv_heads * d]`` whose rows hold each loop's keys side by side, of which
    the kernel reads loop ``loop``'s columns in place (the instance
    ``gqa_decode_attention_loop``)."""
    b, heads, _, d = q.shape
    width = k_pool.shape[-1] // loops
    kv_heads = width // d
    g = heads // kv_heads
    # own[h, kv]: the KV head that query head h attends
    own = (jnp.arange(heads)[:, None] // g
           == jnp.arange(kv_heads)[None, :])[None, :, :, None]
    q_bd = jnp.where(own, q.astype(k_pool.dtype), 0).reshape(
        b, heads, width)
    o = decode_kernel_call(
        q_bd, k_pool, v_pool, page_table, ctx_lens, out_width=width,
        scale=scale, chunk_tokens=_GQA_CHUNK_TOKENS,
        name="gqa_decode_attention_loop" if loop is not None
        else "gqa_decode_attention" if window is None
        else "gqa_decode_attention_window", interpret=interpret,
        window=window, loop=loop)
    o = jnp.sum(jnp.where(own, o.reshape(b, heads, kv_heads, d), 0), axis=2)
    return o[:, :, None, :].astype(q.dtype)

"""Attention over a LATENT paged pool (multi-head latent attention, MLA:
DeepSeek-V2, arXiv:2405.04434 section 2.1).

The pool keeps one row a token a layer and no heads axis:
``[num_pages, page_size, rank + rope_dim]``, the compressed key-value
latent ``c_kv`` (after its norm) in the first ``rank`` columns and the one
rotary key ``k_rope`` (after RoPE) that all heads share in the rest. Page 0
is the null page, as in :mod:`.paged_attention`.

Two forms of the same attention:

- :func:`latent_decode_attention`, the ABSORBED form: the queries arrive
  already multiplied into the latent space (``q_lat = q_nope W_kvb[K]``),
  every head scores against the same row, and the value is the row's first
  ``rank`` columns; the caller expands ``o_lat`` with ``W_kvb[V]``. What a
  decode step runs. On a TPU (or under ``FLAGS_ragged_interpret``) it is
  the Pallas kernel :func:`mla_decode_kernel_call`: one grid step a row,
  the row's LIVE pages brought to VMEM once for all heads through the
  page table, in chunks through two alternating buffers (the pipeline
  runs on from row to row), folded by an online softmax. Elsewhere the composite: gather every page of the
  table, then a ragged-masked softmax.
- :func:`latent_prefill_attention`, the EXPANDED form, composite XLA: the
  rows are expanded to per-head keys and values first. What a prefill
  runs, over the prompt's own rows and a cached prefix's alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._common import i32_index_scope, vmem_nbytes

__all__ = ["latent_write", "latent_gather", "latent_decode_attention",
           "latent_prefill_attention", "mla_decode_kernel_call",
           "mla_kernel_eligible", "LANES", "padded_width"]

#: kernelcheck certificates this module's Pallas kernel is registered
#: under (analysis/kernelcheck.py REGISTRY; lint rule PT011's contract)
KERNELCHECK_CERTS = ("mla_decode",)

#: a pool row is a whole number of 128-lane rows for the compiled kernel
LANES = 128

#: tokens staged per DMA chunk (a whole number of pages). On the chip at
#: the serving shape (256 rows of 450-2,500 tokens, 16-token pages): 128
#: tokens 2.28 ms a call, 256 1.47, 512 1.10, 640 1.10, 1,280 1.08, the
#: whole table 1.30 (my chip runs, PR 29): past 512 a chunk's fixed cost
#: is spread thin and what is left is the issue of one copy a page
_CHUNK_TOKENS = 512

_VMEM_GATE_BYTES = int((16 << 20) * 0.9)


def padded_width(width: int) -> int:
    """The width of a pool row that the compiled kernel takes: ``width``
    rounded up to whole 128-lane rows (576 -> 640; the pad is zeros, read
    and never counted as work)."""
    return -(-width // LANES) * LANES


def latent_write(pool, rows, page_ids, offsets):
    """Write ``rows`` [batch, tokens, width] at ``page_ids`` / ``offsets``
    [batch, tokens] (dead writes routed to the null page by the caller)."""
    return pool.at[page_ids, offsets].set(rows.astype(pool.dtype))


def latent_gather(pool, page_table):
    """Each row's pages as one sequence: [batch, pages * page_size, width]."""
    b, n_pages = page_table.shape
    seq = pool[page_table]
    return seq.reshape(b, n_pages * pool.shape[1], pool.shape[2])


def _ragged_softmax(scores, ctx_lens, num_query_tokens: int):
    """Softmax over the last axis of float32 ``scores`` [b, h, t, S] under
    the ragged causal-prefix mask (query t of row b sees positions
    ``j <= ctx_lens[b] + t``); masked positions get exact zeros."""
    from .paged_attention import ragged_mask

    mask = ragged_mask(ctx_lens, scores.shape[-1], num_query_tokens)
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(scores - m), 0.0)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _chunk_pages(page_size: int, pages_per_seq: int) -> int:
    """Pages per DMA chunk: about ``_CHUNK_TOKENS`` tokens, a divisor of
    the page table's width."""
    c = max(1, min(pages_per_seq, _CHUNK_TOKENS // page_size))
    while pages_per_seq % c:
        c -= 1
    return c


def _vmem_working_set(heads: int, width: int, rank: int, chunk_kv: int,
                      itemsize: int) -> int:
    """Per-grid-step VMEM at the padded footprint: the two staging
    buffers, the q and output blocks (double-buffered), the logits and
    probabilities of one chunk and the float32 accumulator."""
    ws = vmem_nbytes((2, chunk_kv, width), itemsize)
    ws += 2 * vmem_nbytes((heads, width), itemsize)
    ws += 2 * vmem_nbytes((heads, rank), itemsize)
    ws += 2 * vmem_nbytes((heads, chunk_kv), 4)
    ws += vmem_nbytes((heads, rank), 4)
    return ws


def mla_kernel_eligible(heads: int, width: int, rank: int, page_size: int,
                        pages_per_seq: int, *, itemsize: int = 2,
                        on_tpu: bool = True, flags_on: bool = True,
                        interpret: bool = False) -> tuple[bool, str]:
    """The one gate of the absorbed-decode kernel: ``(eligible, reason)``,
    the reason naming the first gate that blocks it."""
    if not flags_on:
        return False, "FLAGS_use_pallas_kernels is off"
    if not on_tpu and not interpret:
        return False, ("CPU backend: Pallas TPU kernels unavailable (set "
                       "FLAGS_ragged_interpret for the interpreter)")
    if (width % LANES or rank % LANES) and not interpret:
        return False, (f"pool row {width} / rank {rank} is not whole "
                       f"{LANES}-lane rows: composite path")
    chunk_kv = _chunk_pages(page_size, pages_per_seq) * page_size
    ws = _vmem_working_set(heads, width, rank, chunk_kv, itemsize)
    if ws > _VMEM_GATE_BYTES:
        return False, (f"VMEM working set {ws} B exceeds the "
                       f"{_VMEM_GATE_BYTES} B gate: composite path")
    return True, ""


def _mla_decode_kernel(page_size, pages_per_seq, chunk_pages, rank, scale,
                       ctx_ref, tab_ref, q_ref, pool_hbm, o_ref, kv_s,
                       sems, slot_ref):
    """One row: its live pages through two staging buffers, chunk c + 1's
    copies started before chunk c is awaited, every chunk scored for all
    heads at once and folded into a running (max, sum, accumulator). The
    rows run in order and the pipeline runs through them: a row's last
    chunk starts the NEXT row's first, so no row waits for a cold copy
    (``slot_ref`` carries the buffer a row begins in)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bi = pl.program_id(0)
    rows = pl.num_programs(0)
    chunk_kv = chunk_pages * page_size
    # the new token is in the pool already: positions 0 .. ctx are seen.
    # The clamp keeps a dead slot's garbage length inside the table.
    length = jnp.minimum(ctx_ref[bi] + 1,
                         np.int32(pages_per_seq * page_size))
    n_chunks = (length + np.int32(chunk_kv - 1)) // np.int32(chunk_kv)

    def _start(row, c, slot):
        # a chunk's page copies all signal the buffer's one semaphore
        for j in range(chunk_pages):
            pltpu.make_async_copy(
                pool_hbm.at[tab_ref[row, c * chunk_pages + j]],
                kv_s.at[slot, pl.ds(j * page_size, page_size)],
                sems.at[slot]).start()

    def _wait(slot):
        # ONE wait a chunk, for as many bytes as the whole buffer holds (a
        # wait a page cost a tenth of the kernel's time on the chip)
        pltpu.make_async_copy(kv_s.at[slot], kv_s.at[slot],
                              sems.at[slot]).wait()

    @pl.when(bi == 0)
    def _():
        slot_ref[0] = np.int32(0)
        _start(bi, 0, 0)

    slot0 = slot_ref[0]
    q = q_ref[0]                                   # (heads, width)
    heads = q.shape[0]

    def body(c, carry):
        m, l, acc = carry
        slot = (slot0 + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            _start(bi, c + 1, 1 - slot)

        @pl.when((c + 1 == n_chunks) & (bi + 1 < rows))
        def _():
            _start(bi + 1, 0, 1 - slot)

        _wait(slot)
        kv = kv_s[slot]                            # (chunk_kv, width)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + c * chunk_kv
        s = jnp.where(pos < length, s, np.float32(-1e30))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((heads, 1), np.float32(-1e30), jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    acc0 = jnp.zeros((heads, rank), jnp.float32)
    _, l, acc = jax.lax.fori_loop(np.int32(0), n_chunks, body,
                                  (m0, l0, acc0))
    slot_ref[0] = (slot0 + n_chunks) % 2
    # position 0 is seen by every row, so l > 0
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def mla_decode_kernel_call(q, pool, page_table, ctx_lens, *, rank: int,
                           scale: float, interpret: bool = False):
    """The absorbed-decode kernel. q [batch, heads, width] (``q_lat`` and
    ``q_rope`` side by side, zero-padded to the pool row's width); pool
    [num_pages, page_size, width]; returns ``o_lat`` [batch, heads, rank]
    in q's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, width = q.shape
    ps, pps = pool.shape[1], page_table.shape[1]
    chunk = _chunk_pages(ps, pps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda bi, ctx, tab: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),    # the pool: manual DMA
        ],
        out_specs=pl.BlockSpec((1, heads, rank),
                               lambda bi, ctx, tab: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk * ps, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),     # the buffer a row begins in
        ])
    kernel = functools.partial(_mla_decode_kernel, ps, pps, chunk, rank,
                               float(scale))
    with i32_index_scope():  # kernel index math assumes int32 defaults
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, heads, rank), q.dtype),
            # in order: a row starts the copies of the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="mla_decode_attention",
        )(ctx_lens.astype(jnp.int32), page_table.astype(jnp.int32), q,
          pool)


def _use_kernel(heads: int, rank: int, pool, page_table) -> tuple:
    """``(eligible, interpret)`` for this call's shapes."""
    from ..utils.flags import flag
    from ._common import on_tpu_backend

    interp = bool(flag("FLAGS_ragged_interpret", False))
    ok, _ = mla_kernel_eligible(
        heads, pool.shape[-1], rank, pool.shape[1], page_table.shape[1],
        itemsize=pool.dtype.itemsize, on_tpu=on_tpu_backend(),
        flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
        interpret=interp)
    return ok, interp


def latent_decode_attention(q_lat, q_rope, pool, page_table, ctx_lens,
                            scale: float):
    """Absorbed attention of ONE new token a row (already written to the
    pool). q_lat [batch, heads, rank], q_rope [batch, heads, rope_dim];
    returns ``o_lat`` [batch, heads, rank] in q_lat's dtype. A kernel the
    gate called eligible that fails to lower RAISES: nothing here turns a
    kernel failure into a composite result."""
    rank = q_lat.shape[-1]
    width = pool.shape[-1]
    use_kernel, interpret = _use_kernel(q_lat.shape[1], rank, pool,
                                        page_table)
    if use_kernel:
        q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(pool.dtype)
        if width > q.shape[-1]:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))
        return mla_decode_kernel_call(
            q, pool, page_table, ctx_lens, rank=rank, scale=scale,
            interpret=interpret).astype(q_lat.dtype)
    seq = latent_gather(pool, page_table)              # [b, S, width]
    c_kv = seq[..., :rank]
    k_rope = seq[..., rank:rank + q_rope.shape[-1]]
    scores = (jnp.einsum("bhr,bsr->bhs", q_lat, c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhd,bsd->bhs", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    w = _ragged_softmax(scores[:, :, None, :], ctx_lens, 1)[:, :, 0]
    return jnp.einsum("bhs,bsr->bhr", w.astype(c_kv.dtype), c_kv,
                      preferred_element_type=jnp.float32).astype(q_lat.dtype)


def latent_prefill_attention(q_nope, q_rope, pool, page_table, ctx_lens,
                             w_kvb, scale: float, rank: int, kv_limit: int):
    """Expanded attention of ``t`` new tokens a row (already written to the
    pool) at positions ``ctx_lens .. ctx_lens + t - 1``. q_nope [batch, t,
    heads, nope_dim], q_rope [batch, t, heads, rope_dim]; w_kvb [rank,
    heads, nope_dim + v_dim]. ``kv_limit`` is a static bound on
    ``ctx_lens + t``: only the pages that can hold those positions are
    read. Returns [batch, t, heads, v_dim] in q_nope's dtype."""
    page_size = pool.shape[1]
    n_pages = min(page_table.shape[1], -(-int(kv_limit) // page_size))
    seq = latent_gather(pool, page_table[:, :n_pages])  # [b, S, width]
    dn = q_nope.shape[-1]
    kv = jnp.einsum("bsr,rhd->bshd", seq[..., :rank], w_kvb)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_rope = seq[..., rank:rank + q_rope.shape[-1]]
    scores = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    w = _ragged_softmax(scores, ctx_lens, q_nope.shape[1])
    return jnp.einsum("bhts,bshd->bthd", w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q_nope.dtype)

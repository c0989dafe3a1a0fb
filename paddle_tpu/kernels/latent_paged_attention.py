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
  the Pallas kernel :func:`mla_decode_kernel_call`, an instance of the
  decode pipeline of :mod:`.paged_decode` with no values pool: one grid
  step a row, the row's LIVE pages brought to VMEM once for all heads
  through the page table, in chunks through two alternating buffers (the
  pipeline runs on from row to row), folded by an online softmax.
  Elsewhere the composite: gather every page of the table, then a
  ragged-masked softmax.
- :func:`latent_prefill_attention`, the EXPANDED form, composite XLA: the
  rows are expanded to per-head keys and values first. What a prefill
  runs, over the prompt's own rows and a cached prefix's alike.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import paged_decode as _pd
from ._common import vmem_nbytes
from .paged_decode import LANES

__all__ = ["latent_write", "latent_gather", "latent_decode_attention",
           "latent_prefill_attention", "mla_decode_kernel_call",
           "mla_kernel_eligible", "LANES", "padded_width"]

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
    chunk_kv = _pd.chunk_pages_for(page_size, pages_per_seq,
                                   _CHUNK_TOKENS) * page_size
    ws = _vmem_working_set(heads, width, rank, chunk_kv, itemsize)
    if ws > _VMEM_GATE_BYTES:
        return False, (f"VMEM working set {ws} B exceeds the "
                       f"{_VMEM_GATE_BYTES} B gate: composite path")
    return True, ""


def mla_decode_kernel_call(q, pool, page_table, ctx_lens, *, rank: int,
                           scale: float, interpret: bool = False):
    """The absorbed-decode kernel. q [batch, heads, width] (``q_lat`` and
    ``q_rope`` side by side, zero-padded to the pool row's width); pool
    [num_pages, page_size, width]; returns ``o_lat`` [batch, heads, rank]
    in q's dtype. The decode pipeline of :mod:`.paged_decode` with no
    values pool: the value is the row's first ``rank`` columns."""
    return _pd.decode_kernel_call(
        q, pool, None, page_table, ctx_lens, out_width=rank, scale=scale,
        chunk_tokens=_CHUNK_TOKENS, name="mla_decode_attention",
        interpret=interpret)


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

"""Unified ragged paged-attention kernel — ONE Pallas program for every
serving attention mode.

Reference analog: Ragged Paged Attention (arxiv 2604.15464). The serving
engine's four attention contracts — prefill, chunked-prefill tail, single
-token decode, and the speculative K+1 verify — are all instances of one
ragged computation (``paged_attention.ragged_mask``): ``s`` new-token
queries per row entering at positions ``ctx_lens[b] .. ctx_lens[b]+s-1``
against that row's paged KV prefix. Before this module the engine served
them through a per-mode zoo (a fixed-shape library decode kernel that was
skipped entirely in int8 mode, plus the gather+sdpa composite for
everything ragged); this kernel serves all of them, fp32 AND int8, through
one program shape:

- **Grid** ``(batch, num_heads // block_heads, s // query_tile)`` — one
  grid step owns one row's head block and query tile end-to-end; no
  output revisits. q and the output keep the caller's ``[b, h, s, d]``:
  a block's last two dims are ``(query_tile, head_dim)``, which the
  chip's compiler tiles for any head block. With ``pipeline_chunk ==
  pages_per_seq`` (one chunk) the full-width softmax runs the SAME ops
  in the SAME order as the composite path, so interpret mode is
  bit-identical to the jitted composite (the CPU-pinnable correctness
  contract; the tests pin it for all four modes x fp32/int8).
- **Chunked DMA pipeline over the LIVE chunks** (``pipeline_chunk <
  pages_per_seq``) — a grid step stages and scores only the chunks that
  hold a position its queries can see: their count is read from the
  prefetched ``ctx_lens`` (``ceil(min(ctx + t0 + tq, table) / chunk)``,
  :func:`_live_span`), not from the table's width, so a row that fills
  half its table moves half the bytes. The chunks go through TWO
  alternating VMEM buffers: while chunk ``c``'s attention contribution
  is computed, chunk ``c+1``'s page DMAs are already in flight, and a
  grid step's last chunk starts the NEXT grid step's first (the grid
  runs in order), so the fetch latency hides under the matmuls. A whole
  chunk is always copied (its dead end re-reads the row's last live
  page: no table entry past the live count is followed, no staging row
  is left uninitialised). The per-chunk contributions combine through
  flash-style online softmax (running max / rescaled sum / fp32
  accumulator), which reorders the fp32 reduction — parity vs the
  composite is the established bounded-divergence pin (mean greedy
  common-prefix >= 0.5), with page accounting and invariants exact; a
  chunk left out is one the mask zeroed whole, so at one chunk size the
  result is the table-wide loop's bit for bit; the single-chunk path
  stays the bit-identity contract. The default chunk is the whole row
  where that fits the VMEM gate (test sizes) and ``_CHUNK_TOKENS`` = 128
  tokens at serving widths (a 1024-token row of 16 heads x 128 in fp32
  is 16 MiB of K+V before any compute): the loop wastes half a chunk a
  row in the mean, and at 8 pages that is 4 of about 34 staged.
  :func:`pages_staged` is the same arithmetic for the host (the
  engine's ``serving_attention_pages_staged_total``).
- **Scalar prefetch** ``(ctx_lens, cu_q_lens, page_table)`` — the ragged
  parameterization. ``cu_q_lens[b] // s`` picks each row's query/output
  block, which makes the OUTPUT index map data-dependent: kernelcheck
  proves its injectivity by evaluating the map with runtime scalar
  arguments (``index_args`` — the resolved, not suppressed,
  ``allow_data_dependent_outputs`` contract).
- **Paged KV gather** — the pools stay in HBM (``ANY`` memory space);
  each grid step DMAs its row's live pages into VMEM scratch through the
  page table (within a chunk, all copies started before any is awaited,
  so the fetches overlap in the DMA queue; across chunks and grid steps
  they overlap with compute). In int8 mode the per-page-per-head dequant
  ``codes * scale / 127`` is FUSED into this gather.
- **Tiling** — what the v5e compiler accepts, checked ahead of time by
  ``tests/test_tpu_compile.py``: a page copy takes ``(page_size,
  block_heads, head_dim)`` out of the ``[pages, page_size, heads,
  head_dim]`` pool, whose minor pair tiles ``(sublanes, 128)`` — so
  ``block_heads`` is a whole sublane tile of the pool dtype (8 fp32 /
  16 bf16 / 32 int8 heads) or every head, and ``head_dim`` is a multiple
  of 128 (at 64 the compiler refuses the copy; the gate then declares
  the composite path and says why). ``block_heads`` and
  ``pipeline_chunk`` are the tunables: ``ragged_tuned.json`` (written by
  ``tools/ragged_autotune.py``, same idiom as ``flash_tuned.json``)
  overrides the defaults, validated by
  ``analysis.kernelcheck.validate_ragged_tuned`` at BANK and at LOAD so
  load can never see an entry bank rejected. A table value is either the
  legacy bare ``block_heads`` int or a dict
  ``{"block_heads": B, "pipeline_chunk": C, "pages_per_seq": P}`` with
  ``C`` dividing ``P`` — the validator rejects a stale chunk that no
  longer divides its recorded page count.

Certification: the ``ragged_paged`` / ``ragged_paged_q8`` /
``ragged_paged_verify`` / ``ragged_paged_prefill`` kernelcheck entries
freeze the VMEM budget (the ×2 staged buffers priced by the scratch
shapes themselves), prove the data-dependent output map injective at
canonical runtime arguments, and bank the roofline + predicted speedup to
``profiles/kernelcheck.json``; the measured share of its roofline is the
benchmark's ``ragged_paged_attention_roofline`` (PERF.md).

Dispatch lives in :mod:`.paged_attention` (``paged_attention()`` routes
every eligible call here; ``decode_kernel_eligible`` delegates to
:func:`ragged_kernel_eligible`, the single gate). On CPU the kernel runs
through the Pallas interpreter when ``FLAGS_ragged_interpret`` is set —
the bit-identity test path; a TPU runs it compiled, and a kernel the
gate called eligible that fails to trace or lower RAISES.
"""
from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from ._common import i32_index_scope, vmem_nbytes
from .paged_attention import QMAX

__all__ = ["ragged_paged_attention", "ragged_kernel_eligible",
           "block_heads_for", "pipeline_chunk_for", "pages_staged"]

#: kernelcheck certificates this module's Pallas kernel is registered
#: under (analysis/kernelcheck.py REGISTRY; lint rule PT011's contract) —
#: one program, certified at each serving mode's canonical shape
KERNELCHECK_CERTS = ("ragged_paged", "ragged_paged_q8",
                     "ragged_paged_verify", "ragged_paged_prefill")

#: VMEM cap the eligibility gate sizes against — mirrors kernelcheck's
#: v5e budget (16 MiB * 0.9 headroom); the certificate enforces the same
#: bound on the canonical shapes, this gate keeps RUNTIME shapes that
#: would blow it on the composite path instead of dying inside Mosaic
_VMEM_GATE_BYTES = int((16 << 20) * 0.9)

#: what the v5e compiler (jax/libtpu 0.9.0 / 0.0.34) answers to the page
#: DMA at a head_dim that is not a whole number of 128-lane rows: the
#: pool's minor axis pads to 128 lanes in HBM and a copy may not take part
#: of a row. Such a pool (gpt3-350m: 16 heads x 64) is served by the
#: composite path until the pool is stored lane-dense (ROADMAP S4)
_HEAD_DIM_REFUSAL = (
    "head_dim {head_dim} is not a multiple of 128 — Mosaic refuses the "
    "page DMA: 'Slice shape along dimension 3 must be aligned to tiling "
    "(128), but is {head_dim}' — composite path")

_TUNED = None

import os as _os

#: overridable for tests; the shipped table lives beside this module
_TUNED_PATH = _os.path.join(_os.path.dirname(__file__), "ragged_tuned.json")


def _tuned_table() -> dict:
    """kernels/ragged_tuned.json: on-chip autotuned launch parameters
    keyed ``"page_size,num_heads,head_dim"`` (written by
    tools/ragged_autotune.py; absent = defaults). A value is the legacy
    bare ``block_heads`` int or the dict schema carrying the pipeline
    chunk. Entries are validated against the kernel's own constraints at
    load time (``analysis.kernelcheck.validate_ragged_tuned`` — the same
    validator the autotune bank site runs, the flash_tuned.json
    discipline), so a hand-edited entry that doesn't divide its head
    count — or names a pipeline chunk no longer dividing its recorded
    page count — raises HERE, naming the entry, before any kernel is
    dispatched with it."""
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
            from ..analysis.kernelcheck import validate_ragged_tuned

            errors = validate_ragged_tuned(table)
            if errors:
                raise ValueError(
                    f"ragged_tuned.json at {path} has entries violating "
                    f"the ragged-kernel constraints:\n  "
                    + "\n  ".join(errors)
                    + "\nRe-run tools/ragged_autotune.py (which validates "
                    "before writing) or fix the entries by hand.")
        _TUNED = table
    return _TUNED


def _tuned_entry(page_size: int, num_heads: int, head_dim: int) -> dict:
    """The tuned entry as the dict schema (a legacy bare int is a
    ``block_heads``-only dict); empty dict when untuned."""
    tuned = _tuned_table().get(f"{page_size},{num_heads},{head_dim}")
    if tuned is None:
        return {}
    if isinstance(tuned, dict):
        return tuned
    return {"block_heads": int(tuned)}


def block_heads_for(page_size: int, num_heads: int, head_dim: int,
                    pool_itemsize: int = 4) -> int:
    """Heads per grid step: the tuned table wins when it has this
    ``(page_size, num_heads, head_dim)``. The default is the smallest
    head block Mosaic can DMA out of the pool: the pool's minor pair
    ``(heads, head_dim)`` is tiled ``(sublanes, 128)``, so a page copy may
    slice the head axis only in whole sublane tiles (8 fp32 / 16 bf16 /
    32 int8 heads) or take every head. A tuned value must divide
    ``num_heads`` (validated at load); a stale one falls to the
    default."""
    tuned = _tuned_entry(page_size, num_heads, head_dim).get("block_heads")
    if tuned and num_heads % int(tuned) == 0:
        return int(tuned)
    sub = 32 // pool_itemsize  # heads in one sublane tile of the pool
    return sub if num_heads % sub == 0 else num_heads


#: query tokens per grid step: a prefill bucket is tiled so the q/out
#: blocks, the logits and the accumulator stay a fixed VMEM size whatever
#: the bucket; a count that is not a multiple runs as one tile
_Q_TILE = 128


def query_tile_for(num_query_tokens: int) -> int:
    if num_query_tokens % _Q_TILE == 0:
        return _Q_TILE
    return num_query_tokens


#: tokens staged per DMA chunk where the row does not fit VMEM whole. The
#: loop stages whole chunks up to the last position a query sees, so a
#: row wastes half a chunk in the mean: small enough that this is a few
#: pages, large enough that a loop turn's fixed cost does not show.
#: Chosen by chip runs of the serving cell (PERF.md section 6, PR 30)
_CHUNK_TOKENS = 128


def pipeline_chunk_for(page_size: int, num_heads: int, head_dim: int,
                       pages_per_seq: int, *, num_query_tokens: int = 1,
                       block_heads: int | None = None,
                       quantized: bool = False,
                       q_itemsize: int = 4) -> int:
    """Pages staged per DMA chunk: the tuned table wins when its chunk
    still divides THIS call's page count (the validator pins it against
    the page count recorded at tune time; a call at a different
    ``pages_per_seq`` falls back rather than mis-tiling). The default is
    ``pages_per_seq`` itself (one chunk, no pipeline, the exact
    gather-all-then-compute path the bit-identity tests pin) whenever the
    whole row's working set fits the VMEM gate, and where it cannot (16
    heads x 128 x 1024 tokens of fp32 K+V is 16 MiB before any compute)
    the largest divisor of ``pages_per_seq`` of at most ``_CHUNK_TOKENS``
    tokens that fits: the pipelined loop runs to the row's live length,
    and what it copies in vain is the dead end of its last chunk."""
    tuned = _tuned_entry(page_size, num_heads,
                         head_dim).get("pipeline_chunk")
    if tuned:
        c = int(tuned)
        if 0 < c < pages_per_seq and pages_per_seq % c == 0:
            return c
    bh = block_heads or block_heads_for(
        page_size, num_heads, head_dim, 1 if quantized else q_itemsize)

    def fits(c):
        return _vmem_working_set(
            head_dim, pages_per_seq * page_size, num_query_tokens, bh,
            pages_per_seq, quantized, pipeline_chunk=c,
            q_itemsize=q_itemsize) <= _VMEM_GATE_BYTES

    if fits(pages_per_seq):
        return pages_per_seq
    for c in range(max(1, _CHUNK_TOKENS // page_size), 0, -1):
        if pages_per_seq % c == 0 and fits(c):
            return c
    return 1


def _resolve_chunk(pipeline_chunk, pages_per_seq: int) -> int:
    """Clamp an explicit/tuned chunk to a legal one: it must be positive
    and divide the page count, else the single-chunk exact path wins."""
    c = int(pipeline_chunk or pages_per_seq)
    if c <= 0 or pages_per_seq % c:
        return pages_per_seq
    return c


def _vmem_working_set(head_dim: int, total_kv: int, num_query_tokens: int,
                      block_heads: int, pages_per_seq: int,
                      quantized: bool,
                      pipeline_chunk: int | None = None,
                      q_itemsize: int = 4) -> int:
    """Static per-grid-step VMEM estimate at the PADDED footprint — every
    buffer's last two dims round up to the (sublanes, 128) tile of its
    dtype, which is what Mosaic allocates. Counted: the K+V staging
    scratch (one chunk-sized buffer at the default single chunk, x2
    alternating buffers when the DMA pipeline is on), the q/output tile
    blocks (x2 — grid-varying blocks pipeline-double-buffer), the
    gathered-scale blocks in int8 mode, and the kernel body's live
    values: the staged chunk transposed to heads-major in the query dtype
    (plus its fp32 dequant image in int8 mode), the logits and the
    probabilities, and the fp32 accumulator."""
    kv_item = 1 if quantized else q_itemsize
    chunk = _resolve_chunk(pipeline_chunk, pages_per_seq)
    n_bufs = 2 if chunk < pages_per_seq else 1
    chunk_kv = (total_kv // pages_per_seq) * chunk
    tq = query_tile_for(num_query_tokens)
    bh, d = block_heads, head_dim
    # K and V staging scratch, in the pool dtype
    ws = 2 * vmem_nbytes((n_bufs, chunk_kv, bh, d), kv_item)
    # q + out blocks, double-buffered
    ws += 2 * 2 * vmem_nbytes((bh, tq, d), q_itemsize)
    if quantized:
        # k/v scale blocks, double-buffered (the unit lane axis pads to a
        # whole 128-lane row), and the fp32 dequant image of K and V
        ws += 2 * 2 * vmem_nbytes((pages_per_seq, bh, 1), 4)
        ws += 2 * vmem_nbytes((chunk_kv, bh, d), 4)
    # staged K and V transposed heads-major, in the query dtype
    ws += 2 * vmem_nbytes((bh, chunk_kv, d), q_itemsize)
    # logits + probabilities, and the accumulator, fp32
    ws += 2 * vmem_nbytes((bh, tq, chunk_kv), 4)
    ws += vmem_nbytes((bh, tq, d), 4)
    return ws


def _launch_params(page_size: int, num_heads: int, head_dim: int,
                   pages_per_seq: int, num_query_tokens: int,
                   quantized: bool, q_itemsize: int, pool_itemsize: int,
                   block_heads: int | None = None,
                   pipeline_chunk: int | None = None) -> tuple[int, int]:
    """``(block_heads, pipeline_chunk)`` a call at these shapes launches
    with — resolved in ONE place, so the eligibility gate sizes exactly
    what the launch would run."""
    bh = block_heads or block_heads_for(page_size, num_heads, head_dim,
                                        pool_itemsize)
    if num_heads % bh:
        bh = num_heads
    chunk = _resolve_chunk(
        pipeline_chunk or pipeline_chunk_for(
            page_size, num_heads, head_dim, pages_per_seq,
            num_query_tokens=num_query_tokens, block_heads=bh,
            quantized=quantized, q_itemsize=q_itemsize),
        pages_per_seq)
    return bh, chunk


def ragged_kernel_eligible(head_dim: int, pages_per_seq: int,
                           page_size: int, num_query_tokens: int = 1, *,
                           num_heads: int | None = None,
                           quantized: bool = False, on_tpu: bool = True,
                           flags_on: bool = True, interpret: bool = False,
                           pipeline_chunk: int | None = None,
                           q_itemsize: int = 4) -> tuple[bool, str]:
    """Single source of truth for the unified-kernel dispatch gates.

    Returns ``(eligible, reason)`` — ``reason`` names the FIRST gate that
    blocks the kernel (empty when eligible). The runtime dispatch
    (``paged_attention.paged_attention``), the engine's kernel-A/B
    predicate, and the kernelcheck dispatch-coverage report all call
    this, so the coverage table can never drift from the dispatch.

    Unlike the retired library-decode gates there is no int8 ban (the
    dequant is fused into the gather) and no page-table-width alignment
    rule — the remaining gates are the flag, the backend (``interpret``
    sanctions the CPU Pallas interpreter — the test path), a positive
    query count, ``head_dim % 128`` for the compiled kernel (the chip's
    compiler refuses a page DMA out of a pool whose minor axis is not a
    whole 128-lane row; the interpreter has no such rule), and the VMEM
    working set (sized at the SAME ``pipeline_chunk`` the launch would
    resolve, including the x2 staged buffers when the chunk pipeline is
    on)."""
    if not flags_on:
        return False, "FLAGS_use_pallas_kernels is off"
    if not on_tpu and not interpret:
        return False, ("CPU backend: Pallas TPU kernels unavailable "
                       "(set FLAGS_ragged_interpret to run the unified "
                       "kernel through the Pallas interpreter)")
    if num_query_tokens < 1:
        return False, f"num_query_tokens {num_query_tokens} < 1"
    if head_dim % 128 and not interpret:
        return False, _HEAD_DIM_REFUSAL.format(head_dim=head_dim)
    bh, chunk = _launch_params(
        page_size, num_heads or 1, head_dim, pages_per_seq,
        num_query_tokens, quantized, q_itemsize,
        1 if quantized else q_itemsize, pipeline_chunk=pipeline_chunk)
    ws = _vmem_working_set(head_dim, pages_per_seq * page_size,
                           num_query_tokens, bh, pages_per_seq, quantized,
                           pipeline_chunk=chunk, q_itemsize=q_itemsize)
    if ws > _VMEM_GATE_BYTES:
        return False, (f"VMEM working set {ws} B (context "
                       f"{pages_per_seq * page_size} x head_dim "
                       f"{head_dim} x block_heads {bh} x pipeline_chunk "
                       f"{chunk}) exceeds the "
                       f"{_VMEM_GATE_BYTES} B gate — composite path")
    return True, ""


#: what :func:`_live_span` is written in: numpy on the host, bare lax
#: primitives on a kernel's int32 scalars. An operator or a jnp function
#: applied to a tracer is a nested jit to trace, and a serving program
#: traces 24 kernels at every start: the pipelined branch's scalar path
#: written in operators made 119 of them a kernel, in primitives 27
_NP = types.SimpleNamespace(clip=np.clip, add=np.add, div=np.floor_divide)
_LAX = types.SimpleNamespace(
    clip=lambda x, lo, hi: jax.lax.clamp(np.int32(lo), x, np.int32(hi)),
    add=lambda x, y: jax.lax.add(x, np.int32(y)),
    div=lambda x, y: jax.lax.div(x, np.int32(y)))


def _live_span(ctx_end, chunk_kv: int, page_size: int, total_kv: int,
               ops=_NP):
    """``(chunks, last page)`` of what a query tile can see when the last
    position any of its queries sees is ``ctx_end - 1``: the chunks of
    ``chunk_kv`` tokens that hold such a position, and the index of the
    last page that does. The ONE piece of arithmetic that bounds the
    kernel's DMA/compute loop (``ops=_LAX``, traced scalars) and that
    :func:`pages_staged` counts on the host (``ops=_NP``). The clamp
    keeps a dead slot's garbage length inside the table and a grid step
    at one chunk or more."""
    live = ops.clip(ctx_end, 1, total_kv)
    return (ops.div(ops.add(live, chunk_kv - 1), chunk_kv),
            ops.div(ops.add(live, -1), page_size))


def _live_start(ctx_end, chunk_kv: int, window: int, total_kv: int,
                ops=_NP):
    """``(first chunk, first position)`` of what the query at ``ctx_end -
    1`` sees through a window of ``window`` tokens (itself and the
    ``window - 1`` before it): the start beside :func:`_live_span`'s end,
    in the same two arithmetics. Positions behind the first are masked
    exactly; chunks behind the first chunk are not staged."""
    live = ops.clip(ctx_end, 1, total_kv)
    lo = ops.clip(ops.add(live, -window), 0, total_kv)
    return ops.div(lo, chunk_kv), lo


def pages_staged(ctx_lens, num_query_tokens: int, *, page_size: int,
                 pages_per_seq: int, chunk_pages: int | None,
                 query_tile: int | None = None, window: int | None = None):
    """Pages a call stages per row, as the kernel stages them: int64
    ``[rows]`` for ``ctx_lens [rows]`` and ``num_query_tokens`` new
    tokens a row. Counted in whole pages (each head block copies its own
    heads' share of a page, so the head blocks together move every staged
    page once) and per query tile, so a prefill's repeated reads of its
    prefix show. ``chunk_pages`` / ``query_tile`` are the launch's
    resolved values (:func:`_launch_params`, :func:`query_tile_for`):
    the pipelined kernel stages whole chunks up to the last position a
    tile's queries see; the single-chunk kernel (``chunk_pages ==
    pages_per_seq``) the whole table a tile; ``chunk_pages=None`` is the
    composite path, which gathers the table's width once. (Not counted:
    the one dummy chunk a call's last grid step starts for a step that
    does not follow.) ``window``: the decode kernel's loop starts at the
    first chunk that holds a position inside it (:func:`_live_start`)."""
    ctx = np.asarray(ctx_lens, np.int64)
    if chunk_pages is None:
        return np.full(ctx.shape, pages_per_seq, np.int64)
    tq = query_tile or query_tile_for(num_query_tokens)
    staged = np.zeros(ctx.shape, np.int64)
    chunk_kv, total_kv = chunk_pages * page_size, pages_per_seq * page_size
    for t0 in range(0, num_query_tokens, tq):
        chunks = _live_span(ctx + (t0 + tq), chunk_kv, page_size,
                            total_kv)[0]
        if window is not None:
            chunks = chunks - _live_start(ctx + (t0 + tq), chunk_kv, window,
                                          total_kv)[0]
        staged += chunk_pages * chunks
    return staged


def _ragged_kernel(tq, page_size, pages_per_seq, block_heads,
                   chunk_pages, scale, quant, lift_batch,
                   ctx_ref, cu_ref, tab_ref, q_ref, k_hbm, v_hbm, *rest):
    """Kernel body for one ``(row, head block, query tile)`` grid step.

    Single chunk (``chunk_pages == pages_per_seq``): every page of the
    row's table is copied HBM -> VMEM (all ``2 * pages_per_seq`` copies
    started before any is awaited — the DMA queue overlaps them), then
    the ragged-masked softmax runs over the full gathered width,
    op-for-op the composite ``sdpa`` formula so interpret mode is
    bit-identical to the composite path.

    Pipelined (``chunk_pages < pages_per_seq``): only the chunks that
    hold a position this tile's queries can see are staged — their count
    comes from the prefetched ``ctx_lens`` (:func:`_live_span`), not
    from the table's width. Chunks of ``chunk_pages`` pages alternate
    through two staging buffers — chunk ``c+1``'s copies are started
    BEFORE chunk ``c`` is awaited, so its DMAs fly while chunk ``c``'s
    logits/softmax/PV matmuls run, and a grid step's last chunk starts
    the NEXT grid step's first (the grid runs in order; ``slot_ref``
    carries the buffer a step begins in) — and the per-chunk
    contributions fold into a flash-style online softmax (running max
    ``m``, rescaled denominator ``l``, fp32 accumulator) finalized as
    ``acc / l``. A chunk that is left out is one the mask would have
    zeroed whole (``p == 0`` and ``alpha == 1`` exactly), so the result
    is the table-wide loop's bit for bit. The fp32 reduction order
    differs from the composite's full-width softmax, so this path
    carries the bounded-divergence contract, not bit-identity."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ksc_ref = vsc_ref = None
    if quant:
        ksc_ref, vsc_ref, *rest = rest
    o_ref, k_s, v_s, sems, *slot_ref = rest  # slot_ref: pipelined only
    bi = pl.program_id(0)
    hb = pl.program_id(1)
    qt = pl.program_id(2)
    h0 = hb * block_heads
    t0 = qt * tq                          # this tile's first query token
    chunk_kv = chunk_pages * page_size

    def _stage(ref, sc_ref, slot, p0):
        """One staged chunk as ``(block_heads, chunk_kv, head_dim)`` in
        the query dtype. int8 mode fuses the dequant here: codes *
        (scale / 127) per (page, head), elementwise identical to
        paged_gather_quant's broadcast, then the composite's astype."""
        x = ref[slot]                     # (chunk_kv, bh, d) pool dtype
        if quant:
            # (pages, 1, bh, 1): the page's per-head scale broadcasts
            # over its page_size tokens (a major axis) and head_dim (the
            # lanes) — no relayout of the scale block
            sc = sc_ref[0, pl.ds(p0, chunk_pages)][:, None]
            x = (x.astype(jnp.float32).reshape(
                chunk_pages, page_size, block_heads, x.shape[-1])
                * sc).astype(q_ref.dtype).reshape(x.shape)
        return jnp.transpose(x, (1, 0, 2))

    qh = q_ref[0]                         # (bh, tq, d)
    d = qh.shape[-1]
    # f32-pinned constants: the body is retraced at LOWERING time outside
    # any i32/x64 scope, where a weak Python literal hardens to f64 and
    # fails the verifier — np.float32 keeps it the same f32 value the
    # composite's weak-typed literal converts to
    sc = (np.float32(scale) if scale is not None
          else 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32)))

    def _mask(width, j0):
        # the ragged_mask contract: query t (row position ctx + t) sees
        # gathered positions j <= ctx + t
        jpos = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 1) + j0
        tpos = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 0) + t0
        return (jpos <= ctx_ref[bi] + tpos)[None]

    if chunk_pages == pages_per_seq:
        def _chunk_dma(op):
            # reconstructing the same copy object is how wait() pairs
            # with start(). The copied window is (page_size, block_heads,
            # head_dim): block_heads is a whole sublane tile of the pool
            # dtype or every head (see block_heads_for), so the slice of
            # the pool's tiled (heads, head_dim) minor pair is
            # tile-aligned
            for j in range(chunk_pages):
                for src, dst, off in ((k_hbm, k_s, 0),
                                      (v_hbm, v_s, chunk_pages)):
                    op(pltpu.make_async_copy(
                        src.at[tab_ref[bi, j], :, pl.ds(h0, block_heads),
                               :],
                        dst.at[0, pl.ds(j * page_size, page_size)],
                        sems.at[0, off + j]))

        _chunk_dma(lambda cp: cp.start())
        _chunk_dma(lambda cp: cp.wait())
        kh = _stage(k_s, ksc_ref, 0, 0)
        vh = _stage(v_s, vsc_ref, 0, 0)
        if lift_batch:
            # bit-identity corner: XLA:CPU lowers the (batch=1, M=1) q.kT
            # matvec through a different accumulation order than the
            # batched form the composite's [b, h, 1, S] einsum takes
            # (measured ~1e-7; batch>=2 and M>=2 are order-consistent).
            # When the composite is batched (b*h >= 2) but this block is
            # the degenerate cell (block_heads == 1, s == 1), duplicate
            # the row — the lowering is data-independent, so row 0 of the
            # batch-2 product is exactly the composite's value
            logits = jax.lax.dot_general(
                jnp.concatenate([qh, qh], axis=0),
                jnp.concatenate([kh, kh], axis=0),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)[:1]
        else:
            logits = jax.lax.dot_general(
                qh, kh, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
        logits = logits * sc
        logits = jnp.where(_mask(kh.shape[1], np.int32(0)), logits,
                           np.float32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1)
        out = jax.lax.dot_general(
            probs.astype(qh.dtype), vh, (((2,), (1,)), ((0,), (0,))))
        o_ref[0] = out.astype(o_ref.dtype)
        return

    # ---- double-buffered pipeline over the LIVE chunks
    (slot_ref,) = slot_ref
    n_b, n_hb, n_qt = (pl.num_programs(i) for i in range(3))
    total_kv = pages_per_seq * page_size

    # int32 scalar arithmetic below is bare lax primitives (see _LAX)
    lax, i32 = jax.lax, np.int32

    def _span(row, tile):
        # (live chunks, last live page) of grid step (row, ., tile): one
        # past the last position any of its queries sees is ctx + t0 + tq
        t_end = lax.add(lax.mul(tile, i32(tq)), i32(tq))
        return _live_span(lax.add(ctx_ref[row], t_end), chunk_kv,
                          page_size, total_kv, _LAX)

    def _start(row, head0, last, c, slot):
        """Start chunk ``c``'s page copies into buffer ``slot``. A whole
        chunk is copied, so no staging row is ever left uninitialised (a
        masked position's ``p`` is 0, and ``0 * NaN`` is NaN); a page
        past ``last``, the row's last live one, re-reads that page, so no
        table entry beyond the live count is ever followed. The copied
        window is (page_size, block_heads, head_dim): block_heads is a
        whole sublane tile of the pool dtype or every head (see
        block_heads_for), so the slice of the pool's tiled
        (heads, head_dim) minor pair is tile-aligned. All of a buffer's
        K copies signal one semaphore, its V copies another."""
        first = lax.mul(c, i32(chunk_pages))
        for j in range(chunk_pages):
            page = tab_ref[row, lax.min(lax.add(first, i32(j)), last)]
            for kv, (src, dst) in enumerate(((k_hbm, k_s), (v_hbm, v_s))):
                pltpu.make_async_copy(
                    src.at[page, :, pl.ds(head0, block_heads), :],
                    dst.at[slot, pl.ds(j * page_size, page_size)],
                    sems.at[slot, kv]).start()

    def _wait(slot):
        # a wait a page copy, each for one page's bytes of its buffer's
        # semaphore (the semaphore counts bytes, whichever copy brought
        # them): after the last, every copy of the buffer has landed
        for j in range(chunk_pages):
            rows = pl.ds(j * page_size, page_size)
            for kv, buf in enumerate((k_s, v_s)):
                pltpu.make_async_copy(buf.at[slot, rows], buf.at[slot, rows],
                                      sems.at[slot, kv]).wait()

    n_chunks, last = _span(bi, qt)

    @pl.when(lax.eq(lax.bitwise_or(lax.bitwise_or(bi, hb), qt), i32(0)))
    def _():
        slot_ref[0] = i32(0)
        _start(bi, h0, last, i32(0), 0)

    # the grid step after this one, in the order the grid runs; behind
    # the last, the first again: its copies are a dummy, landed below
    qt_1, hb_1, bi_1 = (lax.add(x, i32(1)) for x in (qt, hb, bi))
    wrap_qt = lax.eq(qt_1, n_qt)
    qt_n = lax.select(wrap_qt, i32(0), qt_1)
    hb_n = lax.select(wrap_qt, hb_1, hb)
    wrap_hb = lax.eq(hb_n, n_hb)
    hb_n = lax.select(wrap_hb, i32(0), hb_n)
    bi_n = lax.select(wrap_hb, bi_1, bi)
    is_last = lax.eq(bi_n, n_b)
    bi_n = lax.select(is_last, i32(0), bi_n)
    h0_n = lax.mul(hb_n, i32(block_heads))
    last_n = _span(bi_n, qt_n)[1]

    slot0 = slot_ref[0]

    def body(c, carry):
        m, l, acc = carry
        slot = lax.rem(lax.add(slot0, c), i32(2))
        # under this chunk's compute fly the copies of this step's next
        # chunk or, behind its last, of the next grid step's first. One
        # unconditional start with selected operands: a pl.when in the
        # loop's body cost 0.23 s a kernel to trace on the chip's host
        # (24 kernels a program, at every start; PERF.md, PR 30)
        c_1 = lax.add(c, i32(1))
        more = lax.lt(c_1, n_chunks)
        _start(lax.select(more, bi, bi_n), lax.select(more, h0, h0_n),
               lax.select(more, last, last_n), lax.select(more, c_1, i32(0)),
               lax.sub(i32(1), slot))
        _wait(slot)
        p0 = lax.mul(c, i32(chunk_pages))
        khc = _stage(k_s, ksc_ref, slot, p0)
        vhc = _stage(v_s, vsc_ref, slot, p0)
        logits = jax.lax.dot_general(
            qh, khc, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sc
        logits = jnp.where(_mask(chunk_kv, lax.mul(c, i32(chunk_kv))),
                           logits, np.float32(-1e30))
        # online-softmax fold, all fp32: rescale the running sum and
        # accumulator by exp(m - m_new) and add this chunk's terms
        # (m / l keep a trailing unit axis so they broadcast over lanes
        # without a sublane<->lane relayout)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(vhc.dtype), vhc, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((block_heads, tq, 1), np.float32(-1e30), jnp.float32)
    l0 = jnp.zeros((block_heads, tq, 1), jnp.float32)
    acc0 = jnp.zeros((block_heads, tq, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(np.int32(0), n_chunks, body,
                                  (m0, l0, acc0))
    slot_n = lax.rem(lax.add(slot0, n_chunks), i32(2))
    slot_ref[0] = slot_n

    @pl.when(is_last)
    def _():
        _wait(slot_n)    # no step follows: land the dummy copies
    # chunk 0 always holds the row's position 0 (unmasked for every
    # query: jpos 0 <= ctx + tpos), so l > 0 — the division is safe
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, page_table, ctx_lens, *,
                           scale=None, k_scale=None, v_scale=None,
                           block_heads: int | None = None,
                           pipeline_chunk: int | None = None,
                           interpret: bool = False):
    """The unified kernel entry: same contract as the composite
    ``paged_attention`` path for every mode.

    q ``[batch, heads, s, head_dim]`` — ``s`` is 1 for decode, the pad
    bucket for prefill/chunk calls, ``depth + 1`` for spec-verify; pools
    ``[num_pages, page_size, heads, head_dim]`` (int8 codes when
    ``k_scale``/``v_scale`` — ``[num_pages, heads]`` f32 — are given);
    ``ctx_lens [batch]`` tokens resident per row BEFORE this call's new
    tokens (already written to the pool). ``pipeline_chunk`` (pages per
    DMA chunk; default :func:`pipeline_chunk_for`) < ``pages_per_seq``
    turns on the double-buffered DMA/compute pipeline over the row's
    live chunks. Returns ``[batch, heads, s, head_dim]`` — single-chunk,
    bit-identical in interpret mode to the composite gather +
    ragged-masked sdpa; pipelined, bounded-divergence (the online
    softmax reorders the fp32 reduction)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    ps = k_pool.shape[1]
    pps = page_table.shape[1]
    quant = k_scale is not None
    bh, chunk = _launch_params(
        ps, h, d, pps, s, quant, q.dtype.itemsize, k_pool.dtype.itemsize,
        block_heads, pipeline_chunk)
    tq = query_tile_for(s)
    n_bufs = 2 if chunk < pps else 1

    # the ragged parameterization the paper's kernel contract uses:
    # cu_q_lens locates each row's query span — uniform s per call here,
    # but the kernel only ever reads the prefetched cu_q_lens, so
    # mixed-length batches are one table away
    cu = jnp.arange(b + 1, dtype=jnp.int32) * s
    ctx = ctx_lens.astype(jnp.int32)
    tab = page_table.astype(jnp.int32)

    # np.int32 divisor: index maps are (re)traced at LOWERING time,
    # outside any i32_index_scope — a Python-int literal would promote
    # the division to i64 under the package-global x64 and fail Mosaic
    # (and the interpreter's) verifier
    s_i32 = np.int32(s)

    def q_map(bi, hb, qt, ctx, cu, tab):
        return (cu[bi] // s_i32, hb, qt, 0)

    # q/out keep the caller's [b, h, s, d]: the block's last two dims are
    # (query tile, whole head_dim), which Mosaic tiles for any
    # block_heads; the pools stay in HBM behind manual page DMA
    q_spec = pl.BlockSpec((1, bh, tq, d), q_map)
    in_specs = [
        q_spec,
        pl.BlockSpec(memory_space=pl.ANY),   # K pool: manual DMA
        pl.BlockSpec(memory_space=pl.ANY),   # V pool: manual DMA
    ]
    operands = [ctx, cu, tab, q, k_pool, v_pool]
    if quant:
        # gather the tiny per-page scales OUTSIDE the kernel (b*pps*h
        # floats — noise next to the code pools) with the exact
        # paged_gather_quant divisor, laid out [batch, pps, heads, 1]:
        # heads on sublanes and a unit lane axis, the shape the in-kernel
        # dequant broadcasts over (page_size, head_dim) with no relayout
        sc_tab = tab
        if chunk < pps:
            # the pipelined kernel follows no table entry past a row's
            # last live page (_start re-reads that page): its scale
            # stands in there too, so a masked position dequantizes to a
            # finite value whatever the dead entries point at
            last = _live_span(ctx + np.int32(s), chunk * ps, ps, pps * ps,
                              _LAX)[1]
            sc_tab = jnp.take_along_axis(
                tab, jnp.minimum(jnp.arange(pps, dtype=jnp.int32)[None],
                                 last[:, None]), axis=1)
        ksc = (k_scale[sc_tab] / QMAX)[..., None]
        vsc = (v_scale[sc_tab] / QMAX)[..., None]
        sc_spec = pl.BlockSpec((1, pps, bh, 1),
                               lambda bi, hb, qt, *_: (bi, 0, hb, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [ksc, vsc]

    scratch_shapes = [
        # staging buffers: (n_bufs, chunk_kv, ...) — at n_bufs == 2
        # the leading axis IS the double-buffer price kernelcheck's
        # scratch model charges at face value
        pltpu.VMEM((n_bufs, chunk * ps, bh, d), k_pool.dtype),
        pltpu.VMEM((n_bufs, chunk * ps, bh, d), v_pool.dtype),
    ]
    if chunk < pps:
        scratch_shapes += [
            pltpu.SemaphoreType.DMA((2, 2)),   # [buffer, K or V]
            pltpu.SMEM((1,), jnp.int32),       # the buffer a step begins in
        ]
    else:
        scratch_shapes.append(pltpu.SemaphoreType.DMA((1, 2 * chunk)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, h // bh, s // tq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch_shapes)
    kernel = functools.partial(_ragged_kernel, tq, ps, pps, bh, chunk,
                               None if scale is None else float(scale),
                               quant, s == 1 and bh == 1 and b * h >= 2)
    # pipelined, a grid step starts the copies of the next: in order
    semantics = "arbitrary" if chunk < pps else "parallel"
    with i32_index_scope():  # kernel index math assumes int32 defaults
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(semantics,) * 3),
            interpret=interpret,
            name="ragged_paged_attention",
        )(*operands)

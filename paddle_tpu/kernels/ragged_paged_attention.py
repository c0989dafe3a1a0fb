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
- **Chunked DMA pipeline** (``pipeline_chunk < pages_per_seq``) — the
  row's pages are staged through TWO alternating VMEM buffers: while
  chunk ``c``'s attention contribution is computed, chunk ``c+1``'s page
  DMAs are already in flight — the fetch latency hides under the
  matmuls, not just under other fetches. The per-chunk contributions
  combine through flash-style online softmax (running max / rescaled
  sum / fp32 accumulator), which reorders the fp32 reduction — parity
  vs the composite is the established bounded-divergence pin (mean
  greedy common-prefix >= 0.5), with page accounting and invariants
  exact; the single-chunk path stays the bit-identity contract. The
  default chunk is the largest that fits the VMEM gate: one chunk at
  test sizes, a pipelined chunk at serving widths (a 1024-token row of
  16 heads x 128 in fp32 is 16 MiB of K+V before any compute).
- **Scalar prefetch** ``(ctx_lens, cu_q_lens, page_table)`` — the ragged
  parameterization. ``cu_q_lens[b] // s`` picks each row's query/output
  block, which makes the OUTPUT index map data-dependent: kernelcheck
  proves its injectivity by evaluating the map with runtime scalar
  arguments (``index_args`` — the resolved, not suppressed,
  ``allow_data_dependent_outputs`` contract).
- **Paged KV gather** — the pools stay in HBM (``ANY`` memory space);
  each grid step DMAs its row's pages into VMEM scratch through the page
  table (within a chunk, all copies started before any is awaited, so
  the fetches overlap in the DMA queue; across chunks they overlap with
  compute). In int8 mode the per-page-per-head dequant
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

import jax
import jax.numpy as jnp
import numpy as np

from ._common import i32_index_scope, vmem_nbytes
from .paged_attention import QMAX

__all__ = ["ragged_paged_attention", "ragged_kernel_eligible",
           "block_heads_for", "pipeline_chunk_for"]

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


def pipeline_chunk_for(page_size: int, num_heads: int, head_dim: int,
                       pages_per_seq: int, *, num_query_tokens: int = 1,
                       block_heads: int | None = None,
                       quantized: bool = False,
                       q_itemsize: int = 4) -> int:
    """Pages staged per DMA chunk: the tuned table wins when its chunk
    still divides THIS call's page count (the validator pins it against
    the page count recorded at tune time; a call at a different
    ``pages_per_seq`` falls back rather than mis-tiling). The default is
    the largest divisor of ``pages_per_seq`` whose working set fits the
    VMEM gate: ``pages_per_seq`` itself (one chunk, no pipeline, the exact
    gather-all-then-compute path the bit-identity tests pin) whenever the
    whole row fits, a pipelined chunk at serving widths where it cannot
    (16 heads x 128 x 1024 tokens of fp32 K+V is 16 MiB before any
    compute)."""
    tuned = _tuned_entry(page_size, num_heads,
                         head_dim).get("pipeline_chunk")
    if tuned:
        c = int(tuned)
        if 0 < c < pages_per_seq and pages_per_seq % c == 0:
            return c
    bh = block_heads or block_heads_for(
        page_size, num_heads, head_dim, 1 if quantized else q_itemsize)
    for c in range(pages_per_seq, 0, -1):
        if pages_per_seq % c == 0 and _vmem_working_set(
                head_dim, pages_per_seq * page_size, num_query_tokens, bh,
                pages_per_seq, quantized, pipeline_chunk=c,
                q_itemsize=q_itemsize) <= _VMEM_GATE_BYTES:
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

    Pipelined (``chunk_pages < pages_per_seq``): chunks of
    ``chunk_pages`` pages alternate through two staging buffers — chunk
    ``c+1``'s copies are started BEFORE chunk ``c`` is awaited, so its
    DMAs fly while chunk ``c``'s logits/softmax/PV matmuls run — and the
    per-chunk contributions fold into a flash-style online softmax
    (running max ``m``, rescaled denominator ``l``, fp32 accumulator)
    finalized as ``acc / l``. The fp32 reduction order differs from the
    composite's full-width softmax, so this path carries the
    bounded-divergence contract, not bit-identity."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        ksc_ref, vsc_ref, o_ref, k_s, v_s, sems = rest
    else:
        o_ref, k_s, v_s, sems = rest
        ksc_ref = vsc_ref = None
    bi = pl.program_id(0)
    h0 = pl.program_id(1) * block_heads
    t0 = pl.program_id(2) * tq            # this tile's first query token
    num_chunks = pages_per_seq // chunk_pages
    chunk_kv = chunk_pages * page_size

    def _copy(page, j, slot, src, dst, sem_off):
        # page: row-table index; j: slot-local page; reconstructing the
        # same copy object is how wait() pairs with start(). The copied
        # window is (page_size, block_heads, head_dim): block_heads is a
        # whole sublane tile of the pool dtype or every head (see
        # block_heads_for), so the slice of the pool's tiled
        # (heads, head_dim) minor pair is tile-aligned
        return pltpu.make_async_copy(
            src.at[tab_ref[bi, page], :, pl.ds(h0, block_heads), :],
            dst.at[slot, pl.ds(j * page_size, page_size)],
            sems.at[slot, sem_off + j])

    def _chunk_dma(c, slot, op):
        for j in range(chunk_pages):
            page = c * chunk_pages + j
            op(_copy(page, j, slot, k_hbm, k_s, 0))
            op(_copy(page, j, slot, v_hbm, v_s, chunk_pages))

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
            sc = sc_ref[0, p0:p0 + chunk_pages][:, None]
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

    if num_chunks == 1:
        _chunk_dma(0, 0, lambda cp: cp.start())
        _chunk_dma(0, 0, lambda cp: cp.wait())
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

    # ---- double-buffered pipeline: warm up chunk 0, then per chunk
    # start c+1's DMAs before waiting on c — fetch hides under compute
    _chunk_dma(0, 0, lambda cp: cp.start())
    m = jnp.full((block_heads, tq, 1), np.float32(-1e30), jnp.float32)
    l = jnp.zeros((block_heads, tq, 1), jnp.float32)
    acc = jnp.zeros((block_heads, tq, d), jnp.float32)
    for c in range(num_chunks):
        slot = c % 2
        if c + 1 < num_chunks:
            _chunk_dma(c + 1, (c + 1) % 2, lambda cp: cp.start())
        _chunk_dma(c, slot, lambda cp: cp.wait())
        khc = _stage(k_s, ksc_ref, slot, c * chunk_pages)
        vhc = _stage(v_s, vsc_ref, slot, c * chunk_pages)
        logits = jax.lax.dot_general(
            qh, khc, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sc
        logits = jnp.where(_mask(chunk_kv, np.int32(c * chunk_kv)), logits,
                           np.float32(-1e30))
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
        m = m_new
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
    DMA chunk; default tuned-or-``pages_per_seq``) < ``pages_per_seq``
    turns on the double-buffered DMA/compute pipeline. Returns
    ``[batch, heads, s, head_dim]`` — at the single-chunk default,
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
        ksc = (k_scale[tab] / QMAX)[..., None]
        vsc = (v_scale[tab] / QMAX)[..., None]
        sc_spec = pl.BlockSpec((1, pps, bh, 1),
                               lambda bi, hb, qt, *_: (bi, 0, hb, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [ksc, vsc]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, h // bh, s // tq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            # staging buffers: (n_bufs, chunk_kv, ...) — at n_bufs == 2
            # the leading axis IS the double-buffer price kernelcheck's
            # scratch model charges at face value
            pltpu.VMEM((n_bufs, chunk * ps, bh, d), k_pool.dtype),
            pltpu.VMEM((n_bufs, chunk * ps, bh, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((n_bufs, 2 * chunk)),
        ])
    kernel = functools.partial(_ragged_kernel, tq, ps, pps, bh, chunk,
                               None if scale is None else float(scale),
                               quant, s == 1 and bh == 1 and b * h >= 2)
    with i32_index_scope():  # kernel index math assumes int32 defaults
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=interpret,
            name="ragged_paged_attention",
        )(*operands)

"""Paged KV cache: preallocated page pool + refcounted allocator + page
tables + automatic prefix caching.

The device side is a per-layer pool whose leaves the MODEL states
(``PagedCacheSpec``: GPT keeps ``k_pool`` and ``v_pool`` of ``[num_pages,
page_size, heads, head_dim]``, a latent-attention model one ``kv_pool`` of
``[num_pages, page_size, width]`` with no heads axis), updated only
functionally (``.at[]`` scatters in kernels/paged_attention.py and
kernels/latent_paged_attention.py) so the whole cache threads through the
engine's jitted step. Nothing here reads a leaf by name: the movers (swap,
copy-on-write, spill) thread ``cfg.pool_leaf_keys`` in order. A model whose
layers keep different things states its leaves BY LAYER
(``leaves_by_layer``), and a third kind of leaf beside a token's and a
page's: what a SLOT keeps whatever its length (``CacheLeaf.per_slot``: a
recurrent layer's state, ``[max_batch, ...]``), which no allocator hands
out, which the model zeroes in the program when a request starts at
position 0, which ``swap_out`` / ``swap_in`` carry in the ``SwapHandle``
beside the slot's pages, and which makes the pages unshareable by prefix.
A model that keeps NOTHING a token (every leaf per-slot: a model of
recurrent state alone) has no page on the device: the cache keeps its page
bookkeeping on the host only, sized so that it never runs out
(``PagedKVCache.paged`` is False), and the engine uploads no table.
The host side is bookkeeping only: a refcounted block allocator
and per-slot page tables, mirrored into a dense ``[max_batch,
pages_per_seq]`` int32 array each step — static shape, so table churn never
recompiles.

Pages come in GROUPS by layer kind (``PageGroup``, data the model states):
each group has its own page count, allocator, page table and LIFETIME. A
model of one group (GPT, a latent model, a hybrid's attention layers) is
what it always was: ``cache.allocator`` and ``cache.page_table`` are group
0's. A group with a ``window`` (layers whose query sees itself and the
``window - 1`` tokens before it) returns a page to its allocator as soon
as no later query of the slot can see it (``release_behind``): its table
row keeps a position's column, so column ``i`` is still tokens ``i *
page_size ..``, and the columns behind the window read the null page. The
groups share ``pages_per_seq`` (a table row's width) and the slot; a
request is admitted, grown, swapped and released in all of them together,
and exhaustion of any one is exhaustion.

Page 0 is reserved (never allocated): it is the null/trash page that padding
tokens and inactive slots write to, keeping the jitted scatter branch-free.

Prefix caching (vLLM-style automatic page sharing): every FULL page whose
token block is known is registered in a content index under a LINKED exact
key ``(parent_serial, block_tokens)`` — the parent's never-reused
registration serial pins the rest of the prefix transitively, giving
exact matching (no hash collisions, so cached reuse can never corrupt
numerics) at O(page_size) memory per page. A new
request's prompt is matched against the index in whole pages; matched pages
are mapped into its page table with a refcount bump instead of being
re-prefilled. Pages whose refcount drops to zero while registered stay
resident in an LRU "reclaimable" set — future identical prefixes re-hit
them, and an allocation that would otherwise fail evicts them oldest-first
(purging their index entries so a recycled page can never serve stale KV).

Copy-on-write: a request that must write into a shared page (the only such
write is the recompute of the LAST prompt token when the entire prompt was
cached — its logits are needed to sample the first output token) gets a
private copy first when any other holder exists; the last holder writes in
place (the rewrite reproduces the identical bytes: KV of the same tokens
over the same exact-zero-masked prefix is deterministic).

Swap-style preemption: ``swap_out(slot)`` copies the slot's pages into a
host-memory ``SwapHandle`` through ONE jitted gather over a stacked
per-layer pool view (not a per-layer host loop), and ``swap_in``
reallocates (possibly different page ids) and restores the bytes through
one jitted donated scatter. Both run over fixed shapes (page index vectors
padded to ``pages_per_seq`` with the null page), so swap events never
retrigger a compile — ``compile_counts`` pins exactly one trace each.

Quantized pool (``kv_dtype="int8"``, KVQuant-style — arxiv 2401.18079):
the per-layer pools store int8 codes plus per-page-per-head f32 absmax
scales ``[num_pages, num_heads]``, quantized in-jit at scatter time and
dequantized inside the attention gather (kernels/paged_attention.py).
Every host-side structure here — allocator, page tables, prefix index,
COW, swap — moves LOGICAL page ids and opaque page bytes, so quantization
changes only the byte volume: swap handles and the host tier carry the
codes + scales verbatim (restores are bit-exact), and HBM per page drops
~4x. The fp32 default path is byte-for-byte unchanged.

Host spill tier (``host_tier_bytes > 0``): at LRU eviction, refcount-0
indexed prefix pages are SPILLED to a bounded host-memory tier through the
same jitted swap gather (one batched gather per eviction sweep, chunked at
``pages_per_seq``) instead of being purged. Each spilled page keeps its
content-index key AND its chain serial, so the next prompt matching that
prefix restores it through the donated swap scatter before prefill — the
restored page re-registers under its original serial, descendants on
device or in the tier stay reachable, and the admission counts as a prefix
hit. The tier LRU-drops its own oldest entries past the byte bound.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from ..obs import PhaseAccumulator

NULL_PAGE = 0
_RESERVED_PAGES = 1  # page 0 = null page


class PageAllocator:
    """Refcounted block allocator over page ids ``[_RESERVED_PAGES,
    num_pages)``. ``alloc`` hands out pages at refcount 1; ``incref``/
    ``decref`` implement sharing; ``free`` is decref-to-zero for every page
    (so double-free and foreign-page free still raise — the invariants the
    serving tests pin down). A page at refcount zero either returns to the
    free list or, when ``hold=True`` (the prefix cache's reclaimable
    pages), parks in an LRU side pool until reclaimed or re-taken."""

    def __init__(self, num_pages: int):
        if num_pages <= _RESERVED_PAGES:
            raise ValueError(f"need more than {_RESERVED_PAGES} pages "
                             f"(page 0 is the reserved null page)")
        self.num_pages = num_pages
        # pop() hands out low ids first (stable, test-friendly)
        self._free = list(range(num_pages - 1, _RESERVED_PAGES - 1, -1))
        self._ref: dict[int, int] = {}  # page -> refcount (>= 1)
        # refcount-0 pages held for the prefix cache, oldest (LRU) first
        self._cached: OrderedDict[int, None] = OrderedDict()

    @property
    def num_usable(self) -> int:
        return self.num_pages - _RESERVED_PAGES

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_reclaimable(self) -> int:
        """Refcount-0 pages parked for the prefix cache — free after an LRU
        eviction, but still holding valid reusable KV until then."""
        return len(self._cached)

    @property
    def pages_in_use(self) -> int:
        """Pages referenced by at least one holder. Reclaimable cached
        pages are NOT in use: accounting drains to zero when every request
        retires even while the prefix cache stays warm."""
        return len(self._ref)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> list[int] | None:
        """n pages at refcount 1, or None (and no state change) when the
        free list can't cover the request — partial grants would deadlock
        the scheduler. Reclaimable pages are NOT tapped here: the owner of
        the prefix index must evict (and purge) them explicitly."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, page: int) -> int:
        """Add a holder to a live page. A reclaimable (refcount-0) page
        must be re-taken with ``take_cached`` instead."""
        if page not in self._ref:
            raise ValueError(f"incref of page {page} with no live holders")
        self._ref[page] += 1
        return self._ref[page]

    def decref(self, page: int, hold: bool = False) -> int:
        """Drop one holder; returns the remaining count. At zero the page
        returns to the free list, or parks in the reclaimable LRU pool when
        ``hold`` (the caller vouches its content is indexed for reuse).
        Decref of a page with no holders raises — double decref and foreign
        pages are caller bugs, never silently absorbed."""
        c = self._ref.get(page)
        if c is None:
            raise ValueError(
                f"decref of page {page} not handed out by this allocator "
                f"(double free or foreign page)")
        c -= 1
        if c:
            self._ref[page] = c
            return c
        del self._ref[page]
        if hold:
            self._cached[page] = None
            self._cached.move_to_end(page)
        else:
            self._free.append(page)
        return 0

    def free(self, pages) -> None:
        """Decref-to-zero each page (back-compat surface: a non-shared page
        at refcount 1 goes straight back to the free list)."""
        for p in pages:
            self.decref(p)

    def take_cached(self, page: int) -> None:
        """Prefix-cache hit on a reclaimable page: revive it at refcount 1
        without touching its pool bytes."""
        del self._cached[page]
        self._ref[page] = 1

    def reclaim_lru(self) -> int | None:
        """Evict the least-recently-parked reclaimable page to the free
        list; returns its id (the caller MUST purge its index entry) or
        None when nothing is reclaimable."""
        if not self._cached:
            return None
        page, _ = self._cached.popitem(last=False)
        self._free.append(page)
        return page


class HostTierRestoreError(RuntimeError):
    """A host-tier prefix restore failed (injected via the ``restore_fail``
    fault point or a real scatter error). The admission is undone and the
    stale tier entries dropped; the engine retires the request FAILED."""


def _present(*arrays) -> tuple:
    return tuple(a for a in arrays if a is not None)


def _by_field(arrays) -> dict:
    """A pool's arrays, in leaf order, under the handles' field names."""
    if len(arrays) > 4:
        raise ValueError("a host handle carries at most four pool leaves")
    return dict(zip(("k", "v", "k_scale", "v_scale"), arrays))


@dataclass(eq=False)  # ndarray fields: identity semantics (lint rule PT001)
class SwapHandle:
    """Host-memory copy of one sequence's KV pages (swap-style preemption).

    One array a pool leaf, in ``PagedCacheConfig.pool_leaf_keys`` order
    (the field names are those of the two-leaf pool: ``k`` is the first
    leaf, ``v`` the second where the pool has one — a latent pool has one
    leaf), each stacked over layers: ``[num_layers, n_pages, page_size,
    ...]`` in page-table row order, so restoring into ANY n_pages free
    pages (in order) preserves every token position exactly. Quantized
    pools additionally carry the per-page-per-head scales ``[num_layers,
    n_pages, heads]`` — the handle holds the pool's raw bytes either way,
    so a swap round-trip is bit-exact in both modes.
    """
    n_pages: int
    k: np.ndarray | None  # None: a pool with no paged leaf
    v: np.ndarray | None = None
    k_scale: np.ndarray | None = None
    v_scale: np.ndarray | None = None
    # what the SLOT kept beside its pages (a recurrent model's state): one
    # array a per-slot leaf, in ``slot_leaf_keys`` order, each stacked over
    # the layers that keep it; empty for a pool of pages alone
    state: tuple = ()
    # a pool of several page groups: ``(pages, first table column)`` of
    # each group behind the first, whose pages are ``n_pages`` from column
    # 0. The arrays' page axis is as wide as the widest group; a layer's
    # row holds its own group's pages from column 0 of that axis
    rest: tuple = ()

    @property
    def arrays(self) -> tuple:
        return _present(self.k, self.v, self.k_scale, self.v_scale)

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays + tuple(self.state))


@dataclass(eq=False)  # ndarray fields: identity semantics (lint rule PT001)
class SpilledPage:
    """One prefix page in the host tier: its content-index key, its chain
    serial (kept so a restore re-links descendants exactly), and the raw
    per-layer page bytes — codes + scales in quantized mode."""
    key: tuple
    serial: int
    k: np.ndarray  # the pool's first leaf: [num_layers, page_size, ...]
    v: np.ndarray | None = None  # its second, where it has one
    k_scale: np.ndarray | None = None  # [num_layers, heads] (quantized)
    v_scale: np.ndarray | None = None

    @property
    def arrays(self) -> tuple:
        """One array a pool leaf, in ``pool_leaf_keys`` order."""
        return _present(self.k, self.v, self.k_scale, self.v_scale)

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays)


class HostTier:
    """Bounded LRU of :class:`SpilledPage` keyed by content-index key —
    the capacity tier behind the paged pool. Pure host-side bookkeeping:
    the cache owns every device transfer."""

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self.bytes = 0
        self._entries: OrderedDict[tuple, SpilledPage] = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key: tuple, touch: bool = True) -> SpilledPage | None:
        """Peek an entry; the caller pops it only after a successful
        restore. ``touch`` promotes it to MRU — pass False for read-only
        PROBES (the scheduler's degraded-mode warm-waiter scan probes
        every waiter every step; letting probes reorder the LRU would
        make never-admitted stale prefixes outlive the genuinely warm
        ones at the byte bound)."""
        e = self._entries.get(key)
        if e is not None and touch:
            self._entries.move_to_end(key)
        return e

    def put(self, entry: SpilledPage) -> None:
        """Insert, dropping oldest entries (for real — their KV is gone)
        until the byte bound holds. An entry larger than the whole bound
        is refused outright."""
        self.pop(entry.key)
        if entry.nbytes > self.max_bytes:
            return
        while self._entries and self.bytes + entry.nbytes > self.max_bytes:
            _, old = self._entries.popitem(last=False)
            self.bytes -= old.nbytes
        self._entries[entry.key] = entry
        self.bytes += entry.nbytes

    def pop(self, key: tuple) -> SpilledPage | None:
        e = self._entries.pop(key, None)
        if e is not None:
            self.bytes -= e.nbytes
        return e


# ------------------------------------------------------ prefix digests
# Chained per-page digests of a token stream — the fleet router's gossip
# currency. The cache's own index keys stay EXACT token tuples (a digest
# collision there would splice foreign KV); digests are advisory routing
# hints only, so a collision costs at worst one suboptimal route. FNV-1a
# 64-bit with explicit constants: python's hash() is salted per process
# and could never gossip across replicas or runs.
DIGEST_SEED = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_U64 = (1 << 64) - 1


def _block_tokens(tokens, page_size: int, i: int) -> tuple:
    """Block ``i`` of ``tokens`` as a plain int tuple — the single place
    token blocks are sliced for keying, shared by the exact index keys
    and the gossip digests so they can never disagree."""
    return tuple(int(t) for t in tokens[i * page_size:(i + 1) * page_size])


def _digest_step(parent_digest: int, block: tuple) -> int:
    """Fold one page-aligned token block into its parent chain digest."""
    h = parent_digest
    for t in block:
        for shift in (0, 8, 16, 24):  # 4 bytes/token covers any vocab
            h ^= (t >> shift) & 0xFF
            h = (h * _FNV_PRIME) & _U64
        h ^= 0xFE  # token delimiter: (1,2),(3) never equals (1),(2,3)
        h = (h * _FNV_PRIME) & _U64
    return h


def prefix_digest(tokens, page_size: int) -> tuple:
    """Chained digests for every FULL page-aligned prefix of ``tokens``:
    element ``i`` summarizes blocks ``0..i`` inclusive. The router hashes
    an incoming prompt once with this and counts how many leading
    elements appear in a replica's gossiped digest set — that count times
    ``page_size`` equals what ``cached_prefix_tokens`` would report
    locally (pinned by a parity test)."""
    out, h = [], DIGEST_SEED
    for i in range(len(tokens) // page_size):
        h = _digest_step(h, _block_tokens(tokens, page_size, i))
        out.append(h)
    return tuple(out)


@dataclass(frozen=True)
class PageGroup:
    """The layers that share a page table and a page's lifetime, as the
    MODEL states them: ``layers`` (indices into the model's layers; every
    layer that pages is in exactly one group) and, for layers whose query
    sees only itself and the ``window - 1`` tokens before it, ``window``:
    the cache then frees a slot's page once every later query's window has
    passed it. ``window=None`` keeps a context's every page."""
    name: str
    layers: tuple
    window: int | None = None


@dataclass(frozen=True)
class CacheLeaf:
    """One leaf of a layer's pool, as the MODEL states it: what a token
    keeps (``shape``, behind the pool's ``[num_pages, page_size]``), with
    ``per_page`` what a page keeps (behind ``[num_pages]``: the int8
    pool's scales), or with ``per_slot`` what a SLOT keeps whatever its
    length (behind ``[max_batch]``: a recurrent layer's state). No
    allocator hands a per-slot leaf out and no page table names it: row
    ``s`` is slot ``s``'s, the model zeroes it in the program when a
    request starts at position 0, and a swap carries it with the slot's
    pages."""
    name: str
    shape: tuple
    dtype: object
    per_page: bool = False
    per_slot: bool = False

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * np.dtype(self.dtype).itemsize


@dataclass(frozen=True)
class PagedCacheSpec:
    """What a model keeps in the paged cache — its answer to
    ``model.paged_cache_spec(kv_dtype=, tensor_parallel=, speculative=)``,
    which the engine asks once at construction (a model that cannot do
    what is asked raises ValueError with the reason). ``dtype`` is the
    weights'. ``counters`` names what each layer's returned cache may
    carry under ``"counters"`` (an int32 vector, summed over the layers
    of a launch and fetched with its tokens). ``pages_staged``, where the
    model can say it: ``(num_query_tokens, pages_per_seq, page_size) ->
    (ctx_lens [rows] -> pages [rows])``, the pages one layer's attention
    copies out of the pool for a launch, by the path its dispatch takes
    (the engine's ``serving_attention_pages_staged_total``).

    ``leaves`` is what every layer keeps; a model whose layers differ by
    kind gives ``leaves_by_layer`` instead, one tuple of leaves a layer
    (a hybrid: ``k_pool`` / ``v_pool`` in its attention layers, a state a
    slot in its recurrent ones). ``no_prefix_sharing``, where the model's
    pages cannot be shared by token prefix, is the reason (a page of keys
    without the state that went with its last token is a wrong answer):
    the engine refuses ``enable_prefix_caching`` with it."""
    num_layers: int
    max_seq_len: int
    dtype: object
    leaves: tuple = ()
    counters: tuple = ()
    pages_staged: object = None
    leaves_by_layer: tuple | None = None
    no_prefix_sharing: str = ""
    # the page groups by layer kind (``PageGroup``); () is one group of
    # every layer, which keeps every page: GPT, a latent model, a hybrid
    groups: tuple = ()
    # where ``pages_staged`` counts more than one layer's worth (layers of
    # different kinds stage different spans): ``(num_query_tokens,
    # page_size) -> ((ctx_lens [rows], tokens [rows]) -> pages [rows])``,
    # the pages that hold what a launch's real tokens attend to, counted
    # over the same layers. None: one layer's, ``ceil((ctx + tokens) /
    # page_size)``
    pages_live: object = None
    # the model computes its head only at the positions the engine names
    # (``head_at`` [rows] int32 in every layer's cache of a prefill; the
    # logits come back ``[rows, 1, vocab]``): a prefill reads one row
    head_at_positions: bool = False
    # where a page of this model cannot cross the wire (serving/wire.py's
    # page frame), the reason: a fleet that fetches pages refuses it
    no_wire: str = ""


def kv_heads_leaves(num_heads: int, head_dim: int, dtype=None,
                    quantized: bool = False) -> tuple:
    """The leaves of a keys-and-values pool with a heads axis: ``k_pool``
    and ``v_pool`` of ``[heads, head_dim]`` a token; int8 codes beside
    per-page-per-head float32 scales when quantized."""
    dt = np.int8 if quantized else (dtype if dtype is not None
                                    else np.float32)
    leaves = (CacheLeaf("k_pool", (num_heads, head_dim), dt),
              CacheLeaf("v_pool", (num_heads, head_dim), dt))
    if quantized:
        leaves += (CacheLeaf("k_scale", (num_heads,), np.float32, True),
                   CacheLeaf("v_scale", (num_heads,), np.float32, True))
    return leaves


@dataclass(frozen=True)
class PagedCacheConfig:
    num_layers: int
    # a keys-and-values pool by its two sizes (``leaves`` then follows),
    # or any pool by its ``leaves`` (then these stay 0)
    num_heads: int = 0
    head_dim: int = 0
    num_pages: int = 64
    page_size: int = 16
    max_batch: int = 4
    pages_per_seq: int = 8  # page-table width == max seq pages per request
    dtype: object = None  # jnp dtype; None -> float32
    enable_prefix_caching: bool = True  # cross-request page sharing
    debug_checks: bool = False  # strict CompileGuards on the swap/COW jits
    tp: object = None  # serving.tp.TPContext: pools sharded on the heads
    # axis across its mesh, swap/COW jits wrapped to run per-shard. None =
    # single-chip. The allocator, page tables, and prefix index are
    # host-side and operate on LOGICAL page ids — sharding never touches
    # them.
    kv_dtype: str = "float32"  # "float32" | "int8": int8 stores the pools
    # as codes + per-page-per-head f32 absmax scales, quantized at scatter
    # time and dequantized inside the attention gather — ~4x less HBM per
    # resident token at a bounded greedy-quality delta. The fp32 default
    # is byte-for-byte the pre-quantization path.
    host_tier_bytes: int = 0  # host-memory spill tier bound; 0 = off.
    # Evicted refcount-0 prefix pages spill here (keeping their index keys)
    # instead of being purged, and restore on the next prefix hit.
    leaves: tuple | None = None  # CacheLeaf a pool leaf, as the model's
    # PagedCacheSpec states them; None: the keys-and-values pool of
    # num_heads x head_dim in ``dtype`` (int8 + scales when quantized)
    leaves_by_layer: tuple | None = None  # one tuple of CacheLeaf a layer,
    # for a model whose layers keep different things (``leaves`` is then
    # not read)
    groups: tuple = ()  # PageGroup a page group, as the model's
    # PagedCacheSpec states them; () is one group "full" of every layer
    group_pages: tuple = ()  # the pages (null page included) of
    # groups[1:], in order; groups[0] has ``num_pages``

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    @property
    def page_groups(self) -> tuple:
        """The groups, resolved: one ``full`` group of every layer where
        the model stated none."""
        if not self.groups:
            return (PageGroup("full", tuple(range(self.num_layers))),)
        seen = [i for g in self.groups for i in g.layers]
        if sorted(seen) != sorted(set(seen)) or any(
                not 0 <= i < self.num_layers for i in seen):
            raise ValueError("page groups must name each layer at most "
                             f"once, inside the model: {self.groups}")
        if len(self.group_pages) != len(self.groups) - 1:
            raise ValueError(
                f"{len(self.groups)} page groups need {len(self.groups) - 1}"
                f" entries of group_pages, got {self.group_pages}")
        for g in self.groups:
            if g.window is not None and g.window < 1:
                raise ValueError(f"group {g.name}: window {g.window} < 1")
        if self.groups[0].window is not None:
            raise ValueError(
                "the first page group keeps a context's every page (the "
                "prefix index, copy-on-write and the spill tier are its); "
                "a model of window layers alone states an empty first "
                "group")
        return tuple(self.groups)

    @property
    def group_num_pages(self) -> tuple:
        """The pages of each group, the null page included."""
        return (self.num_pages,) + tuple(self.group_pages)

    @property
    def group_of_layer(self) -> tuple:
        """The group of each layer (0 for a layer in none: it keeps no
        page, and nothing reads its table)."""
        of = [0] * self.num_layers
        for gi, g in enumerate(self.page_groups):
            for i in g.layers:
                of[i] = gi
        return tuple(of)

    @property
    def layer_leaves(self) -> tuple:
        """One tuple of CacheLeaf a layer: what each layer's pool holds,
        in the fixed order that the engine and the movers thread them
        in."""
        if self.leaves_by_layer is not None:
            if len(self.leaves_by_layer) != self.num_layers:
                raise ValueError(
                    f"leaves_by_layer states {len(self.leaves_by_layer)} "
                    f"layers, num_layers is {self.num_layers}")
            return tuple(tuple(ls) for ls in self.leaves_by_layer)
        one = self.leaves if self.leaves is not None else kv_heads_leaves(
            self.num_heads, self.head_dim, self.dtype, self.quantized)
        return (tuple(one),) * self.num_layers

    def _leaf_keys(self, per_slot: bool) -> tuple:
        names = [lf.name for ls in self.layer_leaves for lf in ls
                 if lf.per_slot == per_slot]
        return tuple(dict.fromkeys(names))

    @property
    def pool_leaf_keys(self) -> tuple:
        """The names of the PAGED leaves (a token's and a page's) of the
        layers that have them, in a fixed order — the movers (swap,
        copy-on-write, spill) thread these and stay layout-agnostic."""
        return self._leaf_keys(per_slot=False)

    @property
    def slot_leaf_keys(self) -> tuple:
        """The names of the per-slot leaves, in a fixed order; empty for a
        pool of pages alone."""
        return self._leaf_keys(per_slot=True)

    @property
    def kv_bytes_per_token(self) -> int:
        """Device bytes one resident token costs across the layers that
        page (every per-token leaf, plus the per-page leaves amortized per
        token) — the ``serving_kv_bytes_per_token`` gauge."""
        leaves = [lf for ls in self.layer_leaves for lf in ls
                  if not lf.per_slot]
        per_token = sum(lf.nbytes for lf in leaves if not lf.per_page)
        per_page = sum(lf.nbytes for lf in leaves if lf.per_page)
        return per_token + (per_page + self.page_size - 1) // self.page_size

    @property
    def state_bytes_per_slot(self) -> int:
        """Device bytes a slot keeps whatever its length, across the
        layers that keep a state — the ``serving_state_bytes_per_slot``
        gauge; 0 for a pool of pages alone."""
        return sum(lf.nbytes for ls in self.layer_leaves for lf in ls
                   if lf.per_slot)

    @property
    def max_tokens_per_seq(self) -> int:
        return self.pages_per_seq * self.page_size

    @property
    def usable_pages(self) -> int:
        return self.num_pages - _RESERVED_PAGES


def init_pools(cfg: PagedCacheConfig) -> list[dict]:
    """Per-layer dicts of the pool's leaves (``cfg.layer_leaves``: GPT's
    {k_pool, v_pool}, a latent model's {kv_pool}, a recurrent layer's
    per-slot {ssm_state, conv_state} behind ``[max_batch]``), device
    arrays, zero-filled; quantized pools add the zero-initialized {k_scale,
    v_scale} leaves (a zero scale marks an all-zero page — the write path
    substitutes 1.0 before any division). Under tensor parallelism every
    leaf is CREATED under its heads-axis sharding — each device allocates
    only its own [num_pages, page_size, heads/tp, head_dim] shard; a pool
    sized for the mesh never exists whole on one device (it would not
    fit)."""
    import jax.numpy as jnp

    pool_sh, scale_sh = (cfg.tp.pool_shardings() if cfg.tp is not None
                         else (None, None))

    def leaf(lf, num_pages):
        if lf.per_slot:
            return jnp.zeros((cfg.max_batch,) + tuple(lf.shape), lf.dtype)
        return jnp.zeros(
            (num_pages,) + (() if lf.per_page else (cfg.page_size,))
            + tuple(lf.shape), lf.dtype,
            device=scale_sh if lf.per_page else pool_sh)

    # a layer's pool has its group's pages
    pages = cfg.group_num_pages
    return [{lf.name: leaf(lf, pages[g]) for lf in leaves}
            for leaves, g in zip(cfg.layer_leaves, cfg.group_of_layer)]


class _Group:
    """One page group's host state: its allocator, its table (a view of
    the cache's stacked tables) and each live slot's pages in position
    order, the first of them at table column ``first[slot]``: 0 but in a
    window group, whose dead pages leave from the front."""

    def __init__(self, spec: PageGroup, num_pages: int, table):
        self.name, self.window = spec.name, spec.window
        self.layers = tuple(spec.layers)
        self.allocator = PageAllocator(num_pages)
        self.table = table
        self.pages: dict[int, list[int]] = {}
        self.first: dict[int, int] = {}
        self.released = 0  # pages freed behind the window, ever

    def window_span(self, page_size: int) -> int:
        """The most pages that hold a window's positions, a page's edge
        anywhere: ``ceil(window / page_size) + 1``."""
        return -(-self.window // page_size) + 1

    def map(self, slot: int, pages: list, first: int = 0) -> None:
        self.pages[slot], self.first[slot] = pages, first
        self.table[slot, :] = NULL_PAGE
        self.table[slot, first:first + len(pages)] = pages

    def drop(self, slot: int) -> None:
        pages = self.pages.pop(slot, None)
        self.first.pop(slot, None)
        if pages:
            self.allocator.free(pages)
        self.table[slot, :] = NULL_PAGE


class PagedKVCache:
    """Host-side manager of the pool: slot admission (with prefix-cache
    matching), on-demand growth during decode, release. The engine owns
    moving ``self.pools`` through jit; the cache's own jitted helpers
    (swap gather/scatter, COW page copy) rebind them in place."""

    def __init__(self, cfg: PagedCacheConfig):
        if cfg.kv_dtype not in ("float32", "int8"):
            raise ValueError(f"kv_dtype {cfg.kv_dtype!r} not in "
                             f"('float32', 'int8')")
        if cfg.host_tier_bytes < 0:
            raise ValueError(f"host_tier_bytes {cfg.host_tier_bytes} < 0")
        if cfg.host_tier_bytes and not cfg.enable_prefix_caching:
            raise ValueError(
                "host_tier_bytes spills INDEXED prefix pages — it needs "
                "enable_prefix_caching=True (nothing would ever spill)")
        if cfg.slot_leaf_keys and cfg.enable_prefix_caching:
            raise ValueError(
                f"a pool with per-slot leaves {cfg.slot_leaf_keys} cannot "
                "share pages by prefix: no snapshot of the slot's state at "
                "a page boundary goes with a shared page")
        if cfg.slot_leaf_keys and cfg.tp is not None:
            raise ValueError("per-slot leaves have no placement under "
                             "tensor parallelism")
        specs = cfg.page_groups
        if len(specs) > 1 and cfg.enable_prefix_caching:
            raise ValueError(
                f"a pool of {len(specs)} page groups cannot share pages by "
                "prefix: the index names one page a block, and a window "
                "group's page of a shared prefix is freed behind the window "
                "of whoever holds it")
        if len(specs) > 1 and cfg.tp is not None:
            raise ValueError("page groups have no placement under tensor "
                             "parallelism")
        # a pool of per-slot leaves alone keeps nothing a token: no page
        # exists on the device and no table is read. The pages below are
        # the host's bookkeeping only, as many as every slot's longest
        # context takes, so admission is bounded by the slots and the
        # context limit alone
        self.paged = bool(cfg.pool_leaf_keys)
        if not self.paged:
            if len(specs) > 1:
                raise ValueError("page groups of a pool with no paged leaf")
            cfg = dataclasses.replace(
                cfg, num_pages=_RESERVED_PAGES
                + cfg.max_batch * cfg.pages_per_seq)
        self.cfg = cfg
        # one table a group, stacked: a launch uploads them as one array.
        # The FIRST group's allocator, table and slot pages are the
        # cache's own (``allocator``, ``page_table``, ``_slot_pages``): a
        # model of one group is what it always was, and the prefix index,
        # copy-on-write and the spill tier below are the first group's
        self._tables = np.full(
            (len(specs), cfg.max_batch, cfg.pages_per_seq), NULL_PAGE,
            np.int32)
        self.groups = [_Group(g, n, self._tables[i]) for i, (g, n)
                       in enumerate(zip(specs, cfg.group_num_pages))]
        self._rest = self.groups[1:]
        self.has_windows = any(g.window is not None for g in self._rest)
        self.allocator = self.groups[0].allocator
        # under tensor parallelism the pools' heads axis is sharded across
        # the mesh; the page ids in the (host-side) table stay logical, so
        # every allocator/prefix-cache/COW decision below is
        # sharding-agnostic
        self.pools = init_pools(cfg)
        self.page_table = self.groups[0].table
        self._slot_pages = self.groups[0].pages
        # slot -> the position of its next query, as release_behind was
        # last told (window groups only)
        self._slot_pos: dict[int, int] = {}
        # ---- prefix cache: exact token-chain -> full immutable page.
        # Keys are LINKED, not flat: (parent_serial, block_tokens), where
        # parent_serial is the registration serial of the page holding the
        # previous block (0 for the chain head). Serials are NEVER reused,
        # so a key transitively pins the exact full prefix in O(page_size)
        # memory per page — flat full-prefix keys would be quadratic in
        # chain length — while staying collision-free: a recycled PAGE ID
        # can collide, a retired serial cannot (a stale child entry whose
        # parent was evicted is simply unreachable until its own page is
        # evicted and purged).
        self._key_to_page: dict[tuple, int] = {}
        self._page_key: dict[int, tuple] = {}
        self._page_serial: dict[int, int] = {}  # registered page -> serial
        self._serials = itertools.count(1)      # 0 = chain-head parent
        self._slot_cached: dict[int, int] = {}  # slot -> cached prompt tokens
        self.cow_copies = 0   # shared pages privatized before a write
        self.evictions = 0    # reclaimable pages purged under pressure
        # ---- host spill tier: evicted prefix pages' second life
        self.host_tier = (HostTier(cfg.host_tier_bytes)
                          if cfg.host_tier_bytes else None)
        self.spills = 0        # pages spilled to the host tier
        self.restores = 0      # pages restored from the host tier
        self.host_tier_hits = 0  # admissions that restored >= 1 page
        self._slot_restored: dict[int, int] = {}  # slot -> restored pages
        # engine-installed probe: restore_fault(rid) -> True fails the
        # restore (the ``restore_fail`` fault point); None costs one
        # attribute check per admission that would restore
        self.restore_fault = None
        # the span source (the engine installs its own): the copy-on-write
        # page copy is the serve.cow_copy span of the engine's step. A
        # cache with no engine holds a disabled one.
        self.spans = PhaseAccumulator()
        self._build_jits()

    @property
    def compile_counts(self) -> dict:
        """Trace counts per cache-owned jitted step, dict-shaped (the PR 3
        pinned surface), read off the CompileGuards: the fixed swap/COW
        shapes mean each compiles exactly once for the cache's lifetime."""
        return {k: g.traces for k, g in self.guards.items()}

    @property
    def tables(self) -> np.ndarray:
        """What a launch uploads: the one group's ``[max_batch,
        pages_per_seq]`` table, or the groups' tables stacked ``[groups,
        max_batch, pages_per_seq]`` (layer ``i`` reads row
        ``cfg.group_of_layer[i]``)."""
        return self.page_table if not self._rest else self._tables

    def _stack_rows(self, keys) -> list:
        """For each layer ``{leaf: its row}`` among the layers that keep
        that leaf of ``keys``: where a layer's leaf stands in the array a
        mover stacks over those layers."""
        count = dict.fromkeys(keys, 0)
        rows = []
        for leaves in self.cfg.layer_leaves:
            names = {lf.name for lf in leaves}
            rows.append({k: count[k] for k in keys if k in names})
            for k in rows[-1]:
                count[k] += 1
        return rows

    def _build_jits(self) -> None:
        import jax.numpy as jnp

        from ..analysis.tracecheck import CompileGuard

        quantized = self.cfg.quantized
        keys = self.cfg.pool_leaf_keys
        # the movers move PAGED leaves, of the layers that have them: a
        # stacked array's row ``rows[i][k]`` is layer i's leaf k. Where
        # every layer keeps every leaf (GPT, a latent model) row i is
        # layer i and these are the programs they always were; a layer's
        # per-slot leaves pass through untouched
        rows = self._stack_rows(keys)
        # the page ids a mover is given: one vector, or with several
        # groups one a group, stacked; layer i moves its own group's
        gof = self.cfg.group_of_layer
        at = (lambda idx, i: idx) if not self._rest \
            else (lambda idx, i: idx[gof[i]])

        def gather_of(keys, at=at):
            # one stacked array a leaf of ``keys``, over the layers that
            # keep it. Index each layer BEFORE stacking: stacking whole
            # pools would materialize an O(pool) concatenate per swap event
            # — the exact cost this jit exists to avoid; this way only the
            # gathered rows are ever copied. Quantized pools move their raw
            # codes + the touched pages' scale rows — never dequantized, so
            # a round-trip is bit-exact.
            return lambda pools, idx: tuple(
                jnp.stack([pl[k][at(idx, i)] for i, pl in enumerate(pools)
                           if k in pl])
                for k in keys)

        def scatter_of(keys, rows, at=at):
            def scatter(pools, idx, *stacked):
                by_key = dict(zip(keys, stacked))
                return [dict(pl, **{k: pl[k].at[at(idx, i)].set(
                    by_key[k][r]) for k, r in row.items()})
                        for i, (pl, row) in enumerate(zip(pools, rows))]
            return scatter

        gather, scatter = gather_of(keys), scatter_of(keys, rows)

        def copy_page(pools, src, dst):
            return [dict(pl, **{k: pl[k].at[dst].set(pl[k][src])
                                for k in at})
                    for pl, at in zip(pools, rows)]

        # gather READS the pools — donation would delete the other
        # sequences' live KV; scatter and COW consume them: without
        # donation each .at[] write would copy the ENTIRE pool and hold
        # two pools live. Budget 1 each: the padded fixed shapes mean a
        # second trace is always a bug.
        if self.cfg.tp is not None:
            # per-shard data movement: each device gathers/scatters/copies
            # its own heads slice; the replicated page-index operands make
            # it collective-free (certified by the tp2_swap/cow hlocheck
            # registry steps)
            nl = self.cfg.num_layers
            gather = self.cfg.tp.wrap_cache(gather, "gather", nl,
                                            quantized=quantized)
            scatter = self.cfg.tp.wrap_cache(scatter, "scatter", nl,
                                             quantized=quantized)
            copy_page = self.cfg.tp.wrap_cache(copy_page, "copy", nl,
                                               quantized=quantized)
        strict = self.cfg.debug_checks
        self._gather_jit = CompileGuard(  # lint: disable=PT006
            gather, "swap_gather", budget=1, strict=strict)
        self._scatter_jit = CompileGuard(
            scatter, "swap_scatter", budget=1, strict=strict,
            donate_argnums=(0,))
        self._copy_jit = CompileGuard(
            copy_page, "cow_copy", budget=1, strict=strict,
            donate_argnums=(0,))
        self.guards = {"swap_gather": self._gather_jit,
                       "swap_scatter": self._scatter_jit,
                       "cow_copy": self._copy_jit}
        slot_keys = self.cfg.slot_leaf_keys
        if not slot_keys:
            return
        # what a SLOT keeps rides with its pages through a swap: row
        # ``slot`` of every per-slot leaf, stacked over the layers that
        # keep it (the slot is an operand, so one trace serves them all)
        whole = lambda idx, i: idx  # noqa: E731  (a slot, not page ids)
        state_gather = gather_of(slot_keys, whole)
        state_scatter = scatter_of(slot_keys, self._stack_rows(slot_keys),
                                   whole)
        self._state_gather_jit = CompileGuard(  # lint: disable=PT006
            state_gather, "state_gather", budget=1, strict=strict)
        self._state_scatter_jit = CompileGuard(
            state_scatter, "state_scatter", budget=1, strict=strict,
            donate_argnums=(0,))
        self.guards.update(state_gather=self._state_gather_jit,
                           state_scatter=self._state_scatter_jit)

    # ------------------------------------------------------------- sizing
    def pages_for(self, num_tokens: int) -> int:
        return max(1, math.ceil(num_tokens / self.cfg.page_size))

    def fits_ever(self, total_tokens: int,
                  prompt_tokens: int | None = None) -> bool:
        """Could a request of total_tokens run with the whole pool to
        itself? The admission-time check that makes preemption loops
        terminate (a lone running request can always grow). Reusable
        prefix pages don't relax this bound — they may be evicted before
        the request runs, so the guarantee must hold cold — but they don't
        tighten it either: every reclaimable page is evictable on demand,
        so the full ``usable_pages`` capacity always counts."""
        need = self.pages_for(total_tokens)

        def most(g: _Group) -> int:
            # a window group holds the whole prompt while it is prefilled
            # (the whole request's pages where the caller does not say how
            # much of it is prompt) and a window's pages after
            if g.window is None or prompt_tokens is None:
                return need
            # (a window's span and the page a step grows into)
            return min(need, max(self.pages_for(prompt_tokens),
                                 g.window_span(self.cfg.page_size) + 1))

        return (total_tokens <= self.cfg.max_tokens_per_seq
                and all(most(g) <= g.allocator.num_usable
                        for g in self.groups))

    # ----------------------------------------------------- prefix caching
    def _block_key(self, parent_serial: int, tokens, i: int) -> tuple:
        """Index key for block ``i`` of a token chain: (serial of the
        parent block's page, the block's exact tokens). Exact tuples (not
        hash digests) key the dict — a collision could silently splice
        another prompt's KV into a request, so exactness is a correctness
        requirement, not a nicety; the parent serial carries the rest of
        the prefix transitively."""
        return (parent_serial,
                _block_tokens(tokens, self.cfg.page_size, i))

    def match_prefix(self, tokens) -> list[int]:
        """Longest chain of cached FULL pages covering a prefix of
        ``tokens``, in page order. Whole-page granularity: a partial page
        can never be content-addressed (its key would be ambiguous about
        the tail)."""
        if not self.cfg.enable_prefix_caching:
            return []
        pages, parent = [], 0
        for i in range(len(tokens) // self.cfg.page_size):
            page = self._key_to_page.get(self._block_key(parent, tokens, i))
            if page is None:
                break
            pages.append(page)
            parent = self._page_serial[page]
        return pages

    def register_prefix(self, slot: int, tokens) -> int:
        """Index every full page of ``slot`` whose token block is covered by
        ``tokens`` (the KV actually resident — the engine passes the prompt
        after prefill and prompt+generated-with-KV at finish). First
        registration wins: an identical chain already indexed keeps its
        existing page. Returns the number of newly indexed pages."""
        if not self.cfg.enable_prefix_caching:
            return 0
        pages = self._slot_pages.get(slot)
        if not pages:
            return 0
        new, parent = 0, 0
        for i in range(min(len(pages), len(tokens) // self.cfg.page_size)):
            key = self._block_key(parent, tokens, i)
            existing = self._key_to_page.get(key)
            if existing is not None:
                parent = self._page_serial[existing]
                continue
            if pages[i] in self._page_key:
                # this page already anchors a DIFFERENT chain (e.g. it was
                # COW-sourced); without it the chain breaks — descendants
                # would need a parent serial no key can reach
                break
            serial = next(self._serials)
            self._key_to_page[key] = pages[i]
            self._page_key[pages[i]] = key
            self._page_serial[pages[i]] = serial
            if self.host_tier is not None:
                # a freshly prefilled page re-registering a key a spilled
                # page still holds (e.g. the same text regenerated) makes
                # the tier copy stale — the device index always wins
                self.host_tier.pop(key)
            parent = serial
            new += 1
        return new

    def cached_tokens(self, slot: int) -> int:
        """Prompt tokens slot ``slot`` reused from the prefix cache at
        admission (0 for a cold admission or a swap-restore)."""
        return self._slot_cached.get(slot, 0)

    def restored_pages(self, slot: int) -> int:
        """Host-tier pages restored into ``slot`` at its admission (0
        otherwise) — the scheduler stamps the ``restore`` trace event off
        this."""
        return self._slot_restored.get(slot, 0)

    def _match_host_tail(self, tokens, parent: int, start_block: int,
                         touch: bool = True) -> list[SpilledPage]:
        """Continue a device-index prefix chain into the host tier: the
        longest run of spilled pages extending block ``start_block`` of
        ``tokens`` from chain serial ``parent``. ``touch=False`` for
        read-only probes (no LRU reorder); the restore pops the entries
        only after the scatter lands."""
        if self.host_tier is None:
            return []
        out = []
        for i in range(start_block, len(tokens) // self.cfg.page_size):
            e = self.host_tier.get(self._block_key(parent, tokens, i),
                                   touch=touch)
            if e is None:
                break
            out.append(e)
            parent = e.serial
        return out

    def cached_prefix_tokens(self, tokens) -> int:
        """Tokens of ``tokens`` a fresh admission would serve from the
        prefix cache right now (whole-page device-index matches plus the
        host tier's continuation of the chain). A read-only probe — no
        refcounts move, no tier LRU reorder — used by the scheduler's
        degraded-mode preference for warm waiters."""
        pages = self.match_prefix(tokens)
        parent = self._page_serial[pages[-1]] if pages else 0
        spilled = self._match_host_tail(tokens, parent, len(pages),
                                        touch=False)
        return (len(pages) + len(spilled)) * self.cfg.page_size

    def gossip_digests(self) -> frozenset:
        """Chain digests for every prefix chain reachable from the root —
        device index plus the host tier's continuations — as a compact set
        the fleet router gossips instead of token content. A digest is
        included iff the whole chain up to it is resolvable, so counting
        leading ``prefix_digest`` elements in this set reproduces
        ``cached_prefix_tokens`` exactly (parity-pinned). Registration
        walks chains left-to-right, so a child's serial always exceeds its
        parent's — one serial-ordered pass resolves every node."""
        if not self.cfg.enable_prefix_caching:
            return frozenset()
        nodes = [(self._page_serial[page], key)
                 for key, page in self._key_to_page.items()]
        if self.host_tier is not None:
            nodes.extend((e.serial, key)
                         for key, e in self.host_tier._entries.items())
        by_serial = {0: DIGEST_SEED}  # serial -> chain digest
        for serial, (parent_serial, block) in sorted(nodes):
            parent = by_serial.get(parent_serial)
            if parent is None:
                continue  # ancestor purged: chain unreachable from root
            by_serial[serial] = _digest_step(parent, block)
        del by_serial[0]
        return frozenset(by_serial.values())

    def export_prefix_chain(self, tokens,
                            max_pages: int | None = None) -> list:
        """The longest resolvable prefix chain covering ``tokens`` as
        STANDALONE :class:`SpilledPage` copies — the payload of a
        cross-replica page fetch (serving/fleet.py encodes each through
        serving/wire.py). Device-index pages are gathered through the
        same jitted program spills use (chunked at ``pages_per_seq`` —
        an export can never retrigger a compile), then the host tier's
        continuation is copied as-is. Read-only: no refcounts move, no
        tier LRU reorder, no index change — the donor replica keeps
        serving exactly as before. Entries come back in chain order
        from the root."""
        import jax.numpy as jnp

        if self.cfg.slot_leaf_keys:
            raise ValueError(
                "a page of a pool with per-slot leaves "
                f"{self.cfg.slot_leaf_keys} cannot cross the wire: it "
                "would need the slot's state at its last token with it")
        if self._rest:
            raise ValueError(
                f"a page of a pool of {len(self.groups)} page groups cannot "
                "cross the wire: the index names the first group's pages, "
                "and a window group's may be gone")
        pages = self.match_prefix(tokens)
        parent = self._page_serial[pages[-1]] if pages else 0
        spilled = self._match_host_tail(tokens, parent, len(pages),
                                        touch=False)
        if max_pages is not None:
            pages = pages[:max_pages]
            spilled = spilled[:max(0, max_pages - len(pages))]
        out: list[SpilledPage] = []
        w = self.cfg.pages_per_seq
        for at in range(0, len(pages), w):
            chunk = pages[at:at + w]
            got = [np.asarray(a) for a in self._gather_jit(
                self.pools, jnp.asarray(self._padded_idx(chunk)))]
            for j, page in enumerate(chunk):
                out.append(SpilledPage(
                    key=self._page_key[page],
                    serial=self._page_serial[page],
                    **_by_field([a[:, j].copy() for a in got])))
        out.extend(SpilledPage(
            key=e.key, serial=e.serial,
            **_by_field([a.copy() for a in e.arrays]))
            for e in spilled)
        return out

    def import_spilled_chain(self, entries) -> int:
        """Adopt a peer's exported prefix chain into the LOCAL host
        tier — the receiving half of a cross-replica page fetch. Serial
        spaces are per-cache (``itertools.count(1)``), so peer serials
        are REMAPPED: entries are chain-walked from the root (arrival
        order is irrelevant — the wire may reorder frames), and each
        block either already exists locally — device index or tier,
        first-registration-wins, the peer copy is dropped — or is
        inserted under a FRESH local serial with its key re-parented
        onto the local chain. The next admission then restores these
        pages bit-exactly through the ordinary host-tier path (the
        tier IS the landing zone). Returns pages newly inserted."""
        if self.host_tier is None:
            raise ValueError(
                "import_spilled_chain needs the host tier "
                "(host_tier_bytes > 0) as its landing zone")
        want_dtype = np.dtype(self.cfg.layer_leaves[0][0].dtype)
        n_leaves = len(self.cfg.pool_leaf_keys)
        by_parent: dict[int, SpilledPage] = {}
        for e in entries:
            by_parent.setdefault(int(e.key[0]), e)
        new = 0
        src_parent = 0  # cursor in the PEER's serial space
        parent = 0      # the chain so far in the LOCAL serial space
        while src_parent in by_parent:
            e = by_parent.pop(src_parent)
            src_parent = int(e.serial)
            if e.k.dtype != want_dtype or len(e.arrays) != n_leaves:
                raise ValueError(
                    f"imported page dtype {e.k.dtype}/scales="
                    f"{e.k_scale is not None} does not match this "
                    f"pool (kv_dtype={self.cfg.kv_dtype!r}, leaves "
                    f"{self.cfg.pool_leaf_keys})")
            key = (parent, tuple(e.key[1]))
            page = self._key_to_page.get(key)
            if page is not None:
                parent = self._page_serial[page]
                continue
            held = self.host_tier.get(key, touch=False)
            if held is not None:
                parent = held.serial
                continue
            serial = next(self._serials)
            self.host_tier.put(SpilledPage(
                key=key, serial=serial,
                **_by_field([np.array(a, copy=True) for a in e.arrays])))
            if self.host_tier.get(key, touch=False) is None:
                break  # refused at the byte bound: descendants would
                # chain onto a parent the tier no longer holds
            parent = serial
            new += 1
        return new

    def _unregister(self, page: int) -> None:
        key = self._page_key.pop(page, None)
        if key is not None:
            self._key_to_page.pop(key, None)
            self._page_serial.pop(page, None)
            # descendants keyed on this page's retired serial are now
            # unreachable (serials never recur); they purge when their own
            # pages are evicted or re-registered

    def _spill_pages(self, pages: list[int]) -> None:
        """Copy the named (still-resident, refcount-0 indexed) pages into
        the host tier before they are reclaimed, keeping their index keys
        and chain serials. ONE batched jitted gather per ``pages_per_seq``
        chunk of the sweep — the same compiled program swap_out uses, so a
        spill can never retrigger a compile — not a per-page transfer."""
        import jax.numpy as jnp

        w = self.cfg.pages_per_seq
        for at in range(0, len(pages), w):
            chunk = pages[at:at + w]
            got = [np.asarray(a) for a in self._gather_jit(
                self.pools, jnp.asarray(self._padded_idx(chunk)))]
            for j, page in enumerate(chunk):
                self.host_tier.put(SpilledPage(
                    key=self._page_key[page],
                    serial=self._page_serial[page],
                    **_by_field([a[:, j].copy() for a in got])))
                self.spills += 1

    def _alloc_or_evict(self, n: int) -> list[int] | None:
        """Allocate n pages, LRU-evicting reclaimable cached pages when the
        free list alone can't cover it. Evicted pages are purged from the
        content index BEFORE they can be handed out again — a recycled page
        must never be reachable under its stale key. With the host tier
        enabled, the sweep's victims spill their bytes (and keys) there
        first — one batched gather, then the reclaims."""
        if n == 0:
            return []
        if self.allocator.num_free + self.allocator.num_reclaimable < n:
            return None  # doomed: keep the warm cache, change no state
        need = n - self.allocator.num_free
        if need > 0:
            if self.host_tier is not None:
                # reclaim_lru pops oldest-first — exactly this LRU prefix
                victims = list(itertools.islice(
                    self.allocator._cached, need))
                self._spill_pages(victims)
            for _ in range(need):
                page = self.allocator.reclaim_lru()
                self._unregister(page)
                self.evictions += 1
        return self.allocator.alloc(n)

    def _claim_shared(self, page: int) -> None:
        """Take a hold on a matched cache page: revive a reclaimable page
        at refcount 1, or bump a live page's count."""
        if self.allocator.refcount(page) == 0:
            self.allocator.take_cached(page)
        else:
            self.allocator.incref(page)

    def _release_pages(self, pages) -> None:
        """Drop this holder's reference on every page; indexed pages whose
        count reaches zero park in the reclaimable LRU pool (their KV stays
        valid for future hits), everything else returns to the free list."""
        for p in pages:
            self.allocator.decref(p, hold=p in self._page_key)

    def shared_page_count(self) -> int:
        """Pages currently mapped by more than one page table."""
        return sum(1 for c in self.allocator._ref.values() if c > 1)

    def _copy_page_bytes(self, src: int, dst: int) -> None:
        """Jitted donated single-page pool copy (the COW data move)."""
        import jax.numpy as jnp

        with self.spans.span("cow_copy", pages=1):
            self.pools = self._copy_jit(
                self.pools, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32))

    # ---------------------------------------------------------- admission
    def _restore_pages(self, entries: list[SpilledPage],
                       pages: list[int], rid=None) -> None:
        """Scatter host-tier entries into freshly allocated ``pages``
        (aligned lists) through the jitted donated swap scatter, chunked at
        ``pages_per_seq``, then re-register each page under its ORIGINAL
        key and serial — descendants of the chain, on device or still in
        the tier, stay reachable. The ``restore_fail`` fault point (and any
        real scatter error that didn't consume the pools) raises
        HostTierRestoreError AFTER dropping the stale tier entries; the
        caller undoes the admission."""
        import jax.numpy as jnp

        hook = self.restore_fault
        if hook is not None and hook(rid):
            for e in entries:
                self.host_tier.pop(e.key)
            raise HostTierRestoreError(
                f"restore_fail injected (rid {rid})")
        c = self.cfg
        w = c.pages_per_seq
        for at in range(0, len(entries), w):
            es = entries[at:at + w]
            # one [layers, w, ...] array a pool leaf, entry j in row j
            stacked = [np.zeros((c.num_layers, w) + a.shape[1:], a.dtype)
                       for a in es[0].arrays]
            for j, e in enumerate(es):
                for full, a in zip(stacked, e.arrays):
                    full[:, j] = a
            args = [jnp.asarray(self._padded_idx(pages[at:at + w]))] \
                + [jnp.asarray(a) for a in stacked]
            try:
                self.pools = self._scatter_jit(self.pools, *args)
            except Exception as err:  # noqa: BLE001 — isolate the restore
                if any(arr.is_deleted() for pl in self.pools
                       for arr in pl.values()):
                    raise  # donation consumed the pools: engine-fatal
                for e in entries:
                    self.host_tier.pop(e.key)
                raise HostTierRestoreError(
                    f"host-tier restore failed: "
                    f"{type(err).__name__}: {err}") from err
        for e, page in zip(entries, pages):
            self.host_tier.pop(e.key)
            self._key_to_page[e.key] = page
            self._page_key[page] = e.key
            self._page_serial[page] = e.serial
            self.restores += 1
        self.host_tier_hits += 1

    def admit(self, slot: int, num_tokens: int, tokens=None,
              rid=None) -> bool:
        """Allocate what a prompt of num_tokens needs and populate the
        slot's page-table row. When ``tokens`` is given and prefix caching
        is on, the longest indexed whole-page prefix is SHARED (refcount
        bump, no allocation) and only the remainder is allocated — the
        engine then prefills only the uncached tail. False (no state
        change) when even LRU eviction can't cover the private remainder.

        A fully cached prompt still needs its last token recomputed (the
        first output token is sampled from its logits), so the cached span
        is capped at ``num_tokens - 1`` and the page holding that last
        token must be writable: copy-on-write when any OTHER holder shares
        it, in place when this request is the last (only) holder. The
        in-place path keeps the page's index entry because the one write
        that reaches it reproduces the exact bytes already resident (same
        tokens over the same exact-zero-masked prefix, deterministic
        kernels). The COW page is reserved inside the same all-or-nothing
        allocation as the private remainder.

        Host tier: the device-index match is extended into the spill tier
        — matching spilled pages are restored (allocated as private pages,
        scattered back, re-registered under their original keys/serials)
        and count toward ``cached`` exactly like device hits. A failed
        restore (``restore_fail`` injection or a real scatter error) undoes
        the whole admission and raises HostTierRestoreError — the engine
        retires the request FAILED."""
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already admitted")
        total = self.pages_for(num_tokens)
        # the groups behind the first: a prompt's pages in each, all or
        # nothing (a window group holds the whole prompt until its prefill
        # is launched: release_behind then frees what lies behind)
        if not self._map_rest(slot, [(total, 0)] * len(self._rest)):
            return False
        shared: list[int] = []
        spilled: list[SpilledPage] = []
        if tokens is not None and self.cfg.enable_prefix_caching:
            shared = self.match_prefix(tokens[:num_tokens])
            parent = self._page_serial[shared[-1]] if shared else 0
            spilled = self._match_host_tail(tokens[:num_tokens], parent,
                                            len(shared))
            for p in shared:
                self._claim_shared(p)
        cached = (len(shared) + len(spilled)) * self.cfg.page_size
        full_hit = bool(shared or spilled) and cached >= num_tokens
        if full_hit:
            cached = num_tokens - 1
        # refcount includes this request's own claim: > 1 = other holders.
        # A restored page is always this request's private copy, so a full
        # hit whose LAST page comes from the tier never needs COW.
        need_cow = full_hit and not spilled \
            and self.allocator.refcount(shared[-1]) > 1
        # the spilled pages' slots are part of the private remainder: they
        # are allocated here and filled by the restore scatter below
        private = self._alloc_or_evict(total - len(shared)
                                       + (1 if need_cow else 0))
        if private is None:
            self._release_pages(shared)
            self._drop_rest(slot)
            return False
        if spilled:
            try:
                self._restore_pages(spilled, private[:len(spilled)], rid)
            except HostTierRestoreError:
                for p in private:  # fresh refcount-1 pages: free them
                    self.allocator.decref(p)
                self._release_pages(shared)
                raise
        if need_cow:
            dst = private.pop()
            src = shared[-1]
            self._copy_page_bytes(src, dst)
            self.allocator.decref(src, hold=src in self._page_key)
            shared[-1] = dst
            self.cow_copies += 1
        pages = shared + private
        self._slot_pages[slot] = pages
        self._slot_cached[slot] = cached
        if spilled:
            self._slot_restored[slot] = len(spilled)
        self.page_table[slot, :] = NULL_PAGE
        self.page_table[slot, :len(pages)] = pages
        return True

    def shrink(self, slot: int, num_tokens: int) -> int:
        """Return the slot's over-allocated TAIL pages to the allocator —
        the speculative-decoding rewind: a verify step reserves capacity
        for ``ctx + depth + 1`` tokens up front (scheduler
        ``decode_reserve``), and once the in-jit accept count is fetched,
        the pages past the accepted span recycle here. Only pages this
        slot privately over-allocated are popped: a shared (refcount > 1)
        or content-indexed tail page is never speculative headroom, so
        the walk stops there. Returns the number of pages freed; the
        rejected tokens' KV bytes inside the kept pages need no scrub —
        the ragged exact-zero mask never attends past ``ctx_lens`` and
        the next write overwrites them."""
        pages = self._slot_pages.get(slot)
        if not pages:
            return 0
        keep = self.pages_for(num_tokens)
        freed = 0
        while len(pages) > keep:
            page = pages[-1]
            if self.allocator.refcount(page) != 1 or page in self._page_key:
                break
            pages.pop()
            self.page_table[slot, len(pages)] = NULL_PAGE
            self.allocator.decref(page)
            freed += 1
        for g in self._rest:
            mine = g.pages.get(slot, [])
            while mine and g.first[slot] + len(mine) > keep:
                g.table[slot, g.first[slot] + len(mine) - 1] = NULL_PAGE
                g.allocator.decref(mine.pop())
                freed += 1
        return freed

    def grow(self, slot: int, num_tokens: int) -> bool:
        """Ensure the slot can hold num_tokens, allocating pages on demand
        (the continuous-batching decode step grows one token at a time),
        evicting reclaimable cached pages first. False when the pool is
        truly exhausted — the scheduler must preempt."""
        pages = self._slot_pages[slot]
        need = self.pages_for(num_tokens)
        if need > self.cfg.pages_per_seq:
            raise ValueError(
                f"slot {slot}: {num_tokens} tokens need {need} pages > "
                f"pages_per_seq={self.cfg.pages_per_seq}")
        while len(pages) < need:
            got = self._alloc_or_evict(1)
            if got is None:
                return False
            self.page_table[slot, len(pages)] = got[0]
            pages.extend(got)
        for g in self._rest:
            mine = g.pages[slot]
            while g.first[slot] + len(mine) < need:
                got = g.allocator.alloc(1)
                if got is None:
                    return False
                g.table[slot, g.first[slot] + len(mine)] = got[0]
                mine.extend(got)
        return True

    # ------------------------------------------------ groups behind the first
    def _map_rest(self, slot: int, wants: list) -> bool:
        """``(pages, first column)`` a group behind the first, allocated
        and mapped into the slot's rows; False (and no state change) when
        any group cannot give its share."""
        got = []
        for g, (n, _) in zip(self._rest, wants):
            pages = g.allocator.alloc(n)
            if pages is None:
                for h, ps in got:
                    h.allocator.free(ps)
                return False
            got.append((g, pages))
        for (g, pages), (_, first) in zip(got, wants):
            g.map(slot, pages, first)
        return True

    def _drop_rest(self, slot: int) -> None:
        for g in self._rest:
            g.drop(slot)
        self._slot_pos.pop(slot, None)

    def release_behind(self, slot: int, next_pos: int) -> int:
        """Return to their allocators the slot's window-group pages that
        no later query can see. The slot's next query enters at position
        ``next_pos`` (every later one further on) and sees position ``j``
        while ``next_pos - j < window``: a page whose LAST position is at
        or behind ``next_pos - window`` is dead. Its table column reads the
        null page from now on; the kernels start past it and mask behind
        the window exactly, so what the page holds next, for whom, changes
        nothing for this slot. The engine calls this once a launch has
        been dispatched with the table that still named the page (the
        device runs launches in order). Returns the pages freed."""
        ps, freed = self.cfg.page_size, 0
        for g in self._rest:
            mine = g.pages.get(slot)
            if g.window is None or not mine:
                continue
            # pages 0 .. dead - 1 end at or behind next_pos - window
            dead = max(0, (next_pos - g.window + 1) // ps)
            k = min(dead - g.first[slot], len(mine))
            if k <= 0:
                continue
            g.allocator.free(mine[:k])
            del mine[:k]
            g.table[slot, g.first[slot]:g.first[slot] + k] = NULL_PAGE
            g.first[slot] += k
            g.released += k
            freed += k
        if self._rest:
            self._slot_pos[slot] = next_pos
        return freed

    def window_pages(self, slot: int) -> dict:
        """Pages the slot holds in each window group, by group name."""
        return {g.name: len(g.pages.get(slot, ())) for g in self._rest
                if g.window is not None}

    def residency(self) -> tuple[int, int]:
        """``(resident, one lifetime)`` in page-layers: the pages in use
        in each group times the group's layers, and what the same contexts
        would hold under ONE table and lifetime (the first group's pages,
        which are every context's, times every layer that pages)."""
        resident = sum(g.allocator.pages_in_use * len(g.layers)
                       for g in self.groups)
        return resident, self.allocator.pages_in_use * sum(
            len(g.layers) for g in self.groups)

    def pages_held(self) -> tuple[int, int]:
        """``(in use, usable)`` over every group: the pages that live slots
        (and shared prefixes they hold) map, and the pool's usable pages."""
        return (sum(g.allocator.pages_in_use for g in self.groups),
                sum(g.allocator.num_usable for g in self.groups))

    # --------------------------------------------------------------- swap
    def _padded_idx(self, pages) -> np.ndarray:
        """Page ids padded to the fixed ``pages_per_seq`` width with the
        null page, so the swap jits never see a new shape (compile-once)."""
        idx = np.full(self.cfg.pages_per_seq, NULL_PAGE, np.int32)
        idx[:len(pages)] = pages
        return idx

    def _swap_idx(self, slot: int, pages) -> np.ndarray:
        """What a swap's mover is given: the first group's padded page
        ids, or with several groups each group's, stacked."""
        idx = self._padded_idx(pages)
        if not self._rest:
            return idx
        return np.stack([idx] + [self._padded_idx(g.pages[slot])
                                 for g in self._rest])

    def swap_out(self, slot: int) -> SwapHandle:
        """Copy the slot's pages to host memory and drop its holds. One
        jitted gather over the layer-stacked pools replaces the old
        per-layer host loop (O(layers) device round-trips and a full-pool
        functional copy per layer); shared pages are copied too — the
        restore owns private pages — but their device copies survive for
        the other holders."""
        pages = self._slot_pages.get(slot)
        if not pages:
            raise ValueError(f"slot {slot} has no pages to swap out")
        import jax.numpy as jnp

        rest = tuple((len(g.pages[slot]), g.first[slot])
                     for g in self._rest)
        n = max([len(pages)] + [k for k, _ in rest])
        got = () if not self.paged else self._gather_jit(
            self.pools, jnp.asarray(self._swap_idx(slot, pages)))
        state = ()
        if self.cfg.slot_leaf_keys:
            state = tuple(np.asarray(a) for a in self._state_gather_jit(
                self.pools, jnp.asarray(slot, jnp.int32)))
        handle = SwapHandle(n_pages=len(pages), state=state, rest=rest,
                            **{"k": None, **_by_field(
                                [np.asarray(a)[:, :n].copy() for a in got])})
        self.release(slot)
        return handle

    def swap_in(self, slot: int, handle: SwapHandle) -> bool:
        """Reallocate handle.n_pages pages for the slot and restore the
        swapped KV into them through the jitted donated scatter. False (no
        state change) when even eviction can't cover the handle — the
        scheduler keeps the request queued. Pool shapes never change, so
        swap/restore can never retrigger a compile of the serving steps."""
        import jax.numpy as jnp

        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} already admitted")
        if len(handle.rest) != len(self._rest):
            raise ValueError("the handle is of a pool with other page "
                             "groups")
        if not self._map_rest(slot, list(handle.rest)):
            return False
        pages = self._alloc_or_evict(handle.n_pages)
        if pages is None:
            self._drop_rest(slot)
            return False
        w = self.cfg.pages_per_seq
        args = [jnp.asarray(self._swap_idx(slot, pages))]
        for a in handle.arrays:
            full = np.zeros((a.shape[0], w) + a.shape[2:], a.dtype)
            full[:, :a.shape[1]] = a
            args.append(jnp.asarray(full))
        if self.paged:
            # pad rows scatter zeros into the null page — never read
            # unmasked
            self.pools = self._scatter_jit(self.pools, *args)
        if handle.state:
            # the slot may be another than the one swapped out: the state
            # goes where the pages' table row now is
            self.pools = self._state_scatter_jit(
                self.pools, jnp.asarray(slot, jnp.int32),
                *(jnp.asarray(a) for a in handle.state))
        self._slot_pages[slot] = pages
        self.page_table[slot, :] = NULL_PAGE
        self.page_table[slot, :len(pages)] = pages
        return True

    # ------------------------------------------------------------ release
    def release(self, slot: int) -> None:
        pages = self._slot_pages.pop(slot, None)
        self._slot_cached.pop(slot, None)
        self._slot_restored.pop(slot, None)
        if pages:
            self._release_pages(pages)
        self.page_table[slot, :] = NULL_PAGE
        self._drop_rest(slot)

    def utilization(self) -> float:
        """The fullest group's share in use."""
        return max(g.allocator.pages_in_use / max(1, g.allocator.num_usable)
                   for g in self.groups)

    def stats(self) -> dict:
        """One consistent host-side reading of the pool's observable state
        — the shared source for the serving gauges (metrics.on_state) and
        the obs step-timeline records, so the two surfaces can never
        disagree about page pressure within a step."""
        a = self.allocator
        t = self.host_tier
        by_group = {} if not self._rest else {"groups": {
            g.name: {"pages_in_use": g.allocator.pages_in_use,
                     "free_pages": g.allocator.num_free,
                     "usable_pages": g.allocator.num_usable,
                     "layers": len(g.layers), "window": g.window,
                     "window_pages_released": g.released}
            for g in self.groups}}
        # the first group's, as ever: its pages are every context's
        return {**by_group,
                "pages_in_use": a.pages_in_use,
                "free_pages": a.num_free,
                "reclaimable_pages": a.num_reclaimable,
                "usable_pages": self.cfg.usable_pages,
                "shared_pages": self.shared_page_count(),
                "cow_copies": self.cow_copies,
                "evictions": self.evictions,
                "host_tier_pages": len(t) if t is not None else 0,
                "host_tier_bytes": t.bytes if t is not None else 0,
                "host_tier_hits": self.host_tier_hits,
                "host_tier_spills": self.spills,
                "host_tier_restores": self.restores,
                # what the slots keep beside their pages (0 a slot for a
                # pool of pages alone)
                "slots_live": len(self._slot_pages),
                "state_bytes_per_slot": self.cfg.state_bytes_per_slot}

    # --------------------------------------------------------- invariants
    def _check_group(self, g: _Group) -> None:
        """A group behind the first: no page in two live slots, none both
        free and mapped, the live slots those of the first group, and in a
        window group no page a slot's next query cannot see, nor more
        behind that query than a window takes."""
        a, ps = g.allocator, self.cfg.page_size
        free, live = set(a._free), set(a._ref)
        assert not a._cached, f"group {g.name} parks no page"
        assert not (free & live), f"group {g.name}: a page free and live"
        assert len(free) + len(live) == a.num_usable, \
            f"group {g.name}: every usable page is free or live"
        held = list(itertools.chain.from_iterable(g.pages.values()))
        assert len(held) == len(set(held)), \
            f"group {g.name}: a page in two live slots (or twice in one)"
        assert set(held) == live, \
            f"group {g.name}: the live pages are the slots' pages"
        assert set(g.pages) == set(self._slot_pages), \
            f"group {g.name}: its live slots are not the first group's"
        if g.window is None:
            return
        most = g.window_span(ps)
        for slot, pages in g.pages.items():
            pos = self._slot_pos.get(slot)
            if pos is None:
                continue        # admitted, nothing launched yet
            first = g.first[slot]
            assert (first + 1) * ps - 1 > pos - g.window or not pages, \
                f"group {g.name}: slot {slot} holds a page behind the " \
                f"window of its next query at {pos}"
            behind = min(len(pages), pos // ps + 1 - first)
            assert behind <= most, \
                f"group {g.name}: slot {slot} holds {behind} pages up to " \
                f"position {pos}, a window of {g.window} takes {most}"

    def check_invariants(self) -> None:
        """Structural invariants the test suite sweeps after every
        scenario; raises AssertionError with the violated relation."""
        for g in self._rest:
            self._check_group(g)
        a = self.allocator
        free = set(a._free)
        live = set(a._ref)
        parked = set(a._cached)
        assert not (free & live) and not (free & parked) \
            and not (live & parked), "page states must be disjoint"
        assert len(free) + len(live) + len(parked) == a.num_usable, \
            "every usable page is exactly one of free/live/reclaimable"
        assert all(c >= 1 for c in a._ref.values()), "live refcounts >= 1"
        indexed = set(self._page_key)
        assert parked <= indexed, "reclaimable pages must stay indexed"
        assert not (free & indexed), \
            "a free page reachable through the prefix index would serve " \
            "stale KV to its next matcher"
        assert {p for k, p in self._key_to_page.items()} == indexed
        assert set(self._page_serial) == indexed, \
            "every indexed page carries exactly one chain serial"
        held = list(itertools.chain.from_iterable(self._slot_pages.values()))
        from collections import Counter

        holds = Counter(held)
        assert all(holds[p] <= a.refcount(p) for p in holds), \
            "a page table may never hold more references than its refcount"
        assert all(0 <= s < self.cfg.max_batch for s in self._slot_pages), \
            "a live slot is a row of the per-slot leaves"
        for pl, leaves in zip(self.pools, self.cfg.layer_leaves):
            for lf in leaves:
                if lf.per_slot:
                    assert pl[lf.name].shape == (self.cfg.max_batch,) \
                        + tuple(lf.shape), \
                        f"per-slot leaf {lf.name} is not [max_batch, ...]"
        for g in self.groups:
            for slot, pages in g.pages.items():
                first = g.first.get(slot, 0)
                row = g.table[slot]
                assert list(row[first:first + len(pages)]) == list(pages) \
                    and not row[:first].any() \
                    and not row[first + len(pages):].any(), \
                    f"group {g.name}: slot {slot}'s table row is not its " \
                    "pages at their columns and the null page elsewhere"
        if self.host_tier is not None:
            t = self.host_tier
            assert t.bytes == sum(e.nbytes for e in t._entries.values()), \
                "host-tier byte accounting must match its entries"
            assert t.bytes <= t.max_bytes, \
                "host tier exceeded its declared byte bound"
            assert not (set(t._entries) & set(self._key_to_page)), \
                "a content key reachable both on device and in the host " \
                "tier would make the tier copy silently stale"

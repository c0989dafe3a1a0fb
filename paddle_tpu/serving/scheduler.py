"""Admission + continuous batching scheduler (host side).

Policy, in the vLLM shape: FIFO admission with head-of-line order (a request
is only admitted when a decode slot AND its prompt's pages are available, and
never out of arrival order); one decode step serves every running slot; when
the pool runs dry mid-decode a running request is preempted — youngest first,
but requests that were prefilled (or swap-resumed) this very step and have
not decoded yet are spared while any seasoned victim exists, so admission
work is never thrown away before it produced a single decode.

Two preemption modes (``preemption_mode``):

- ``recompute`` (vLLM RECOMPUTE): pages freed, generated tokens dropped, the
  request requeues at the FRONT and replays from prefill. Deterministic for
  greedy AND sampling: the engine derives PRNG keys from (engine seed, rid,
  token index), so a recomputed request reproduces its original tokens
  exactly — recomputation never resamples.
- ``swap``: pages are copied to host memory (kv_cache.SwapHandle) and the
  request resumes later with its generated tokens intact — no decode work is
  lost, at the cost of host RAM and the restore copy.

Backpressure: the waiting queue is bounded by ``max_waiting`` (0 =
unbounded). A full queue either rejects the newcomer (``shed_policy=
"reject"`` raises :class:`EngineOverloaded`) or sheds the longest-waiting
request (``"shed-oldest"``), which is returned to the caller marked SHED.
Preemption requeues bypass the bound AND are never shed — a preempted
request was already admitted once and must not be lost to its own
eviction; a full queue holding only preemption victims rejects the
newcomer even under shed-oldest.

Prefix caching changes the ACCOUNTING, not the policy: admission and
preemption are costed in unique pages. A prompt's cached whole-page prefix
is mapped by refcount bump (free to admit), a preemption victim only
returns its private pages to the pool (shared pages keep their other
holders' refcounts and stay resident), and the cache LRU-evicts
refcount-0 reusable pages before any allocation is allowed to fail.

Admission-time validation guarantees every accepted request can finish with
the pool to itself — the bound is checked COLD (reusable prefix pages may
be evicted before the request runs), so the preempt-retry loop always
terminates even when every cached page is gone.

Chunked prefill (``ServingConfig(chunk_size=)``) adds one state between
admission and decode: a PREFILLING request holds its slot and pages but is
still streaming its prompt through the prefill step, ``chunk_size`` tokens
per engine step. The scheduler treats it like RUNNING everywhere
(eviction, deadlines, preemption); ``Request.prefilled_tokens`` tracks the
progress — it survives a swap preemption (the swapped pages hold exactly
those tokens' KV) and resets with a recompute preemption. Under SLO
degradation the engine passes ``admit(prefer_cached=True)``, which relaxes
strict FIFO to prefer waiters with warm prefix-cache hits (their uncached
tail is cheap); preemption victims still always go first.
"""
from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from .kv_cache import HostTierRestoreError, PagedKVCache

WAITING, RUNNING, FINISHED = "waiting", "running", "finished"
CANCELLED, FAILED, EXPIRED, SHED = "cancelled", "failed", "expired", "shed"
# chunked prefill: admitted (slot + pages held) but still streaming its
# prompt through the prefill step chunk_size tokens per step — not yet
# decoding. Treated like RUNNING for eviction/deadlines/preemption.
PREFILLING = "prefilling"

_rid_counter = itertools.count()


class EngineOverloaded(RuntimeError):
    """Admission refused: the bounded waiting queue is full and the shed
    policy is "reject". The caller should back off and retry."""


@dataclass(eq=False)  # identity semantics: requests are entities, and the
class Request:        # generated dataclass __eq__ chokes on ndarray fields
    prompt: np.ndarray  # [prompt_len] int
    max_new_tokens: int
    rid: int = field(default_factory=lambda: next(_rid_counter))
    state: str = WAITING
    slot: int | None = None
    generated: list = field(default_factory=list)
    preemptions: int = 0
    admit_seq: int = -1  # admission order stamp (preemption victim = max)
    deadline: float | None = None  # absolute engine-clock time; None = never
    error: BaseException | None = None  # recorded when state == FAILED
    swap: object | None = None  # kv_cache.SwapHandle while swapped out
    fresh: bool = False  # prefilled/swap-resumed this step, no decode yet
    cached_tokens: int = 0  # prompt tokens served from the prefix cache
    prefilled_tokens: int = 0  # prompt tokens with KV resident (chunked
    # prefill progress; includes the cached prefix). Survives swap
    # preemption — the restored pages hold exactly these tokens — and
    # resets with a recompute preemption, whose pages are gone.
    prefix_hit_tokens: int = 0  # the prefix-cache hit width at this
    # prefill attempt's START — unlike cached_tokens (which a swap
    # restore zeroes: restored pages are not an admission-time hit), it
    # survives swap so the completion-time hit/miss accounting still
    # credits the tokens the cache genuinely served.
    resumed_from_swap: bool = False  # set by admit()'s swap-restore path,
    # consumed (cleared) by the engine when it stamps swap_in/resumed
    tenant: str = "default"  # the request's SLO/traffic class (obs/
    # tenant.py) — observe-only: admission and scheduling never read it
    # (weighted per-tenant admission belongs to the fleet router), it
    # only labels the goodput ledger, journey, and latency families
    tokens_emitted: int = 0  # tokens this request EVER emitted, incl.
    # tokens a recompute preemption dropped and replayed — the ledger
    # accrues this at retirement so per-tenant goodput+badput token
    # totals reconcile exactly with serving_tokens_total (which also
    # counts re-emissions); len(generated) is the client-visible count
    tokens_in_flight: int = 0  # tokens computed for this request (by its
    # completed prefill or by a decode) and not fetched yet: 1 from one
    # step's decode phase to the next (the engine launches decode k+1
    # before it fetches decode k, and the decode behind a prefill before
    # it fetches the prefill's first token; 2 only in between). Such a
    # token exists on the device only; the next launch consumes it there

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def tokens_resident(self) -> int:
        """Tokens whose KV lives in the cache once the next decode has
        run: prompt + generated + the one in flight (each generated
        token's KV is written by the decode step that consumes it)."""
        return self.prompt_len + len(self.generated) + self.tokens_in_flight

    @property
    def all_launched(self) -> bool:
        """Its last token is emitted or in flight: finish by length is
        known before the fetch, so no further decode is launched for it
        and it retires when the token in flight comes back."""
        return len(self.generated) + self.tokens_in_flight \
            >= self.max_new_tokens

    @property
    def done(self) -> bool:
        return self.state == FINISHED

    def output(self) -> np.ndarray:
        return np.concatenate(
            [np.asarray(self.prompt),
             np.asarray(self.generated, dtype=np.asarray(self.prompt).dtype)])


class Scheduler:
    def __init__(self, cache: PagedKVCache, max_batch: int,
                 max_waiting: int = 0, shed_policy: str = "reject",
                 preemption_mode: str = "recompute", tracer=None):
        if shed_policy not in ("reject", "shed-oldest"):
            raise ValueError(f"shed_policy {shed_policy!r} not in "
                             f"('reject', 'shed-oldest')")
        if preemption_mode not in ("recompute", "swap"):
            raise ValueError(f"preemption_mode {preemption_mode!r} not in "
                             f"('recompute', 'swap')")
        self.cache = cache
        self.max_batch = max_batch
        self.max_waiting = max_waiting
        self.shed_policy = shed_policy
        self.preemption_mode = preemption_mode
        # the engine's obs.trace.Tracer (or None, costing one attribute
        # check per event site): the scheduler stamps the lifecycle
        # transitions it owns — admitted, preempted, swap_out
        self._tracer = tracer
        self.waiting: deque[Request] = deque()
        self.running: dict[int, Request] = {}  # slot -> Request
        self._free_slots = list(range(max_batch - 1, -1, -1))  # pop() -> 0,1,..
        self._admit_seq = itertools.count()
        self.preemption_count = 0
        # extra per-slot token capacity every decode step must hold BEYOND
        # tokens_resident — the speculative-decoding engine sets this to
        # its depth K (a verify step writes KV at ctx .. ctx + K before
        # the accept count is known; rejected tokens' pages shrink back).
        # 0 = plain decode, byte-identical accounting to the pre-spec
        # engine.
        self.decode_reserve = 0
        # called before a victim is picked; True when it changed what is
        # running (the engine drains its decode in flight here, so that a
        # victim is chosen and preempted with every token it has on the
        # host) and the page demand has to be read again
        self.before_preempt = None
        self._head_skips = 0  # prefer_cached fairness counter
        # (request, error) pairs whose host-tier restore failed mid-admit:
        # the admission was undone (pool state = pre-admit), the request
        # still sits in ``waiting`` — the engine drains this right after
        # admit() and retires each FAILED
        self.restore_failures: list[tuple[Request, Exception]] = []

    # ------------------------------------------------------------ admission
    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def all_done(self) -> bool:
        return not self.waiting and not self.running

    @property
    def inflight_waiting(self) -> int:
        """Preempted (in-flight) requests sitting in the waiting queue —
        work a paused drain must still finish."""
        return sum(r.preemptions > 0 for r in self.waiting)

    def add(self, req: Request) -> Request | None:
        """Queue a request. Returns the request this admission shed (state
        SHED, resources dropped), or None. Raises EngineOverloaded when the
        queue is full under the "reject" policy."""
        # the decode reserve is part of the admission bound: a verify step
        # may hold KV capacity for decode_reserve speculative tokens past
        # the request's own total, and the lone-request growth guarantee
        # must cover that worst case too
        total = req.prompt_len + req.max_new_tokens + self.decode_reserve
        if not self.cache.fits_ever(total, req.prompt_len):
            raise ValueError(
                f"request {req.rid}: {total} tokens can never fit "
                f"(max {self.cache.cfg.max_tokens_per_seq} per sequence, "
                + ", ".join(f"{g.allocator.num_usable} usable pages"
                            + (f" in group {g.name}"
                               if len(self.cache.groups) > 1 else "")
                            for g in self.cache.groups)
                + (f", incl. the speculative decode reserve of "
                   f"{self.decode_reserve}" if self.decode_reserve else "")
                + ")")
        shed = None
        if self.max_waiting and len(self.waiting) >= self.max_waiting:
            if self.shed_policy == "reject":
                raise EngineOverloaded(
                    f"waiting queue full ({self.max_waiting}); request "
                    f"{req.rid} rejected")
            # shed-oldest: the longest-waiting NEWCOMER yields its place —
            # it is the most likely to be past caring (deadline-wise), and
            # dropping it keeps FIFO order intact for every survivor.
            # Preemption victims requeued at the front are not newcomers:
            # they already spent admission work (and in swap mode hold their
            # whole KV), so they are never shed — if the queue is all
            # victims, the newcomer is rejected instead.
            shed = next((r for r in self.waiting if r.preemptions == 0),
                        None)
            if shed is None:
                raise EngineOverloaded(
                    f"waiting queue full ({self.max_waiting}) with only "
                    f"preempted in-flight requests; request {req.rid} "
                    f"rejected")
            self.waiting.remove(shed)  # identity removal (eq=False)
            shed.state, shed.swap = SHED, None
        req.state = WAITING
        self.waiting.append(req)
        return shed

    #: consecutive times a warm waiter may jump the same queue head under
    #: prefer_cached before the head is force-admitted next — bounds
    #: starvation of a cold whale under sustained degraded warm traffic
    HEAD_SKIP_LIMIT = 16

    def _next_waiter(self, prefer_cached: bool, probe: dict) -> Request:
        """The next admission candidate. FIFO head-of-line by default.
        Under SLO degradation (``prefer_cached``) a WARM waiter — one
        with a non-empty prefix-cache hit — may jump the queue: its
        uncached tail costs almost none of the throttled chunk budget.
        Cold waiters never reorder among themselves (no shortest-job
        scheduling smuggled in), preemption victims at the front always
        go first, a head skipped ``HEAD_SKIP_LIMIT`` consecutive times is
        force-admitted (warm traffic cannot starve a cold whale
        indefinitely), and strict FIFO returns the moment degradation
        clears. ``probe`` memoizes the per-waiter index probes for the
        duration of one admit() call."""
        head = self.waiting[0]
        if not prefer_cached or head.preemptions > 0:
            return head
        if self._head_skips >= self.HEAD_SKIP_LIMIT:
            self._head_skips = 0
            return head
        best, best_key = head, None
        for i, r in enumerate(self.waiting):
            if r.rid not in probe:
                probe[r.rid] = self.cache.cached_prefix_tokens(r.prompt)
            cached = probe[r.rid]
            if cached <= 0:  # cold: only eligible as the FIFO head
                continue
            key = (r.prompt_len - cached, i)
            if best_key is None or key < best_key:
                best, best_key = r, key
        if best is not head:
            self._head_skips += 1
        else:
            self._head_skips = 0
        return best

    def admit(self, resume_only: bool = False,
              prefer_cached: bool = False) -> list[Request]:
        """Admit waiting requests FIFO into free slots while pages are
        available. Head-of-line: the first request that doesn't fit blocks
        the queue (no out-of-order admission — arrival order is the service
        order the tests pin). A swapped-out request needs its handle's pages
        restored rather than prompt pages allocated. ``resume_only`` admits
        only preemption victims (always queued at the front): the paused-
        drain mode, where in-flight work resumes but newcomers wait.
        ``prefer_cached`` (the SLO controller's degraded mode) relaxes
        strict arrival order to prefer warm prefix-cache waiters — see
        ``_next_waiter``."""
        admitted = []
        tr = self._tracer
        probe: dict[int, int] = {}  # rid -> cached tokens, one admit() call
        while self.waiting and self._free_slots:
            req = self._next_waiter(prefer_cached, probe)
            if resume_only and req.preemptions == 0:
                break
            slot = self._free_slots[-1]
            spills0 = self.cache.spills
            if req.swap is not None:
                if not self.cache.swap_in(slot, req.swap):
                    break
                req.swap = None
                req.cached_tokens = 0
                req.resumed_from_swap = True
            else:
                try:
                    ok = self.cache.admit(slot, req.prompt_len,
                                          tokens=req.prompt, rid=req.rid)
                except HostTierRestoreError as e:
                    # the cache undid the whole admission (tier entries
                    # dropped, pages freed, shares released); the request
                    # stays queued HERE — the engine drains
                    # restore_failures immediately after admit() and
                    # retires it FAILED through the normal evict path
                    self.restore_failures.append((req, e))
                    break
                if not ok:
                    break
                # admission cost is counted in UNIQUE pages: the cached
                # whole-page prefix was mapped by refcount bump, so only
                # the uncached tail consumed pool capacity
                req.cached_tokens = self.cache.cached_tokens(slot)
            self._free_slots.pop()
            if self.waiting[0] is req:
                self.waiting.popleft()
            else:  # prefer_cached picked past the head: identity removal
                self.waiting.remove(req)
            req.state, req.slot = RUNNING, slot
            req.admit_seq = next(self._admit_seq)
            self.running[slot] = req
            admitted.append(req)
            if tr is not None:
                # host-tier lifecycle instants, chronological: spills this
                # admission forced (its allocation's eviction sweep), then
                # the pages restored INTO it, then the admission itself
                spilled = self.cache.spills - spills0
                if spilled:
                    tr.event(req.rid, "spill", pages=spilled)
                restored = self.cache.restored_pages(slot)
                if restored:
                    tr.event(req.rid, "restore", pages=restored)
                tr.event(req.rid, "admitted", slot=slot,
                         cached_tokens=req.cached_tokens)
        return admitted

    def pop_restore_failures(self) -> list[tuple[Request, Exception]]:
        """Drain the restore-failed (request, error) pairs recorded by
        admit() — the engine retires each FAILED."""
        out, self.restore_failures = self.restore_failures, []
        return out

    # ------------------------------------------------------------- decoding
    def pick_victim(self) -> Request:
        """Preemption victim: youngest admitted, but among requests that
        have decoded at least once when any exist — preempting a request
        that was prefilled this same step wastes its whole prefill before
        the first decode token it bought."""
        seasoned = [r for r in self.running.values() if not r.fresh]
        pool = seasoned or list(self.running.values())
        return max(pool, key=lambda r: r.admit_seq)

    def ensure_decode_pages(self) -> list[tuple[Request, int]]:
        """Before a decode step: every running slot is about to write the KV
        of its last generated token at position ``tokens_resident - 1``
        (engine ctx), so it needs capacity for ``tokens_resident`` tokens —
        NOT one more; asking for tokens_resident + 1 would demand a page one
        step early and preempt spuriously at page boundaries. A nonzero
        ``decode_reserve`` (speculative decoding) adds its K candidate
        writes at ``ctx + 1 .. ctx + K`` on top — for decoding slots only;
        a PREFILLING request isn't in the verify batch and holds its full
        prompt allocation already. A request whose last token is already
        in flight (``all_launched``) writes nothing more. Preempts per
        ``pick_victim`` until the survivors fit. Returns (request, vacated
        slot) pairs — the engine must deactivate those slots."""
        preempted = []
        for slot in sorted(self.running,
                           key=lambda s: self.running[s].admit_seq):
            req = self.running.get(slot)
            if req is None:  # already preempted (or retired by a drain)
                continue
            if req.all_launched:
                continue
            reserve = self.decode_reserve if req.state != PREFILLING else 0
            while req.slot is not None \
                    and not self.cache.grow(slot,
                                            req.tokens_resident + reserve):
                if self.before_preempt is not None and self.before_preempt():
                    continue  # tokens came back, requests may have retired
                victim = self.pick_victim()
                preempted.append((victim, self.preempt(victim)))
                # admission-time fits_ever() guarantees a lone request can
                # always grow, so this loop terminates
        return preempted

    def preempt(self, req: Request) -> int:
        """Preempt a running request per ``preemption_mode`` and requeue it
        at the front of the waiting queue. Returns the vacated slot."""
        slot = req.slot
        self.running.pop(slot)
        tr = self._tracer
        if tr is not None:
            tr.event(req.rid, "preempted", mode=self.preemption_mode,
                     tokens=len(req.generated))
        if self.preemption_mode == "swap":
            req.swap = self.cache.swap_out(slot)
            if tr is not None:
                tr.event(req.rid, "swap_out", pages=req.swap.n_pages)
        else:
            self.cache.release(slot)
            req.generated.clear()
            # a mid-prefill victim's chunk progress lived in those pages
            req.prefilled_tokens = 0
        self._free_slots.append(slot)
        req.state, req.slot = WAITING, None
        req.preemptions += 1
        self.preemption_count += 1
        self.waiting.appendleft(req)
        return slot

    def evict(self, req: Request) -> int | None:
        """Remove a request from waiting or running WITHOUT finishing it
        (cancel / deadline expiry / injected failure), freeing its slot,
        pages, and any swap handle. Returns the vacated slot (None when the
        request was waiting). The caller owns the terminal state."""
        if req.state in (RUNNING, PREFILLING):
            slot = req.slot
            self.running.pop(slot)
            self.cache.release(slot)
            self._free_slots.append(slot)
            req.slot = None
            return slot
        if req.state == WAITING:
            # identity removal (Request has eq=False); a missing request
            # here is a caller bug — let the ValueError be loud
            self.waiting.remove(req)
            req.swap = None
        return None

    def finish(self, req: Request) -> None:
        slot = req.slot
        self.running.pop(slot)
        self.cache.release(slot)
        self._free_slots.append(slot)
        req.state, req.slot = FINISHED, None

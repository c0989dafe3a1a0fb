"""The serving loop: scheduler + paged cache + model, one jitted step.

Static-shape discipline is the whole design: the decode step is a single
``jax.jit``-compiled function of (params, pools, page_table [max_batch,
pages_per_seq], ctx_lens [max_batch], prev_toks [max_batch], override
[max_batch], active [max_batch], rids [max_batch], gen_idx [max_batch]) —
every array keeps its shape for the life of the engine, so requests
joining and leaving the batch
NEVER retrigger compilation (the e2e test asserts exactly-one trace per
function via ``compile_counts``, which is now a read-through view of the
``analysis.tracecheck.CompileGuard`` wrapping each jitted step — the guard
counts traces, enforces the compile budget, and on an unexpected retrace
explains WHICH argument's signature changed). Prefill compiles once per PAD
BUCKET: a
prompt (or, on a prefix-cache hit, its uncached tail) is right-padded to
the smallest bucket in a fixed power-of-two set capped at
``max_prompt_len``, so short prompts stop paying max-length prefill FLOPs
and the bucket set is the only source of prefill compiles.

How a step program is run. The engine has three kinds of compiled
program (prefill, one a pad bucket; decode; with ``spec=``, verify) and
runs all of them the same way, through code that exists once:

- a program's description (``_Program``: phase, audit label, rows x tokens;
  ``engine._programs`` by label) and its operands (``_prefill_args``,
  ``_decode_args``, ``_verify_args``: the order of a program's operands is
  known here and nowhere else — the hlocheck registry asks for them);
- ONE launch (``_launch``): the ``debug_checks`` audit, the dispatch inside
  the ``serve.<phase>.dispatch`` span, the classification of a failure
  (a strict-guard refusal and consumed pools are engine-fatal; anything
  else retires the one request a prefill launch serves), the rebind of
  the donated pools, the count of the launch's attention pages. A fact
  that is true of every launch is written there;
- ONE fetch (``_fetch``): the single sanctioned device->host copy of a
  program's output, inside the caller's ``*.fetch`` span, with the model's
  counters split off the tokens. Decode fetches the launch of the step
  before, a completed prefill is fetched behind the decode launch that
  follows it (below); a verify fetches its own;
- ONE prefill path (``_prefill``): advance a request's prefill by ``n``
  tokens from where it stands; if that completes the prompt, seat the
  request (``_seat``, which a swap-resume uses too) with its first token
  in flight and leave the token's fetch (``_first_token``) to the decode
  phase. A whole uncached tail is the one chunk that is final.

``_step`` is then the schedule and nothing else: sweep and admit, seat or
prefill what was admitted, advance the chunked prefills, fault sites and
page pressure, decode or verify; the step's accounts follow it
(``_step_and_account``).

Automatic prefix caching: admission matches the prompt against the paged
cache's content index in whole pages (kv_cache.py), maps the hit pages
into the new slot's page-table row by refcount bump, and prefills ONLY the
uncached tail — queries enter at ``ctx_lens = cached_tokens``, riding the
same ragged ``paged_attention`` contract decode already uses, so there is
no kernel change and compile-once holds. Greedy outputs are bit-identical
with caching on or off: the fixed gather width plus exact-zero ragged
masking make KV bytes position-deterministic, so cached pages hold exactly
the bytes a cold prefill would recompute.

Chunked prefill (``ServingConfig(chunk_size=N)``): a long prompt no longer
monopolizes an engine step at its full pad bucket. An admitted request
enters a PREFILLING state and advances N prompt tokens per step through
the SAME prefill path and program — each chunk's queries enter at
``ctx_lens = tokens already prefilled``, the exact ragged mechanism the
prefix-cache tail prefill rides, with the chunk padded into the existing
bucket set (the bucket set stays the only source of prefill compiles,
whatever the chunk size or count). Decode for the running batch proceeds in the
same step, so TPOT stays bounded while whales prefill and newcomer TTFT
stops queueing behind them. Intermediate chunks never fetch their sampled
token (nor the model's counters behind it: ``serving_moe_*`` leave those
launches out), so the sync-free decode certification is unchanged: one
fetch per decode step plus one per COMPLETED prefill. Outputs are bit-identical
chunked or not — same KV bytes, same last-token logits (the PR 3
exact-zero ragged masking argument, applied inductively per chunk).

Speculative decoding (``ServingConfig(spec=SpecConfig(...))``): each step
proposes ``depth`` candidate tokens per running request in-jit (a small
stateless draft model over a sliding window, or n-gram lookup on the
request's own token history — serving/spec.py) and verifies all K+1
tokens in ONE batched ragged pass through the same paged decode path
(queries at ``ctx_lens .. ctx_lens + K``), with accept/reject computed
in-jit as a masked cumulative match against the target's own tokens.
Because every emitted token is the TARGET's (greedy argmax, or the sample
under the identical (seed, rid, token_idx) fold), outputs are
bit-identical to plain decoding at any acceptance rate and preemption
replay stays exact. The verify program compiles once per configured
depth, the host fetches one packed [batch, K+2] array per step (the
decode token fetch renamed — the sync-free certification formula is
unchanged), the scheduler reserves K extra token slots per decoding
request, and the rejected span's pages recycle through the refcounted
allocator (``PagedKVCache.shrink``) as soon as the accept count lands.

Tensor-parallel serving (``ServingConfig(tensor_parallel=N)``): the
weights shard Megatron-style and the paged KV pool shards its heads axis
across an N-device mesh (serving/tp.py), and the SAME step bodies run
inside ``shard_map`` — compiled once per bucket like single-chip, with
exactly ``2 * num_layers + 1`` all-reduces per step (row-parallel
out_proj + fc2 per block, one for the logits), declared as a
``CollectiveBudget`` and certified by the hlocheck audit under
``debug_checks``. Outputs are bit-identical TP=N vs TP=1 and every
invariant below — compile counts, the sync-free certification formula,
prefix-cache/COW/eviction on logical page ids, per-shard swap — is
sharding-blind.

On top, ``ServingConfig(slo=SLOConfig(ttft_p99_s=, tpot_p99_s=))`` installs
an SLO-adaptive admission controller (serving/slo.py): each step boundary
it reads the streaming ``serving_step_duration_s`` / ``serving_tpot_s``
histograms — host-side integer bucket counts, zero added device syncs —
and AIMD-adapts how many prefill chunks each step may admit; while
degraded, waiters with warm prefix-cache hits are admitted ahead of cold
ones (their uncached tail is cheap). The current limit is mirrored in the
``serving_chunk_limit`` gauge.

The order of a step, and when a token is handed over. ``step()`` sweeps
deadlines, admits and LAUNCHES the prefills of what it admitted (no
fetch), makes room for the decode, LAUNCHES decode k behind them, and
only then fetches: first the tokens of decode k-1, which the previous
``step()`` launched and which has been running on the device while the
host came round (emitted and retired at once), then the first token of
each prefill it completed, in admission order. On the device the order
is decode k-1, the prefills, decode k, with nothing between them: all
that the host does between two launches (a fetch's tail, emit,
accounting, the caller's loop, admit, a prefill's upload and dispatch,
evict, the decode's upload and dispatch) happens under a program and not
between two of them. What it takes:

- the last token stays on the device: the decode program takes the
  previous launch's token output as it is (``prev_toks``, not donated —
  it is still to be fetched) and merges it in-program with the host's
  ``override`` for the slots whose last token the host does know
  (swap-resumed, or every slot of a drained engine). A prefill program
  takes ``prev_toks`` too and returns it with its sampled token in the
  request's slot: that array is the next launch's ``prev_toks`` and what
  the host fetches for the first token. No eager operation runs between
  two steps;
- what does not depend on a token's value advances at the launch
  (``_ctx``, ``_gen``, the page that ``ensure_decode_pages`` reserves, a
  prefilled request's seat); what does (``req.generated``,
  ``tokens_emitted``, ``_last_tok``, the finish, ``decode_mark``,
  ``prefill_end`` / ``first_token``, the prompt's pages in the prefix
  index, ``on_tokens``) advances at the fetch. A caller sees a decode
  token when it is appended to ``req.generated``: one ``step()`` after
  the step that launched it. A prefill's first token is handed over by
  the step that completed the prefill, behind that step's decode launch;
- finish by length is known at the launch (tokens emitted + in flight, a
  first token in flight among them: a request of ``max_new_tokens`` 1 is
  never launched): such a slot is left out of the next launch and
  retires at its fetch. Finish by EOS is known one step late (for a
  first token: after the decode behind the prefill was launched): the
  surplus token of the launch already made is dropped at its fetch —
  never appended, counted or indexed; its KV write went to a page of the
  request's own, freed with it. Outputs are token for token those of an
  engine that fetches every step;
- ``_drain(reason)`` fetches and emits what is in flight, now (the first
  tokens of prefills not yet fetched, then the decode), at every site
  that needs the host's view whole before it acts (``DRAIN_REASONS``):
  preemption and swap-out, ``cancel`` and the deadline sweep when they
  hit a request with a token in flight, the fault injector's
  decode-phase hits, ``debug_checks`` (every step: the invariant sweep
  and the sync tally read a whole step), the flight record and the fatal
  path, the end of ``run()``. A drained engine is exactly the engine
  that fetched every step; a ``step()`` with a decode in flight and
  nothing to launch only fetches. A device error of decode k surfaces at
  its fetch in step k+1, with a note naming step k;
- three callers want a first token on the host at once and keep the
  blocking fetch inside ``_prefill`` (one branch at the fetch, decided
  by the engine's own state): speculative decoding (``_verify_phase``
  replaces plain decode wholesale, mirrors every known token into the
  proposers' history, fetches its own launch in the same step and never
  has a launch in flight), ``debug_checks`` (every step drains), and, as
  ever, a chunk that is not final, which fetches nothing at all.

``serving_decode_overlapped_total`` over ``serving_decode_steps`` is the
share of launches made under a decode in flight,
``serving_prefill_overlapped_total`` over ``serving_prefills_total`` the
share of completed prefills whose first token was fetched behind the
decode launch that followed them;
``serving_decode_drains_total{reason=}`` counts the early fetches.

Decode semantics match text/generation.py: prefill picks the first token
from the last prompt logit, each decode step feeds the previous token back
in, writes its KV at position ctx, and samples the next — so per-request
greedy outputs are identical to single-request ``generate``. Sampling PRNG
keys are derived in-jit from (engine seed, rid, token index): a request's
token stream is a pure function of its identity, so a RECOMPUTE-preempted
sampling request replays its original tokens instead of resampling.

Resilience layer:

- per-request deadlines (``add_request(..., deadline_s=)``) swept at every
  step boundary, and ``cancel(rid)`` — both retire a request from waiting OR
  running state and free its slot + pages;
- admission backpressure: ``max_waiting`` bounds the queue, ``shed_policy``
  picks reject (EngineOverloaded) vs shed-oldest;
- swap-style preemption (``preemption_mode="swap"``) resumes preempted
  requests with their generated tokens intact;
- a deterministic fault-injection harness (serving/faults.py) consulted at
  step boundaries: a faulted step retires only the affected requests as
  FAILED (exception recorded on the request) and keeps serving the rest —
  faults fire BEFORE the mutation they poison, so host scheduler/cache
  state stays exactly the pre-step state minus the retired request;
- ``run(budget_s=...)``: a wall-clock budget that pauses admission and
  drains in-flight work instead of raising mid-stream.

The engine clock is pluggable (``clock=``, default time.monotonic) and the
``slow_step`` fault point advances a virtual skew on top of it, so every
deadline/budget behavior is testable without sleeping.

Debug checks (``ServingConfig(debug_checks=True)``): every step boundary
runs the CompileGuard audits in strict mode (an over-budget retrace raises
RetraceError naming the offending argument BEFORE paying the recompile; a
donated-then-referenced pool raises DonationViolation), sweeps
``PagedKVCache.check_invariants()``, and tallies host syncs
(``analysis.tracecheck.SyncTally``) into the ``serving_analysis_*``
metrics. Each jitted step is additionally donation-audited at jaxpr level
before its FIRST trace (``analysis.donation_audit``): a donated buffer the
computation never consumes is a wrong ``donate_argnums`` and raises
DonationViolation naming the leaf. On top of that, every COMPILED PROGRAM
(each prefill pad bucket + the decode step) is hlocheck-audited ONCE at
its first trace (``analysis.hlocheck``): the step is AOT-lowered and its
optimized HLO certified against the single-chip budget — zero collective
ops, zero host-transfer/callback ops baked into the program, and XLA's
``input_output_alias`` table honoring every donated pool (a
donated-but-copied pool is a silent 2x HBM cost no trace-level check can
see). Reports land in ``engine.hlo_audits`` and roll up into the
``serving_hlo_*`` metrics. Costs host work per step plus one extra AOT
compile per program — a debugging mode, not a serving mode.

Observability (``paddle_tpu.obs``, on by default via ``enable_tracing``):
every request accrues a timestamped lifecycle trace (enqueued, admitted,
prefill_start/end, first_token, periodic decode marks, preemption/swap
events, retired-with-state) off the same pluggable clock — retrievable
with ``engine.trace(rid)``, summarized into queue_wait / TTFT / TPOT /
e2e, fed into the fixed-bucket serving histograms at retirement, and
exportable as Perfetto-loadable Chrome trace JSON
(``engine.export_chrome_trace()``) alongside the bounded per-step
timeline (``engine.timeline``). The contract: O(1) appends per event,
ONE attribute check per event site when tracing is off, and ZERO new
host syncs on the decode loop either way (the SyncTally certification
in bench/demo is unchanged with tracing enabled).

Goodput attribution (rides ``enable_tracing``; obs/attribution.py): one
span mechanism inside ``step()``. Every boundary is ONE call site
(``with att.span("decode.fetch"):``) that writes two records — seconds on
the engine clock (a span named after a phase joins the exact split of the
step's wall time, ``StepRecord.phase_s`` and the
``serving_step_phase_s{phase=}`` histogram family; every other span adds
its own extent to ``StepRecord.span_s``) and a
``jax.profiler.TraceAnnotation`` in the host plane of the profiler's own
trace, the timeline of the ``/device:TPU:n`` planes, so a gap of the
device is put down to the part of the step the host was in. Every span
carries ``step=``, the per-request ones ``rid=``:

====================================  ==================================
span                                  extent; attributes
====================================  ==================================
``serve.step``                        all of ``step()``, the record /
                                      watchdog / SLO work after the body
                                      included
``serve.admit``                       deadline sweep, ``scheduler.admit``,
                                      restore failures; ``queue_depth``
``serve.prefill``                     one per request prefilled whole;
                                      ``rid``, ``bucket``, ``cached``,
                                      ``tail``
``serve.chunk_prefill``               the chunk loop; ``chunks``
``serve.prefill.upload``              inside either, one a launch: the
                                      padded ids and the five operands
                                      from the host (``prev_toks`` is on
                                      the device); ``rid``, ``bytes``
``serve.prefill.dispatch``            the call of the jitted program;
                                      ``rid``
``serve.prefill.fetch``               the first-token fetch of a launch
                                      that completed a prompt: inside
                                      ``serve.decode``, behind this step's
                                      launch and the fetch of the step
                                      before's (blocks for what is left of
                                      the prefill), or inside
                                      ``serve.drain``; inside
                                      ``serve.prefill`` /
                                      ``serve.chunk_prefill`` where the
                                      engine fetches at once (spec,
                                      ``debug_checks``); ``rid``
``serve.evict``                       fault sites, decode-page pressure,
                                      preemption
``serve.decode``                      the decode phase: this step's
                                      launch, the fetch and emit of the
                                      step before's, the first-token
                                      fetches of this step's prefills;
                                      ``batch`` (the slots launched)
``serve.decode.upload``               the six device operands, the whole
                                      page table among them; ``bytes``
``serve.decode.dispatch``             the call of the jitted program:
                                      this step's launch
``serve.decode.fetch``                the token fetch of the PREVIOUS
                                      step's launch (blocks for what is
                                      left of it); ``of_step``
``serve.decode.emit``                 the per-slot loop over the fetched
                                      tokens, retirements
``serve.drain``                       an early fetch + emit of what is in
                                      flight (``prefill.fetch`` of each
                                      unfetched prefill, ``decode.fetch``
                                      and ``decode.emit`` inside);
                                      ``reason``
``serve.verify``                      the speculative verify phase;
                                      ``batch``
``serve.verify.dispatch``             the call of the jitted program
``serve.verify.fetch``                the packed fetch of that same launch
                                      (blocks)
``serve.account``                     cache stats, gauges, the step
                                      record, watchdogs, SLO controller:
                                      the obs layer's own cost per step
``serve.add_request``                 ``add_request`` once the request has
                                      its id; ``rid``, ``prompt_len``
``serve.cow_copy``                    one copy-on-write page copy
                                      (kv_cache.py); ``pages``
====================================  ==================================

A span takes its attributes when it opens, so counts known at its end
(admitted, prefills, preempted, tokens emitted, accepted) stay on the
``StepRecord`` and join by ``step``; the request's lifecycle
(``engine.trace(rid)``, ``engine.journey(rid)``: the engine step of each
hop) joins by ``step`` and ``rid``. On the device the compiled programs
are named by their CompileGuards (``jit_serve_decode``,
``jit_serve_prefill_<bucket>``, ``jit_serve_verify``) and the model's
regions by ``jax.named_scope`` (``embed``, ``block/attn``, ``block/mlp``,
``final_norm``, ``kv_write``, ``sample``). With no profiler session an
annotation records nothing; with ``enable_tracing=False`` the accumulator
is disabled: the same sites run, each gets the one shared do-nothing
context and no span object is made (the decision lives behind
``PhaseAccumulator``, not at the call sites). Zero added device syncs
either way.

The step's seconds and the stall record (ride ``enable_tracing``;
obs/stall.py). At a step's close the record's seconds go into counters
that a reader differences over any window, on the engine's clock:
``serving_step_seconds_total``, ``serving_step_span_seconds_total{span=}``
(each entry of ``span_s``), ``serving_step_host_seconds_total`` (the step
less its ``*.fetch`` spans: what the host itself does, as against waiting
for the device; an upload or a dispatch that blocks on a full device
queue counts as the host's, and the by-span family takes it off) and
``serving_step_unstalled_seconds_total`` (the step less its stall
seconds); plain floats that ``metrics.snapshot()`` mirrors, so a step
pays additions and no lock. A BLOCKING span (its name ends in ``.fetch``,
``.upload`` or ``.dispatch``: ``_fetch``, ``_launch`` and the upload
sites) that outlasts its name's NORM (what nine in ten of the name's last
64 spans stayed under) by more than ``max(50 ms, that norm)`` is a STALL:
flagged on the engine's thread by one float compare, its excess over the
norm the stall's seconds. What held it is read by a sampler thread (started here
when tracing is on and the clock is the default one, ended by
``close()`` or with the engine's collection) that sees the open span and
the device value it awaits in one slot, samples the process and the
machine once the span is 20 ms past its norm, asks
``awaited.is_ready()`` every 2 ms (non-blocking, no sync) and samples
again at the release. The record, over that sampled part:

=========================  ==========================================
field                      what
=========================  ==========================================
``step``, ``span``,        the engine step, the span's name, its
``at_s``                   start on the engine's clock
``ms``, ``excess_ms``,     the span's length, its excess over its
``norm_ms``                name's norm, that norm
``sampler_late_ms``        the most that a pass of the sampler
                           itself came late (a timed wait that needs
                           nothing of the device or the runtime): by
                           about the wait's length, the whole
                           process stood still
``sampled_from_ms``,       where in the span the first sample was
``sampled_ms``             taken; how long the sampled part is (D)
``device_ready_after_ms``  ms into the span at which the awaited
                           value (a fetch: the array to copy; an
                           upload or dispatch: the newest launch's
                           output) was first seen ready; ``None``:
                           never
``thread_cpu_ms``,         CPU of the engine's thread and of the
``process_cpu_ms``         whole process
``nvcsw``, ``nivcsw``,     the engine thread's voluntary and
``majflt``                 involuntary switches and major faults
``threads``                the five threads with most CPU and the
                           five with most run-queue wait (``tid``,
                           ``comm``, ``state``, ``cpu_ms``,
                           ``runq_wait_ms``, ``core``)
``psi``, ``loadavg``,      the machine: growth of PSI ``some total``
``vmstat``,                by cpu / memory / io, the load, growth of
``machine_cpu_ms``,        major faults and allocation stalls, of all
``steal_ms``               cores' busy time, of the hypervisor's
                           take; an absent file reads ``None``
``held_by``                ``frozen`` (the sampler came late by over
                           half the stall's excess: held from
                           outside the process; ``runtime_busy`` if
                           the process used over 3/4 of that
                           lateness in CPU, ``late_cpu_ms``), then
                           ``device`` (not ready until within 5 ms
                           of the release), ``cpu_queue`` (a
                           thread, or PSI cpu, over D/2 runnable and
                           waiting for a core), ``memory``, ``io``
                           (PSI over D/2; allocation stalls; major
                           faults), ``runtime_busy`` (the other
                           threads over D/2 of CPU), else ``asleep``
                           (the wake-up itself); ``unsampled``: no
                           evidence (no sampler on a clock of one's
                           own)
=========================  ==========================================

It is read from ``engine.stalls`` (the newest 64),
``StepRecord.extra["stalls"]`` of the step that held the wait (so the
timeline ring and every flight-record dump have it),
``python -m paddle_tpu.obs --flight-record DUMP --stalls``, the counters
``serving_stalls_total{held_by=}`` /
``serving_stall_seconds_total{held_by=}``, and the ``serve.stall`` event
(``span=``, ``step=``) that the sampler's thread leaves in a profiler
trace beside the device's ``XLA Modules`` line. The benchmark driver's
own ``stalls`` list (``serve.steps``: every ``step()`` over 100 ms by the
caller's clock) is the outside view of the same steps; it also lists
steps that are long by their nature (a first step of many prefills),
which this rule does not flag.

Anomaly watchdogs (``enable_watchdogs``, default on)
evaluate edge-triggered rules over host-resident ints at each step
boundary — retrace-after-warmup, Pallas fallback, speculative-acceptance
collapse, eviction thrash, queue stall — each firing a structured Alert
+ ``serving_alerts_total{rule=}`` + a Chrome instant. A black-box flight
recorder (``engine.dump_flight_record(path)``; automatic on engine-fatal
exceptions, the stuck-engine backstop, and every FAILED retirement)
bundles the newest step records, alerts, gauges, audit roll-ups, and
latency summaries into one schema-versioned JSON dump.

Per-tenant SLO observability (rides ``enable_tracing``): requests carry
``add_request(tenant=)``, every retirement is classified by the
goodput/badput ledger (obs/tenant.py — 7 terminal classes against the
``ServingConfig(tenants={name: TenantSLO(...)})`` targets, emitted
tokens accrued per class so the per-tenant totals reconcile exactly
with ``serving_tokens_total``), and every request accrues a **journey**
(obs/journey.py — enqueue → admit → chunks → decode/verify → preempt/
swap → retire hops with engine-step refs, folded off the tracer's own
event stream), exportable as the schema-versioned
``paddle-tpu/journey/v1`` wire dict. The ``slo_burn`` watchdog rule
windows each tenant's violation fraction; the flight record (schema
v2) grows per-tenant roll-ups + a bounded journey ring; Chrome export
grows one track per tenant. All of it is host dict work off stamps
that already existed: zero added device syncs (the SyncTally formula
is pinned unchanged with tenants + journeys on), and the tenant label
never enters a traced program.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
import weakref
from collections import deque
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import hlocheck
from ..analysis.tracecheck import (CompileGuard, DonationViolation,
                                   RetraceError, SyncTally, donation_audit)
from ..core.tensor import Tensor
from ..obs import (ALERT_RULES, HELD_BY, SPANS, JourneyBook,
                   PhaseAccumulator, StallWatch, StepRecord, StepTimeline,
                   TenantLedger, TenantSLO, Tracer, Watchdog, WatchdogConfig,
                   build_flight_record, check_tenant_name, chrome_trace,
                   write_chrome_trace)
from ..obs.recorder import MAX_FLIGHT_JOURNEYS as _MAX_FLIGHT_JOURNEYS
from ..obs.recorder import dump_flight_record as _write_flight_record
from ..text.generation import sample_logits
from ..utils import monitor
from .faults import InjectedFault
from .kv_cache import PagedCacheConfig, PagedKVCache
from .metrics import ServingMetrics
from .scheduler import (CANCELLED, EXPIRED, FAILED, FINISHED, PREFILLING,
                        RUNNING, SHED, WAITING, EngineOverloaded, Request,
                        Scheduler)
from .slo import SLOConfig, SLOController
from .spec import SpecConfig, accept_counts, draft_window, propose_ngram


@dataclass(frozen=True)
class ServingConfig:
    max_batch: int = 4
    num_pages: int = 64
    group_pages: dict | None = None  # {group name: pages}: the pages (null
    # page included) of each page group behind the model's first, for a
    # model that states several (kv_cache.PageGroup: window layers beside
    # full ones); ``num_pages`` is then the first group's
    page_size: int = 16
    pages_per_seq: int = 0  # 0 -> ceil(max_seq_len / page_size)
    max_prompt_len: int = 32  # prefill pad bucket (one compile for all prompts)
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: int | None = None
    pad_token_id: int = 0
    seed: int = 0
    max_waiting: int = 0  # waiting-queue bound; 0 = unbounded
    shed_policy: str = "reject"  # "reject" | "shed-oldest" when queue full
    preemption_mode: str = "recompute"  # "recompute" | "swap"
    enable_prefix_caching: bool = True  # cross-request KV page sharing
    tensor_parallel: int = 1  # Megatron-shard the weights + the paged KV
    # pool (heads axis) across an N-device mesh via shard_map (serving/
    # tp.py): the prefill buckets, chunk phase, and decode still compile
    # ONCE each as sharded programs with exactly 2*num_layers + 1
    # all-reduces per step (row-parallel out_proj + fc2 per block, one
    # for the logits) — declared as a CollectiveBudget and certified by
    # the hlocheck audit under debug_checks. 1 = single-chip serving.
    tp_overlap_scheduler: bool = False  # ask XLA's latency-hiding
    # scheduler to overlap each per-block all-reduce's async -start/-done
    # pair with independent compute (the T3/async-collective idiom).
    # When on, the declared step budget requires min_overlap_frac=1.0 —
    # every collective the backend compiles async must hide under compute
    # (hlocheck's overlap census; vacuous where collectives compile
    # sync, e.g. the forced CPU meshes). No-op unless tensor_parallel>1.
    tp_quantized_logits: bool = False  # ship the b*s*V logits all-reduce
    # as int8 codes + one 4-byte shared-scale psum (serving/tp.py
    # quantized_psum, EQuARX-style): the step's largest collective
    # payload shrinks ~4x at a bounded greedy-quality delta. Off =
    # bit-identical to the unquantized engine (the branch never traces).
    # No-op unless tensor_parallel > 1.
    mesh_topology: object | None = None  # analysis.meshcheck.MeshTopology
    # declaring WHERE the tp mesh lives (hosts x chips-per-host x named
    # axes). Under debug_checks the first-trace audit attributes every
    # collective to its axis, classifies ICI vs DCN, enforces the
    # step budget's per-medium arms (zero-DCN binding when the declared
    # topology is single-host), and feeds the serving_{ici,dcn}_bytes_
    # per_token / serving_collective_time_predicted_s gauges. None =
    # a default single-host topology over tensor_parallel chips (gauges
    # still fed; per-medium arms not enforced — nothing was declared).
    chunk_size: int = 0  # prefill tokens per step per request; 0 = whole
    # tail in one pass (chunking off). Chunks ride the SAME prefill jit
    # (ctx_lens = tokens already resident) padded into the existing
    # bucket set — no new compiles, ever.
    kv_dtype: str = "float32"  # "float32" | "int8": int8 stores the paged
    # KV pool as codes + per-page-per-head f32 absmax scales, quantized
    # in-jit at scatter time and dequantized inside the attention gather
    # (kernels/paged_attention.py) — ~4x the concurrent users per HBM
    # byte at a bounded greedy-quality delta; compile counts, sync-free
    # certification, and TP collective budgets are unchanged. The fp32
    # default is bit-identical to the pre-quantization engine.
    host_tier_bytes: int = 0  # bounded host-memory spill tier: evicted
    # refcount-0 prefix pages keep their content-index keys and spill
    # here (one batched jitted gather per eviction sweep) instead of
    # being purged; the next prefix hit restores them through the donated
    # swap scatter before prefill — warm system prompts survive far
    # beyond HBM. 0 = off (evictions purge, the PR 3 behavior).
    slo: SLOConfig | None = None  # SLO-adaptive chunk admission (needs
    # chunk_size > 0 and enable_tracing — it reads the obs histograms)
    spec: SpecConfig | None = None  # speculative decoding (serving/
    # spec.py): each step proposes depth=K candidate tokens per running
    # request in-jit (a small draft model or prompt/output n-gram lookup)
    # and verifies all K+1 in ONE batched ragged pass through the paged
    # decode path, emitting 1..K+1 tokens per request per step. Outputs
    # stay bit-identical to non-speculative decoding (greedy AND
    # sampling: every emitted token is the target's own, under the same
    # (seed, rid, token_idx) PRNG fold), the verify program compiles once
    # per configured depth, and the host still fetches exactly one packed
    # output per step. None = plain decode.
    debug_checks: bool = False  # strict CompileGuard + invariant sweep/step
    enable_tracing: bool = True  # per-request traces + step timeline (obs)
    trace_capacity: int = 2048  # retained traces (terminal evicted oldest)
    decode_mark_every: int = 32  # decode_mark trace event cadence (tokens)
    timeline_capacity: int = 512  # step records retained in the ring
    enable_watchdogs: bool = True  # anomaly watchdogs (obs/alerts.py) at
    # step boundaries — edge-triggered rules over host-resident ints
    # (zero added syncs); active only with enable_tracing (they read the
    # step record). Each firing bumps serving_alerts_total{rule=}, lands
    # in the alert history + flight record, and renders as a Chrome
    # instant on the engine track.
    watchdog: WatchdogConfig | None = None  # rule thresholds; None =
    # the conservative defaults (a clean engine never fires)
    flight_record_path: str | None = None  # where the automatic flight-
    # record dumps go (engine-fatal paths, stuck-engine backstop, any
    # step that retired a request FAILED); None keeps the record only on
    # engine.last_flight_record. engine.dump_flight_record(path) works
    # either way.
    flight_record_steps: int = 64  # step records per dump (the newest N
    # of the timeline ring)
    tenants: dict | None = None  # {name: obs.TenantSLO(ttft_p99_s=,
    # tpot_p99_s=)} — per-tenant SLO classes (interactive vs batch).
    # OBSERVE-ONLY this layer: requests carry add_request(tenant=) as a
    # label, every retirement is classified into the 7-class goodput/
    # badput ledger (obs/tenant.py) + the per-tenant latency families,
    # and the slo_burn watchdog windows each tenant's violation
    # fraction — but admission/scheduling never read the tenant
    # (weighted admission belongs to the fleet router). Unknown tenants
    # are served under their own label with no SLO (everything finished
    # is in_slo); None declares no classes — the implicit "default"
    # tenant still keeps books. The tenant label never enters a traced
    # program: compile counts and the sync-free certification are
    # byte-identical with tenants on.


#: why a decode in flight was fetched before the next launch (the sites
#: that need the host's view whole before they act): the label values of
#: ``serving_decode_drains_total{reason=}`` and of ``serve.drain`` spans
DRAIN_REASONS = ("preempt", "cancel", "deadline", "fault", "debug_checks",
                 "flight_record", "fatal", "run_end")


def prefill_buckets(max_prompt_len: int) -> list[int]:
    """The fixed prefill pad buckets: powers of two from 8 up, capped at
    (and always including) ``max_prompt_len``. Each bucket compiles the
    prefill step once; nothing else ever does."""
    buckets, b = [], 8
    while b < max_prompt_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_prompt_len)
    return buckets


class _Program(NamedTuple):
    """One compiled step program, as a launch, an audit and a gauge need
    it described. ``engine._programs`` holds one a prefill pad bucket, one
    for decode and, with ``spec=``, one for verify, by ``label``; the
    operands of a launch come from ``_prefill_args`` / ``_decode_args`` /
    ``_verify_args``."""
    phase: str  # "prefill" | "decode" | "verify": its CompileGuard
    # (engine.guards[phase], launched as engine._<phase>_jit) and its
    # serve.<phase>.dispatch span
    label: str  # its audit label: "prefill[<bucket>]", "decode", "verify"
    rows: int   # what one launch computes: rows x tokens a row, padding
    tokens: int  # and dead slots too
    counters: int  # the model's counters behind its tokens (_with_counters)


class _FirstToken(NamedTuple):
    """A completed prefill whose sampled token is still on the device:
    what its fetch needs (``engine._unfetched``)."""
    req: Request
    prog: _Program  # the prefill program that ran
    out: object     # its output: prev_toks with the token in req.slot
    tokens: int     # prompt tokens the launch computed


class ServingEngine:
    """Continuous-batching engine over any model with the paged-cache
    contract (text/gpt.py's ``GPTForCausalLM``, text/kimi_k2.py's
    ``KimiK2ForCausalLM``, text/granite_hybrid.py's
    ``GraniteHybridForCausalLM``): ``functional_state`` /
    ``functional_call``, ``forward(ids, caches=[{<that layer's leaves>,
    page_table, ctx_lens, valid, kv_limit}, ...]) -> (logits,
    new_caches)``, and ``paged_cache_spec(...)``, by which the model
    states what it keeps, layer by layer (``kv_cache.PagedCacheSpec``),
    and refuses what it cannot do. A model that keeps something a SLOT (a
    recurrent state) also finds ``slots`` in every layer's cache: the slot
    of each row of the launch, None where row i is slot i (decode). The
    engine reads no model by its field names."""

    def __init__(self, model, config: ServingConfig | None = None,
                 clock=None, fault_injector=None, draft_model=None):
        self.config = cfg = config or ServingConfig()
        self.model = model
        model.eval()
        if draft_model is not None and (
                cfg.spec is None or cfg.spec.method != "draft"):
            raise ValueError(
                "draft_model= is the spec proposer — it needs "
                "ServingConfig(spec=SpecConfig(method='draft', ...))")
        if cfg.chunk_size < 0:
            raise ValueError(f"chunk_size {cfg.chunk_size} < 0")
        if cfg.chunk_size > cfg.max_prompt_len:
            # a chunk must pad into the existing bucket set (capped at
            # max_prompt_len) — a larger chunk would need a new compile
            raise ValueError(
                f"chunk_size {cfg.chunk_size} exceeds max_prompt_len "
                f"{cfg.max_prompt_len} (chunks pad into the prefill "
                f"bucket set)")
        if cfg.slo is not None and not cfg.chunk_size:
            raise ValueError(
                "ServingConfig(slo=) adapts chunked prefill admission — "
                "set chunk_size > 0 to enable chunking first")
        if cfg.slo is not None and not cfg.enable_tracing:
            raise ValueError(
                "the SLO controller reads the obs step/tpot histograms, "
                "which enable_tracing feeds — it cannot run with tracing "
                "disabled (it would silently never throttle)")
        if cfg.tensor_parallel < 1:
            raise ValueError(f"tensor_parallel {cfg.tensor_parallel} < 1")
        if cfg.kv_dtype not in ("float32", "int8"):
            raise ValueError(f"kv_dtype {cfg.kv_dtype!r} not in "
                             f"('float32', 'int8')")
        if cfg.host_tier_bytes and not cfg.enable_prefix_caching:
            raise ValueError(
                "host_tier_bytes gives evicted INDEXED prefix pages a "
                "second life — enable_prefix_caching=False would leave "
                "nothing to spill; enable it or drop the tier")
        # what the model keeps a layer, and its refusal (with the reason)
        # of what it cannot do under this configuration
        self._cache_spec = spec = model.paged_cache_spec(
            kv_dtype=cfg.kv_dtype, tensor_parallel=cfg.tensor_parallel,
            speculative=cfg.spec is not None)
        if cfg.max_prompt_len > spec.max_seq_len:
            raise ValueError(
                f"max_prompt_len {cfg.max_prompt_len} exceeds the model's "
                f"max_seq_len {spec.max_seq_len}")
        if cfg.flight_record_steps < 1:
            raise ValueError(
                f"flight_record_steps {cfg.flight_record_steps} < 1")
        for tname, slo in (cfg.tenants or {}).items():
            # bad names/targets fail here, not at the first retirement
            check_tenant_name(tname)
            if not isinstance(slo, TenantSLO):
                raise ValueError(
                    f"tenants[{tname!r}] must be an obs.TenantSLO, got "
                    f"{type(slo).__name__}")
            slo.validate()
        if cfg.spec is not None:
            # bad method/depth/draft-shape mismatches fail here, not at
            # the first verify trace; a prebuilt draft_model's real
            # config wins over spec.draft
            cfg.spec.validate(
                model.cfg,
                draft_model.cfg if draft_model is not None else None)
        if cfg.tensor_parallel > 1:
            # mesh + Megatron shard specs + shard_map wrappers; validates
            # divisibility (heads/hidden/ffn) and the visible device count
            from .tp import TPContext
            self._tp = TPContext(
                cfg.tensor_parallel, model.cfg,
                overlap_scheduler=cfg.tp_overlap_scheduler,
                quantized_logits=cfg.tp_quantized_logits)
        else:
            self._tp = None
        if cfg.enable_prefix_caching and spec.no_prefix_sharing:
            raise ValueError(
                "enable_prefix_caching=True is not supported by this "
                f"model: {spec.no_prefix_sharing}")
        pages_per_seq = cfg.pages_per_seq or \
            -(-spec.max_seq_len // cfg.page_size)
        rest = [g.name for g in spec.groups[1:]]
        if sorted(cfg.group_pages or {}) != sorted(rest):
            raise ValueError(
                f"group_pages {cfg.group_pages} does not give the pages of "
                f"the model's page groups behind its first: {rest}")
        self.cache = PagedKVCache(PagedCacheConfig(
            num_layers=spec.num_layers, leaves=spec.leaves or None,
            leaves_by_layer=spec.leaves_by_layer, groups=spec.groups,
            group_pages=tuple(cfg.group_pages[n] for n in rest),
            num_pages=cfg.num_pages, page_size=cfg.page_size,
            max_batch=cfg.max_batch, pages_per_seq=pages_per_seq,
            dtype=spec.dtype,
            enable_prefix_caching=cfg.enable_prefix_caching,
            debug_checks=cfg.debug_checks, tp=self._tp,
            kv_dtype=cfg.kv_dtype, host_tier_bytes=cfg.host_tier_bytes))
        # the jitted steps thread every pool leaf through — the model's
        # own, layer by layer (scale leaves ride beside the codes in
        # quantized mode, a recurrent layer's state a slot beside nothing)
        self._layer_keys = tuple(tuple(lf.name for lf in ls)
                                 for ls in self.cache.cfg.layer_leaves)
        # a model that keeps something a SLOT is told which slot each row
        # of a launch is (``slots`` in every layer's cache; None: row i is
        # slot i, as in decode)
        self._slot_state = bool(self.cache.cfg.slot_leaf_keys)
        # several page groups: a launch uploads their tables stacked, and
        # each layer is handed its own group's
        self._layer_group = (self.cache.cfg.group_of_layer
                             if len(self.cache.groups) > 1 else None)
        # what the model counts a launch (an expert layer's assignments):
        # an int32 vector behind the launch's tokens, in the same fetch
        self._n_counters = len(spec.counters)
        self.prefill_buckets = prefill_buckets(cfg.max_prompt_len)
        self.metrics = ServingMetrics()
        self.metrics.on_tp_degree(cfg.tensor_parallel)
        self.metrics.on_kv_bytes_per_token(self.cache.cfg.kv_bytes_per_token)
        self.metrics.on_state_bytes_per_slot(
            self.cache.cfg.state_bytes_per_slot)
        self.metrics.on_spec_depth(cfg.spec.depth if cfg.spec else 0)
        # labeled-family presence: the watchdog rule counters read 0
        # before anything happens, the same contract _SEEDED gives the
        # scalars
        self.metrics.seed_family("alerts_total", ALERT_RULES)
        params, _ = model.functional_state()
        self._p = {k: v._value for k, v in params.items()}
        if self._tp is not None:
            # Megatron placement: qkv/fc1 column-split, out_proj/fc2
            # row-split (bias on device 0 only — psum adds it exactly
            # once), everything else replicated; recorded shard specs feed
            # the step wrappers below
            self._p = self._tp.shard_params(self._p)
        self._clock = clock or time.monotonic
        self._skew = 0.0  # virtual seconds injected by slow_step faults
        # goodput attribution (obs/attribution.py): the one span mechanism
        # inside step() — each boundary writes its seconds on the engine
        # clock and a serve.* TraceAnnotation into the profiler's trace;
        # clock reads and TraceMe events only, zero device syncs (the
        # SyncTally certification is pinned unchanged). The engine always
        # holds one: with tracing off it is disabled and every site is a
        # no-op behind it
        # the stall watch (obs/stall.py) rides the blocking spans: it
        # flags one that outlasts its name's norm, and its sampler thread
        # reads what held the wait. The thread lives in real time, so
        # only an engine on the default clock starts it; it references
        # nothing of the engine and ends with it (close(), or collection)
        self._stalls = None
        if cfg.enable_tracing:
            self._stalls = StallWatch(
                clock=time.monotonic if clock is None else self.now)
            self.metrics.seed_step_seconds(SPANS, HELD_BY)
            if clock is None:
                self._stalls.start()
                weakref.finalize(self, self._stalls.stop)
        self._attr = PhaseAccumulator(self.now if cfg.enable_tracing
                                      else None, stalls=self._stalls)
        # obs layer: request tracer + step timeline run off the engine
        # clock (virtual-clock testable, zero host syncs); None when off —
        # every event site costs one attribute check and nothing else
        if cfg.enable_tracing:
            self._tracer = Tracer(self.now, capacity=cfg.trace_capacity,
                                  mark_every=cfg.decode_mark_every)
            self._timeline = StepTimeline(cfg.timeline_capacity)
            # request journeys (obs/journey.py): a pure fold over the
            # tracer's event stream (the journal tap) + the host step
            # counter — zero new instrumentation sites, zero syncs
            self._journeys = JourneyBook(lambda: self._now_step,
                                         capacity=cfg.trace_capacity)
            self._tracer.journal = self._journeys.on_event
            # the per-tenant goodput/badput ledger (obs/tenant.py) —
            # observe-only, fed once per retirement in _trace_retire
            self._tenants = TenantLedger(cfg.tenants)
            # anomaly watchdogs: edge-triggered rules over the step
            # record + host counter totals, evaluated at step boundaries
            self._watchdog = (Watchdog(cfg.watchdog or WatchdogConfig(),
                                       clock=self.now)
                              if cfg.enable_watchdogs else None)
        else:
            self._tracer = None
            self._timeline = None
            self._watchdog = None
            self._journeys = None
            self._tenants = None
        # the per-tenant metric families are pre-seeded for the declared
        # tenants + "default" regardless of tracing (the presence
        # contract); _seeded_tenants makes the known-tenant add_request
        # path one set lookup
        tenant_names = ["default"] + sorted(
            t for t in (cfg.tenants or {}) if t != "default")
        self.metrics.seed_tenants(tenant_names)
        self._seeded_tenants = set(tenant_names)
        self.last_flight_record: dict | None = None  # newest auto dump
        self._failed_count = 0   # FAILED retirements ever (auto-dump edge)
        self._failed_dumped = 0
        self._step_stats: dict | None = None  # _step -> step() handoff
        self.scheduler = Scheduler(
            self.cache, cfg.max_batch, max_waiting=cfg.max_waiting,
            shed_policy=cfg.shed_policy, preemption_mode=cfg.preemption_mode,
            tracer=self._tracer)
        # a victim is picked among, and preempted or swapped out with,
        # what the host knows
        self.scheduler.before_preempt = lambda: self._drain("preempt")
        # speculative decoding (serving/spec.py): proposer state plus the
        # host-mirrored token-history buffer the proposers read — shipped
        # with every verify call via _spec_hist (full buffer for n-gram,
        # just the [max_batch, window] known-token slice for draft), a
        # static shape either way so history growth never recompiles.
        # Spec off costs one attribute check per step, nothing else.
        if cfg.spec is not None:
            self._spec = cfg.spec
            # a verify step writes KV at ctx .. ctx + K before the accept
            # count is known: admission and per-step growth must reserve
            # those K slots (over-allocation recycles via cache.shrink)
            self.scheduler.decode_reserve = cfg.spec.depth
            self._hist = np.zeros((cfg.max_batch, spec.max_seq_len),
                                  np.int32)
            if cfg.spec.method == "draft":
                if draft_model is None:
                    from ..text.gpt import GPTForCausalLM
                    draft_model = GPTForCausalLM(cfg.spec.draft)
                draft_model.eval()
                self._draft = draft_model
                dp, _ = draft_model.functional_state()
                self._draft_p = {k: v._value for k, v in dp.items()}
            else:
                self._draft = self._draft_p = None
        else:
            self._spec = None
            self._hist = None
            self._draft = self._draft_p = None
        # whether a Pallas kernel is even dispatchable for this engine's
        # decode shapes: the model's own gate (GPT: the unified ragged
        # kernel's ragged_kernel_eligible), read once
        self._decode_pallas_eligible = model.decode_kernel_eligible(
            pages_per_seq, cfg.page_size, self.cache.cfg.quantized)
        # ctx_lens -> pages the attention stages, by query count a row
        # (the model's answer, asked once a launch shape)
        self._pages_staged: dict = {}

        self._fault_injector = fault_injector
        if fault_injector is not None and self.cache.host_tier is not None:
            # the restore_fail fault point: consulted by the cache right
            # before a host-tier restore scatter. Installed only when an
            # injector exists, so the injector-off path keeps its
            # one-attribute-check contract inside the cache too.
            self.cache.restore_fault = self._restore_fault_probe
        # SLO-adaptive chunk admission: a host-side AIMD controller over
        # chunks-per-step, windowing the obs histograms (serving/slo.py).
        # None (chunking off or no SLO) costs one attribute check per step.
        if cfg.slo is not None:
            self._slo = SLOController(cfg.slo, self.metrics,
                                      default_max_chunks=cfg.max_batch)
            self.metrics.on_chunk_limit(self._slo.chunk_limit)
        else:
            self._slo = None
        self._step_idx = 0
        self._now_step = 0  # step index the restore_fail probe matches
        self.admit_paused = False  # run(budget_s=) drain; settable by callers
        b = cfg.max_batch
        self._ctx = np.zeros(b, np.int32)
        self._last_tok = np.full(b, cfg.pad_token_id, np.int32)
        self._active = np.zeros(b, bool)
        self._rids = np.zeros(b, np.int32)  # per-slot rid (PRNG stream id)
        self._gen = np.zeros(b, np.int32)   # per-slot generated-token count
        # the decode launched and not yet fetched: (its token output on the
        # device, the step that launched it, [(slot, request)]). step()
        # launches decode k and only then fetches decode k-1, so the host's
        # work between two launches runs under a decode program. _ctx and
        # _gen are ahead of the host's tokens by the one in flight;
        # _last_tok is the host's mirror and lags it.
        self._inflight = None
        # the completed prefills whose first token the host has not fetched
        # yet, in admission order: a step launches its prefills, then its
        # decode, and fetches them behind that launch. Empty between two
        # steps; _drain fetches them first
        self._unfetched: deque[_FirstToken] = deque()
        # a speculative engine mirrors every known token into the
        # proposers' history and never has a launch in flight, and
        # debug_checks reads a whole step: both fetch a first token at once
        self._first_token_at_once = cfg.spec is not None or cfg.debug_checks
        # the last launch's token output (a decode's, or a completed
        # prefill's: the decode's with the first token in its slot), the
        # next launch's last tokens for the slots whose token the host has
        # not seen; until the first launch a placeholder that every slot
        # overrides
        prev = np.full(b + self._n_counters, cfg.pad_token_id, np.int32)
        self._prev_toks = (jnp.asarray(prev) if self._tp is None
                           else self._tp.replicated(prev))
        # requests that a drain outside step() finished (cancel, a flight
        # record): the next step() returns their ids
        self._drained_finished: list[int] = []
        self.metrics.seed_family("decode_drains_total", DRAIN_REASONS)
        # what a decode step uploads, from the operands' shapes: the whole
        # page table and the five per-slot vectors (serve.decode.upload)
        self._decode_upload_bytes = sum(
            a.nbytes for a in (self.cache.tables, self._ctx,
                               self._last_tok, self._active, self._rids,
                               self._gen))
        # the cache's copy-on-write page copy is a span of the same
        # mechanism (serve.cow_copy)
        self.cache.spans = self._attr
        self._finished: dict[int, np.ndarray] = {}
        self._retired: dict[int, Request] = {}  # cancelled/expired/failed/shed
        self._requests: dict[int, Request] = {}
        self._host_syncs = 0  # SyncTally total, counted under debug_checks
        self._retraces_emitted = 0  # last value mirrored into the metrics
        self._donation_audits: dict[str, list] = {}  # debug_checks reports
        # hlocheck reports per compiled program ("prefill[BUCKET]"/"decode"),
        # recorded under debug_checks at each program's first trace
        self._hlo_audits: dict[str, hlocheck.HloAuditReport] = {}
        # donate the pools: the engine rebinds self.cache.pools to the
        # returned arrays immediately, and without donation XLA can't alias
        # input to output — the .at[] scatter would copy the ENTIRE pool
        # every token and hold two pools live (for an HBM-sized pool that
        # doubles cache memory and makes a step O(pool), not O(page)).
        # CompileGuard counts traces (the compile_counts surface), enforces
        # the compile budget — one trace per prefill bucket, one decode —
        # and under debug_checks refuses an over-budget retrace with a
        # diff naming the argument whose signature changed.
        # prefill groups by pad-bucket shape: EACH bucket compiles at most
        # once, so a same-bucket retrace (e.g. dtype drift) can't hide in
        # the headroom of buckets this workload never used
        prefill_impl, decode_impl = self._prefill_impl, self._decode_impl
        # per-jit XLA options: only the TP latency-hiding scheduler today
        # (tp_overlap_scheduler; None on backends without it / single-chip)
        xla_opts = (self._tp.compiler_options()
                    if self._tp is not None else None)
        if self._tp is not None:
            # sharded programs: the SAME step bodies run inside shard_map
            # (params/pools under their shard specs, host operands
            # replicated, model psums enabled for the trace) — the guards
            # wrap the sharded callables, so compile counts, budgets, and
            # the retrace/donation audits are identical to single-chip
            prefill_impl = self._tp.wrap_step(
                prefill_impl, spec.num_layers, n_rest=7,
                quantized=self.cache.cfg.quantized)
            decode_impl = self._tp.wrap_step(
                decode_impl, spec.num_layers, n_rest=7,
                quantized=self.cache.cfg.quantized)
        # ``program=`` names what the guard jits, so the profiler's
        # "XLA Modules" line reads jit_serve_prefill_<bucket> (one name
        # per pad bucket), jit_serve_decode, jit_serve_verify
        self._prefill_jit = CompileGuard(
            prefill_impl, "prefill", donate_argnums=(1,),
            budget=len(self.prefill_buckets), strict=cfg.debug_checks,
            group_by=lambda *a: tuple(a[2].shape),
            compiler_options=xla_opts, program="serve_prefill")
        self._decode_jit = CompileGuard(
            decode_impl, "decode", donate_argnums=(1,),
            budget=1, strict=cfg.debug_checks,
            compiler_options=xla_opts, program="serve_decode")
        self.guards = {"prefill": self._prefill_jit,
                       "decode": self._decode_jit}
        if cfg.spec is not None:
            # the speculative verify step: fixed depth K means ONE
            # compiled program per configured K for the engine's lifetime
            # — budget 1, like decode. Under tensor parallelism the
            # replicated draft params (if any) ride as a replicated rest
            # operand; the target's collectives are unchanged and the
            # draft adds none (its psums are suppressed — see
            # _propose_draft).
            verify_impl = self._verify_impl
            if self._tp is not None:
                n_rest = 7 + (1 if cfg.spec.method == "draft" else 0)
                verify_impl = self._tp.wrap_step(
                    verify_impl, spec.num_layers, n_rest=n_rest,
                    quantized=self.cache.cfg.quantized)
            self._verify_jit = CompileGuard(
                verify_impl, "verify", donate_argnums=(1,),
                budget=1, strict=cfg.debug_checks,
                compiler_options=xla_opts, program="serve_verify")
            self.guards["verify"] = self._verify_jit
        else:
            self._verify_jit = None
        # every compiled program's description, by audit label (buckets
        # in rising order: _prefill_program takes the first that fits)
        nc = self._n_counters
        programs = [_Program("prefill", f"prefill[{n}]", 1, n, nc)
                    for n in self.prefill_buckets]
        programs.append(_Program("decode", "decode", cfg.max_batch, 1, nc))
        if cfg.spec is not None:  # _verify_impl drops the counters
            programs.append(_Program("verify", "verify", cfg.max_batch,
                                     cfg.spec.depth + 1, 0))
        self._programs = {p.label: p for p in programs}

    # --------------------------------------------------------- jitted steps
    def _req_key(self, rid, t):
        """PRNG key for request ``rid``'s token ``t``: fold (seed, rid,
        token index). Identity-derived, not a split chain — preemption and
        batch churn cannot shift any other request's stream, and a replayed
        request reproduces its own."""
        base = jax.random.key(self.config.seed)
        return jax.random.fold_in(jax.random.fold_in(base, rid), t)

    def _sample_row(self, logits_row, key):
        cfg = self.config
        return sample_logits(logits_row[None, :], key, cfg.temperature,
                             cfg.top_k, cfg.top_p)[0]

    def _run_model(self, p_arrays, pools, table, ctx, valid, ids,
                   kv_limit=None, slots=None, head_at=None):
        """(logits, new_pools, counters): one paged call of the model.
        ``kv_limit`` is a static bound on the positions this call can
        reach (a prefill stays inside ``max_prompt_len``), for a model
        whose prefill reads its context back from the pool; None is the
        whole page table. ``slots`` [rows] names the slot of each row, for
        a model that keeps something a slot (None: row i is slot i).
        ``table`` is the page table of the launch's rows, or, for a model
        of several page groups, one a group, stacked: each layer gets its
        own group's. ``head_at`` [rows], for a model that takes it
        (``PagedCacheSpec.head_at_positions``): the one position a row
        whose logits are read; the logits are then ``[rows, 1, vocab]``.
        ``counters`` is what the layers counted, summed (int32
        [len(spec.counters)]), or None for a model that counts nothing."""
        shared = dict(page_table=table, ctx_lens=ctx, valid=valid,
                      kv_limit=kv_limit)
        if self._slot_state:
            shared["slots"] = None if slots is None else jnp.reshape(
                slots.astype(jnp.int32), (-1,))
        if head_at is not None:
            shared["head_at"] = head_at
        caches = [dict(pl, **shared) for pl in pools]
        if self._layer_group is not None:
            for c, g in zip(caches, self._layer_group):
                c["page_table"] = table[g]
        (logits, new_caches), _ = self.model.functional_call(
            p_arrays, {}, Tensor(ids), caches=caches)
        new_pools = [{k: c[k] for k in keys}
                     for c, keys in zip(new_caches, self._layer_keys)]
        counters = None
        if self._n_counters:
            counters = sum(c["counters"] for c in new_caches
                           if "counters" in c).astype(jnp.int32)
        return logits._value, new_pools, counters

    @staticmethod
    def _with_counters(tok, counters):
        """The launch's tokens with the model's counters behind them: one
        array, so one fetch. A model that counts nothing returns its
        tokens as they are."""
        if counters is None:
            return tok
        return jnp.concatenate([jnp.atleast_1d(tok), counters])

    def _prefill_impl(self, p_arrays, pools, padded_ids, tail_len, ctx0,
                      page_row, rid, slot, prev_toks):
        """One request's uncached prompt tail in one pass: padded_ids
        [bucket], tail_len scalar (real tail tokens), ctx0 scalar (tokens
        already resident from the prefix cache or an earlier chunk; 0 on
        a cold prefill), page_row [pages_per_seq], slot scalar (the
        request's slot), prev_toks [max_batch (+ counters)] (the last
        launch's token output, where it is: on the device, not donated —
        it may still be to be fetched). The tail's queries enter at
        positions ``ctx0 .. ctx0 + tail_len - 1`` against the slot's page
        table — the cached prefix is attended through the same
        ragged-masked gather decode uses. Returns (new_pools, prev_toks
        with the sampled token in the request's slot and this launch's
        counters behind): what the decode launch behind this one takes as
        ITS ``prev_toks``, so the first token reaches the decode on the
        device and the host fetches the same array when it comes round.
        Compiles once per pad bucket (padded_ids shape)."""
        n = padded_ids.shape[0]
        # one row; of a model of several page groups one row a group
        table = page_row[None, :] if page_row.ndim == 1 \
            else page_row[:, None, :]
        ctx = jnp.reshape(ctx0.astype(jnp.int32), (1,))
        valid = (jnp.arange(n, dtype=jnp.int32) < tail_len)[None, :]
        # a model that takes it computes its head at the last real token
        # alone: at a bucket of thousands the head at every position is
        # most of the program's FLOPs and an array of which one row is read
        at_last = self._cache_spec.head_at_positions
        logits, new_pools, counters = self._run_model(
            p_arrays, pools, table, ctx, valid, padded_ids[None, :],
            kv_limit=self.config.max_prompt_len, slots=slot,
            head_at=jnp.reshape(tail_len.astype(jnp.int32) - 1, (1,))
            if at_last else None)
        with jax.named_scope("sample"):
            last = logits[0, 0, :] if at_last \
                else logits[0, tail_len - 1, :]
            if self.config.do_sample:
                tok = self._sample_row(last, self._req_key(rid, 0))
            else:
                tok = jnp.argmax(last, axis=-1)
            tok = tok.astype(jnp.int32)
        b = self.config.max_batch
        toks = prev_toks[:b].at[slot].set(tok)
        return new_pools, self._with_counters(toks, counters)

    def _decode_impl(self, p_arrays, pools, table, ctx, prev_toks,
                     override, active, rids, gen_idx):
        """One token for every running slot. Inactive slots run the same
        computation against the null page and emit pad — branch-free, so the
        batch composition never changes the compiled program. A slot's last
        token is ``prev_toks`` (the previous launch's output, a decode's or
        a completed prefill's, still on the device, never donated: the host
        fetches it after this launch) unless the host knows it and says so
        with ``override >= 0``: a slot swap-resumed, or every slot of a
        drained engine."""
        if self._n_counters:    # the last launch's counters ride behind
            prev_toks = prev_toks[:override.shape[0]]
        last_tok = jnp.where(override >= 0, override, prev_toks)
        logits, new_pools, counters = self._run_model(
            p_arrays, pools, table, ctx, active[:, None], last_tok[:, None])
        with jax.named_scope("sample"):
            last = logits[:, -1, :]
            if self.config.do_sample:
                keys = jax.vmap(self._req_key)(rids, gen_idx)
                tok = jax.vmap(self._sample_row)(last, keys)
            else:
                tok = jnp.argmax(last, axis=-1)
            tok = jnp.where(
                active, tok,
                jnp.asarray(self.config.pad_token_id)).astype(jnp.int32)
        return new_pools, self._with_counters(tok, counters)

    def _propose_draft(self, draft_p, win):
        """The draft proposer, in-jit: decode K candidates greedily from a
        fresh dense (non-paged) KV buffer over ``win`` — the request's
        last ``window`` known tokens, right-aligned, sliced host-side by
        ``_spec_hist`` — at window-relative positions. The buffer is created
        zero-filled inside the trace every step — the draft carries no
        state across steps, so preemption/prefix-cache/swap/quantization
        never interact with it. Under tensor parallelism the draft is
        replicated and its row-parallel psums are suppressed (every
        device computes the identical candidates locally — zero extra
        collectives, keeping the verify budget at the target's own
        2*num_layers + 1)."""
        from ..text.gpt import tp_axis

        sp, dc = self.config.spec, self._draft.cfg
        K, W = sp.depth, sp.window
        b = win.shape[0]
        dt = self._draft.paged_cache_spec().dtype
        shape = (b, dc.num_heads, W + K, dc.hidden_size // dc.num_heads)
        caches = [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
                  for _ in range(dc.num_layers)]
        with tp_axis(None):
            (logits, caches), _ = self._draft.functional_call(
                draft_p, {}, Tensor(win), caches=caches, pos=0)
            tok = jnp.argmax(logits._value[:, -1, :], axis=-1)
            cands = [tok.astype(jnp.int32)]
            for j in range(1, K):
                (logits, caches), _ = self._draft.functional_call(
                    draft_p, {}, Tensor(tok[:, None]), caches=caches,
                    pos=W + j - 1)
                tok = jnp.argmax(logits._value[:, 0, :], axis=-1)
                cands.append(tok.astype(jnp.int32))
        return jnp.stack(cands, axis=1)  # [b, K]

    def _verify_impl(self, p_arrays, pools, table, ctx, last_tok, active,
                     rids, gen_idx, hist, draft_p=None):
        """One speculative step for every running slot: propose K
        candidates, verify all K+1 tokens (pending last token + the
        candidates) in ONE ragged multi-token pass through the paged
        decode path, and compute the accept count in-jit. Returns
        (new_pools, packed [batch, K+2] int32): the target's own token at
        each of the K+1 positions followed by the accept count — ONE
        host fetch per step, exactly like plain decode's token vector.
        Every emitted token is the TARGET's (argmax, or the sample under
        the (seed, rid, token_idx) fold non-speculative decoding would
        have drawn with the identical context), so acceptance only
        decides how MANY of them this step emits — never their values.
        Inactive slots run the same computation against the null page and
        emit pad, branch-free."""
        cfg = self.config
        sp = cfg.spec
        K = sp.depth
        if sp.method == "draft":
            # ``hist`` is already the right-aligned [batch, window]
            # known-token context (_spec_hist slices it host-side)
            cand = self._propose_draft(draft_p, hist)
        else:
            known = ctx.astype(jnp.int32) + 1  # resident + pending token
            cand = propose_ngram(hist, known, K, sp.ngram,
                                 cfg.pad_token_id)
        cand = jnp.where(active[:, None], cand, cfg.pad_token_id)
        ids = jnp.concatenate([last_tok[:, None], cand], axis=1)
        valid = jnp.broadcast_to(active[:, None], ids.shape)
        logits, new_pools, _ = self._run_model(
            p_arrays, pools, table, ctx, valid, ids)
        with jax.named_scope("sample"):
            if cfg.do_sample:
                offs = jnp.arange(K + 1, dtype=jnp.int32)
                keys = jax.vmap(lambda r, g: jax.vmap(
                    lambda j: self._req_key(r, g + j))(offs))(rids, gen_idx)
                target = jax.vmap(jax.vmap(self._sample_row))(logits, keys)
            else:
                target = jnp.argmax(logits, axis=-1)
            target = jnp.where(active[:, None], target.astype(jnp.int32),
                               cfg.pad_token_id).astype(jnp.int32)
        accepted = jnp.where(active, accept_counts(cand, target),
                             0).astype(jnp.int32)
        packed = jnp.concatenate([target, accepted[:, None]], axis=1)
        return new_pools, packed

    # ------------------------------------------------------------ host loop
    @property
    def compile_counts(self) -> dict:
        """Trace counts per jitted step, dict-shaped — the surface PR 1-3
        pinned (``{"prefill": 1, "decode": 1}``), now read off the
        CompileGuards instead of ad-hoc in-body counters."""
        return {k: g.traces for k, g in self.guards.items()}

    def now(self) -> float:
        """Engine time: the pluggable clock plus any slow_step fault skew —
        the time base for deadlines and run() budgets."""
        return self._clock() + self._skew

    def add_request(self, prompt, max_new_tokens: int,
                    deadline_s: float | None = None,
                    tenant: str = "default",
                    rid: int | None = None) -> int:
        """Queue a prompt; returns the request id. ``deadline_s`` is a
        wall-clock budget from now — a request still waiting or running when
        it elapses is retired EXPIRED at the next step boundary.
        ``tenant`` labels the request's SLO/traffic class for the
        goodput ledger, journey, and per-tenant latency families —
        observe-only (scheduling never reads it); tenants beyond the
        declared ``ServingConfig(tenants=)`` set are served under their
        own label with no SLO targets. ``rid`` lets the fleet router
        pass through an id it already drew (from the same global
        counter — ids stay process-unique) so a request keeps one id
        across routing hops and re-homes; callers without a router
        leave it None. Raises ValueError when the request could never
        fit (prompt too long for the bucket, the model, or the whole
        pool) or the tenant name is malformed, and EngineOverloaded
        when the bounded waiting queue is full under the reject
        policy."""
        if tenant not in self._seeded_tenants:
            # first sight of an ad-hoc tenant: validate the name and
            # seed its families now (declared tenants + "default" were
            # seeded at construction — this path is one set lookup for
            # every later request of the same tenant)
            check_tenant_name(tenant)
            self.metrics.seed_tenants([tenant])
            self._seeded_tenants.add(tenant)
            if self._tenants is not None:
                self._tenants.ensure(tenant)
        prompt = np.asarray(
            prompt._value if isinstance(prompt, Tensor) else prompt)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be 1-D, got shape {prompt.shape}")
        if prompt.shape[0] == 0:
            # an empty prompt would sample its first token from the logits
            # of a padding position (all-null-page KV) — garbage, silently
            raise ValueError("prompt must contain at least one token")
        if int(max_new_tokens) <= 0:
            raise ValueError("max_new_tokens must be positive")
        if prompt.shape[0] > self.config.max_prompt_len:
            raise ValueError(
                f"prompt_len {prompt.shape[0]} exceeds max_prompt_len "
                f"{self.config.max_prompt_len}")
        total = prompt.shape[0] + int(max_new_tokens)
        if total > self._cache_spec.max_seq_len:
            raise ValueError(
                f"prompt_len + max_new_tokens = {total} exceeds max_seq_len "
                f"{self._cache_spec.max_seq_len}")
        req = Request(prompt=prompt.astype(np.int32),
                      max_new_tokens=int(max_new_tokens),
                      deadline=(self.now() + float(deadline_s)
                                if deadline_s is not None else None),
                      tenant=tenant,
                      **({} if rid is None else {"rid": int(rid)}))
        # serve.add_request opens once the request has its id (a span
        # takes its attributes when it opens): the queueing, the shed
        # and the trace's first stamp; step = the step that runs next
        with self._attr.span("add_request", step=self._step_idx,
                             rid=req.rid, prompt_len=req.prompt_len):
            try:
                # validates against pool capacity
                shed = self.scheduler.add(req)
            except EngineOverloaded:
                self.metrics.on_rejected()
                raise
            tr = self._tracer
            if tr is not None:
                # journey first: the tracer's begin() stamps "enqueued",
                # which the journal tap routes onto the journey just opened
                self._journeys.begin(req.rid, tenant)
                tr.begin(req.rid)
            if shed is not None:
                self._requests.pop(shed.rid, None)
                self._retired[shed.rid] = shed
                self.metrics.on_shed()
                self._trace_retire(shed, SHED)
            self._requests[req.rid] = req
        return req.rid

    def cancel(self, rid: int) -> bool:
        """Retire a waiting or running request, freeing its slot and pages.
        True when something was cancelled; False for unknown or already
        terminal requests."""
        req = self._requests.get(rid)
        if req is not None and req.tokens_in_flight:
            self._drain("cancel")  # it may finish here, as it would have
        if req is None or req.state not in (WAITING, RUNNING, PREFILLING):
            return False
        self._retire(req, CANCELLED)
        self.metrics.on_cancelled()
        return True

    def status(self, rid: int) -> str:
        """Lifecycle state of a request: waiting/prefilling/running/
        finished/cancelled/expired/failed/shed (``prefilling`` only under
        chunked prefill: admitted, slot + pages held, prompt still
        streaming through the prefill step). KeyError for an unknown
        rid."""
        if rid in self._requests:
            return self._requests[rid].state
        if rid in self._finished:
            return FINISHED
        if rid in self._retired:
            return self._retired[rid].state
        raise KeyError(f"unknown request {rid}")

    def request(self, rid: int) -> Request | None:
        """The live or retired Request object (e.g. to read ``.error`` off a
        FAILED request); None for finished/unknown rids."""
        return self._requests.get(rid) or self._retired.get(rid)

    def _trace_retire(self, req: Request, state: str) -> None:
        """Stamp the terminal ``retired`` trace event, feed the
        request-latency histograms from the completed lifecycle, and
        settle the tenant ledger (classify the retirement, accrue the
        emitted tokens to goodput or badput, feed the per-tenant
        latency families). One attribute check when tracing is off —
        host dict work only, zero device syncs."""
        tr = self._tracer
        if tr is not None:
            tr.event(req.rid, "retired", state=state,
                     tokens=len(req.generated))
            trace = tr.get(req.rid)
            if trace is not None:
                summary = trace.summary()
                self.metrics.observe_request(summary)
                cls = self._tenants.on_retire(
                    req.tenant, state, ttft=summary["ttft"],
                    tpot=summary["tpot"], tokens=req.tokens_emitted)
                self.metrics.on_tenant_retire(req.tenant, cls,
                                              req.tokens_emitted)
                self.metrics.observe_tenant(
                    req.tenant, ttft=summary["ttft"],
                    tpot=summary["tpot"],
                    queue_delay=summary["queue_wait"])

    def _retire(self, req: Request, state: str,
                error: BaseException | None = None) -> None:
        """Terminal exit for a non-finished request: pull it out of waiting
        or running (slot + pages + swap handle freed) and record it."""
        slot = self.scheduler.evict(req)
        if slot is not None:
            self._clear_slot(slot)
        req.state, req.error = state, error
        self._requests.pop(req.rid, None)
        self._retired[req.rid] = req
        if state == FAILED:
            # the flight recorder's auto-dump edge: step() compares this
            # against the last-dumped count at every step boundary
            self._failed_count += 1
        self._trace_retire(req, state)

    def _fail(self, req: Request, error: BaseException) -> None:
        """Retire one request FAILED with what failed it, and count it;
        everything else keeps being served."""
        self._retire(req, FAILED, error)
        self.metrics.on_failed()

    def _fail_injected(self, point: str, step_idx: int,
                       req: Request) -> None:
        self._fail(req, InjectedFault(
            f"{point} injected (step {step_idx}, rid {req.rid})"))

    def _sweep_deadlines(self) -> None:
        with_deadline = [r for r in self._requests.values()
                         if r.deadline is not None]
        if not with_deadline:
            return
        now = self.now()
        expired = [r for r in with_deadline if now >= r.deadline]
        if any(r.tokens_in_flight for r in expired):
            self._drain("deadline")
        for req in expired:
            if req.state in (WAITING, RUNNING, PREFILLING):
                self._retire(req, EXPIRED)
                self.metrics.on_expired()

    def _clear_slot(self, slot: int) -> None:
        self._active[slot] = False
        self._ctx[slot] = 0
        self._last_tok[slot] = self.config.pad_token_id
        self._rids[slot] = 0
        self._gen[slot] = 0
        if self._hist is not None:
            self._hist[slot] = 0

    def _seat(self, req: Request) -> None:
        """Put a request into the decode batch: the five per-slot arrays,
        from the request itself. A swap-resume (its KV came back with
        ``admit``) seats with the tokens the host knows; a completed
        prefill seats WITHOUT its first token, which is in flight
        (``tokens_in_flight`` 1: it is in the request's slot of
        ``_prev_toks``, so the decode phase overrides nothing there and
        ``all_launched`` counts it) and advances ``_ctx`` and ``_gen`` as
        every token in flight does."""
        slot = req.slot
        known = len(req.generated) + req.tokens_in_flight
        self._ctx[slot] = req.prompt_len + known - 1
        self._last_tok[slot] = req.generated[-1] if req.generated \
            else self.config.pad_token_id
        self._active[slot] = True
        self._rids[slot] = req.rid
        self._gen[slot] = known
        req.state = RUNNING
        req.fresh = True  # no decode yet: spared while a seasoned victim is
        if not req.tokens_in_flight:  # else at the first token's fetch
            self._hist_sync(req)

    def _hist_sync(self, req: Request) -> None:
        """Mirror a request's known tokens (prompt + generated) into its
        row of the spec proposers' token-history buffer — the in-jit
        n-gram lookup and the draft's context window both read it. One
        attribute check when speculation is off."""
        if self._hist is None:
            return
        row = self._hist[req.slot]
        row[:] = 0
        row[:req.prompt_len] = req.prompt
        if req.generated:
            row[req.prompt_len:req.prompt_len + len(req.generated)] = \
                req.generated

    def _spec_hist(self) -> np.ndarray:
        """The history operand the verify dispatch ships. The n-gram
        proposer scans the whole [max_batch, max_seq_len] mirror; the
        draft proposer reads only its right-aligned window of known
        tokens, so method="draft" slices [max_batch, window] host-side —
        O(batch * window) H2D bytes per step instead of the full buffer.
        Fixed shape either way: history growth never recompiles."""
        if self._spec.method != "draft":
            return self._hist
        return draft_window(self._hist, self._ctx + 1, self._spec.window)

    def _restore_fault_probe(self, rid) -> bool:
        """Cache-side consult of the ``restore_fail`` fault point (armed
        FaultInjector only): matched against the CURRENT step index and
        the admitting request's rid, like every other step-boundary
        fault."""
        inj = self._fault_injector
        return inj is not None and inj.hit(
            "restore_fail", step=self._now_step, rid=rid) is not None

    def _preempt_one(self, req: Request, slot: int | None = None) -> None:
        """The one preemption recipe — the injected pool_exhausted path and
        the real ensure_decode_pages path share it: vacate the slot and
        account the preemption (swap mode also counts a swap_out). ``slot``
        is the already-vacated slot when the scheduler preempted the request
        itself; None preempts here."""
        if slot is None:
            slot = self.scheduler.preempt(req)
        self._clear_slot(slot)
        self.metrics.on_preempt()
        if self.config.preemption_mode == "swap":
            self.metrics.on_swap_out()

    def _maybe_finish(self, req: Request, tok: int) -> bool:
        eos = self.config.eos_token_id
        if len(req.generated) >= req.max_new_tokens or \
                (eos is not None and tok == eos):
            slot = req.slot
            # index the generated span too (all but the final token, whose
            # KV was never written) so a future prompt extending this
            # request's text hits the whole conversation, then release —
            # refcount-0 indexed pages park reclaimable, not freed
            self.cache.register_prefix(slot, req.output()[:-1])
            self.scheduler.finish(req)
            self._clear_slot(slot)
            self._finished[req.rid] = req.output()
            self._requests.pop(req.rid, None)  # bookkeeping ends at finish
            self._trace_retire(req, FINISHED)
            return True
        return False

    def _state_summary(self) -> str:
        s = self.scheduler
        waiting = [r.rid for r in itertools.islice(s.waiting, 8)]
        more = "..." if s.queue_depth > 8 else ""
        active = sorted(r.rid for r in s.running.values())
        return (f"step={self._step_idx}, queue_depth={s.queue_depth} "
                f"(waiting rids {waiting}{more}), active rids {active}, "
                f"pages_in_use={self.cache.allocator.pages_in_use}/"
                f"{self.cache.cfg.usable_pages}")

    def step(self) -> list[int]:
        """One continuous-batching iteration: sweep deadlines, admit and
        launch the prefill of (or swap-resume) joiners, launch one decode
        step for the whole batch behind them, then fetch the tokens of
        the decode that the PREVIOUS step launched and retire finishers,
        then fetch the first tokens of this step's prefills. A decode
        token is handed over (appended to ``req.generated``, counted,
        traced) one ``step()`` after the step that launched it; a
        prefill's first token in the step that completed the prefill.
        Returns the request ids
        whose finish this step saw (those that a drain between two steps
        finished among them). Injected faults retire only the requests
        they name; everything else keeps being served.

        Under ``debug_checks`` the step body runs inside a SyncTally (host
        syncs accumulate into ``serving_analysis_host_syncs_total``) and is
        followed by a ``PagedKVCache.check_invariants()`` sweep; the
        CompileGuards are strict, so an unexpected retrace or donation
        misuse raises instead of silently recompiling.

        With tracing on, the whole call is the ``serve.step`` span of the
        profiler's trace (module docstring, "Goodput attribution")."""
        att = self._attr
        att.enter_step(self._step_idx)
        try:
            return self._step_and_account()
        finally:
            att.exit_step()  # closes serve.account, then serve.step

    def _step_and_account(self) -> list[int]:
        """The schedule (``_step``), then the step's accounts: the state
        roll-up and the step's record (``_close_record``), the debug
        sweep, the timeline, the watchdogs, the flight recorder's edge
        and the SLO controller."""
        debug = self.config.debug_checks
        syncs = None
        try:
            if debug:
                with SyncTally() as tally:
                    finished, counts = self._step()
                self._host_syncs += tally.count
                syncs = tally.count
            else:
                finished, counts = self._step()
            finished = self._close_record(finished, counts)
            if debug:
                self.cache.check_invariants()
        except Exception as e:
            # engine-fatal: flush the half-built step into the timeline
            # ring and dump the flight record BEFORE re-raising — the
            # black box must survive the crash it exists to explain
            self._on_fatal(e)
            raise
        # from here to the end of step() is the rest of serve.account
        # (opened in _close_record): in the profiler's trace only, the
        # step's record is closed
        retraces = sum(g.retraces for g in
                       (*self.guards.values(), *self.cache.guards.values()))
        # the counters are pre-seeded at 0, so the non-debug hot loop only
        # pays the two monitor stat_sets when something actually changed
        if debug or retraces != self._retraces_emitted:
            self.metrics.on_analysis(retraces=retraces,
                                     host_syncs=self._host_syncs)
            self._retraces_emitted = retraces
        # obs: the step record is appended HERE (not in _step) so the
        # debug-mode sync tally covers the whole step body it reports on
        if self._timeline is not None and self._step_stats is not None:
            st, self._step_stats = self._step_stats, None
            stall_s = self._close_stalls(st)
            record = StepRecord(host_syncs=syncs, **st)
            self._timeline.append(record)
            self.metrics.observe_step(record.duration, st["batch"])
            # the step's seconds, over whatever window a reader
            # differences them: the step, its spans, the host's part, the
            # part that was no stall (serving_step_*_seconds_total)
            self.metrics.on_step_seconds(record.duration, record.span_s,
                                         stall_s)
            # per-phase attribution into the serving_step_phase_s{phase=}
            # family (zero-time phases stay unobserved — the record keeps
            # the exact split)
            for phase, secs in record.phase_s.items():
                if secs > 0:
                    self.metrics.on_phase(phase, secs)
            # anomaly watchdogs: edge-triggered rules over the step
            # record + already-host-resident counter totals
            if self._watchdog is not None:
                for alert in self._watchdog.on_step(
                        record, self._watchdog_counters(retraces)):
                    self.metrics.on_alert(alert.rule)
        # a step that retired a request FAILED (injected or real fault)
        # auto-dumps the flight record — every -m faults scenario doubles
        # as a recorder test; int compare on the no-failure path
        if self._failed_count != self._failed_dumped:
            self._failed_dumped = self._failed_count
            self._flight_auto("request-failure")
        # SLO-adaptive admission: windowed p99s over the histograms just
        # fed above — pure host-side integer reads, zero device syncs
        if self._slo is not None:
            change = self._slo.on_step()
            if change is not None:
                old, new = change
                self.metrics.on_chunk_limit(new, throttled=new < old)
        return finished

    def _close_stalls(self, st: dict) -> float:
        """The stall watch at a step's close: the records of the blocking
        spans flagged in this step go into the step record's ``extra``
        (``st``: the record's fields) as they are, and each is completed
        in place, counted and put in the ``stalls`` ring once the
        sampler's evidence for it is there (this step or one of the
        next). Returns the step's stall seconds."""
        stall_s, flagged, done = self._stalls.close_step(st["t_end"])
        if flagged:
            st["extra"] = dict(st.get("extra", ()), stalls=flagged)
        for rec in done:
            self.metrics.on_stall(rec["held_by"], 1e-3 * rec["excess_ms"])
        return stall_s

    def _settle_stalls(self) -> None:
        """Between two steps (a flight record, ``stalls``): complete the
        records whose evidence has come since the last step's close."""
        if self._stalls is not None:
            self._close_stalls({"t_end": self.now()})

    def _close_record(self, finished: list, counts: dict) -> list[int]:
        """After the schedule: open ``serve.account`` (the obs layer's
        own cost per step; it outlives the record and ends with
        ``step()``), roll the cache's and the scheduler's state up into
        the gauges, and close the step's record into ``_step_stats`` for
        ``_step_and_account`` to append."""
        att = self._attr
        att.account()
        cs = self.cache.stats()
        self.metrics.on_state(
            queue_depth=self.scheduler.queue_depth,
            active=len(self.scheduler.running),
            pages_used=cs["pages_in_use"],
            usable_pages=cs["usable_pages"],
            shared_pages=cs["shared_pages"],
            cached_pages=cs["reclaimable_pages"],
            cow_copies=cs["cow_copies"],
            evictions=cs["evictions"],
            host_tier_pages=cs["host_tier_pages"],
            host_tier_bytes=cs["host_tier_bytes"],
            host_tier_hits=cs["host_tier_hits"],
            host_tier_spills=cs["host_tier_spills"],
            host_tier_restores=cs["host_tier_restores"])
        if self._drained_finished:
            finished = self._take_drained() + finished
        if self._timeline is not None:
            # close the attribution: the residual (the roll-up above, this
            # very bookkeeping) lands in "other", and the phase dict sums
            # to t_end - t_start exactly by the mark construction
            t_end, phase_s = att.finish()
            self._step_stats = dict(
                counts, t_start=att.t0, t_end=t_end,
                finished=len(finished),
                queue_depth=self.scheduler.queue_depth,
                pages_in_use=cs["pages_in_use"],
                phase_s=phase_s, span_s=att.span_s)
        return finished

    def _step(self) -> tuple[list[int], dict]:
        """The schedule of one step: sweep and admit; seat or prefill
        what was admitted; advance the chunked prefills; fault sites and
        page pressure; decode or verify. Returns (the requests whose
        finish it saw, the step's counts for its record)."""
        # the ONLY injector read of the step (pinned by a test): the
        # uninstalled path costs one attribute lookup and None-checks
        inj = self._fault_injector
        step_idx = self._step_idx
        self._now_step = step_idx  # the restore_fail probe reads this
        self._step_idx += 1
        if inj is not None:
            slow = inj.hit("slow_step", step=step_idx)
            if slow is not None:
                self._skew += slow.delay_s

        # goodput attribution: every boundary below is ONE site that
        # writes both records (obs/attribution.py) — a serve.* span in
        # the profiler's trace and seconds on the engine clock; the
        # phases' seconds sum EXACTLY to the step's wall time
        att = self._attr
        preempt0 = self.scheduler.preemption_count
        finished_now = []
        with att.span("admit", queue_depth=self.scheduler.queue_depth):
            self._sweep_deadlines()
            # the step's record opens here (after the sweep, as it always
            # has: a deadline is read off the clock before the record is)
            att.begin()
            # a paused engine (run(budget_s=) drain) admits no NEWCOMERS,
            # but still resumes preemption victims — they are in-flight
            # work. Under SLO degradation, warm prefix-cache waiters jump
            # cold ones (their uncached tail barely touches the throttled
            # chunk budget).
            admitted = self.scheduler.admit(
                resume_only=self.admit_paused,
                prefer_cached=self._slo is not None and self._slo.degraded)
            # a failed host-tier restore (restore_fail injection or a real
            # scatter error) aborted that request's admission cleanly —
            # the stale tier entries are dropped, the pool state is the
            # pre-admit state: retire it FAILED and keep serving everyone
            # else
            for req, err in self.scheduler.pop_restore_failures():
                self._fail(req, err)
        n_prefills = 0
        for req in admitted:
            n_prefills += self._seat_or_prefill(req, inj, step_idx,
                                                finished_now)
        n_chunks, done = self._advance_chunks(inj, step_idx, finished_now)
        n_prefills += done

        # injected faults + decode-page pressure: preemption, swap-out and
        # eviction sweeps all happen in this window
        with att.span("evict"):
            if inj is not None:
                self._inject_decode_faults(inj, step_idx)
            # every slot's next query is one position on since the last
            # launch: a window page may have fallen behind it
            self._release_behind((int(slot), int(self._ctx[slot]))
                                 for slot in np.nonzero(self._active)[0])
            for req, slot in self.scheduler.ensure_decode_pages():
                self._preempt_one(req, slot)

        n_active = n_accepted = 0
        if self._active.any() or self._inflight is not None:
            if self._spec is not None:
                # speculative decoding: the verify step replaces plain
                # decode wholesale — one batched K+1-token ragged pass,
                # one packed fetch, 1..K+1 tokens emitted per slot
                with att.span("verify", batch=int(self._active.sum())):
                    n_active, n_accepted = self._verify_phase(finished_now)
            else:
                n_active = self._decode_phase(finished_now)
        return finished_now, {
            "step": step_idx, "admitted": len(admitted),
            "prefills": n_prefills, "chunks": n_chunks, "batch": n_active,
            "accepted": n_accepted,
            "preemptions": self.scheduler.preemption_count - preempt0}

    def _seat_or_prefill(self, req: Request, inj, step_idx: int,
                         finished_now: list) -> bool:
        """What an admitted request does in the step that admitted it. A
        swap-resume (``admit`` restored its KV) takes its seat. Anything
        else starts its prefill from the prefix-cache hit that the
        admission mapped: whole, here, inside its ``serve.prefill`` span;
        or, under ``chunk_size``, held PREFILLING for ``_advance_chunks``
        to stream. True when a prefill completed."""
        att = self._attr
        if req.generated:  # swap-resume: there is no prefill here for
            self._seat(req)                     # prefill_fail to hit
            self._swapped_in(req, len(req.generated))
            att.mark("swap")  # seconds only: a few host writes
            return False
        if inj is not None and \
                inj.hit("prefill_fail", step=step_idx, rid=req.rid):
            # consulted before the jitted prefill touches the pools:
            # undoing the admission IS the pre-step state, minus req
            self._fail_injected("prefill_fail", step_idx, req)
            att.mark("admit")
            return False
        chunked = bool(self.config.chunk_size)
        if req.resumed_from_swap:
            # a mid-prefill swap victim: its restored pages hold
            # prefilled_tokens of KV — chunking continues there, no second
            # prefill_start (the trace shows the swap)
            self._swapped_in(req, req.prefilled_tokens)
        else:
            # cold or recompute-readmitted: start (over) from the
            # prefix-cache hit the admission just mapped
            req.prefilled_tokens = req.prefix_hit_tokens = req.cached_tokens
            # a chunked prefill's start is its admission; a whole one's
            # is stamped at its launch (_prefill)
            if chunked and (tr := self._tracer) is not None:
                tr.event(req.rid, "prefill_start",
                         tokens=req.prompt_len - req.prefilled_tokens,
                         cached=req.cached_tokens, chunked=True)
        if chunked:
            # hold the slot in PREFILLING and let the chunk phase stream
            # the prompt, chunk_size tokens per step. fresh=True spares
            # the in-flight prefill from preemption while any decoded
            # victim exists.
            req.state = PREFILLING
            req.fresh = True
            att.mark("admit")  # PREFILLING handoff is admission
            return False
        # only the uncached tail is prefilled, padded to the smallest
        # bucket that holds it. This iteration's interval is this
        # request's prefill (a failed attempt's time too).
        cached = req.prefilled_tokens
        tail = req.prompt_len - cached
        with att.span("prefill", rid=req.rid, cached=cached, tail=tail,
                      bucket=self._prefill_program(tail).tokens):
            return self._prefill(req, tail, finished_now)

    def _swapped_in(self, req: Request, tokens: int) -> None:
        """Account a swap-resume: the victim is back with ``tokens`` of
        its own (generated, or prefilled so far) intact."""
        req.resumed_from_swap = False
        self.metrics.on_swap_in()
        tr = self._tracer
        if tr is not None:
            tr.event(req.rid, "swap_in", tokens=tokens)
            tr.event(req.rid, "resumed", tokens=tokens)

    def _advance_chunks(self, inj, step_idx: int,
                        finished_now: list) -> tuple[int, int]:
        """The chunked prefill phase: every PREFILLING request advances
        one chunk through the prefill path, oldest admitted first, capped
        at the SLO controller's chunks-per-step limit. Decode for the
        running batch proceeds in this same step — a whale prompt cannot
        monopolize an iteration. Returns (chunks launched, prefills
        completed)."""
        cfg = self.config
        if not cfg.chunk_size:
            return 0, 0
        limit = (self._slo.chunk_limit if self._slo is not None
                 else cfg.max_batch)
        prefilling = sorted(
            (r for r in self.scheduler.running.values()
             if r.state == PREFILLING),
            key=lambda r: r.admit_seq)[:limit]
        if not prefilling:
            return 0, 0
        n_chunks = done = 0
        with self._attr.span("chunk_prefill", chunks=len(prefilling)):
            for req in prefilling:
                if inj is not None and inj.hit(
                        "chunk_fail", step=step_idx, rid=req.rid):
                    # before the chunk touches the pools: the partial
                    # prefill's pages drain with the retirement,
                    # survivors keep prefilling / decoding this very step
                    self._fail_injected("chunk_fail", step_idx, req)
                    continue
                n_chunks += 1
                done += self._prefill(
                    req, min(cfg.chunk_size,
                             req.prompt_len - req.prefilled_tokens),
                    finished_now)
        return n_chunks, done

    def _prefill(self, req: Request, n: int, finished_now: list) -> bool:
        """THE prefill path: advance this request's prefill by ``n``
        prompt tokens from ``req.prefilled_tokens`` through the prefill
        program of the smallest pad bucket that holds them — the queries
        enter at ``ctx_lens = tokens already resident``, the ragged
        contract a prefix-cache tail and a chunk share. A whole tail
        (``chunk_size == 0``) is the one chunk that is final. A chunk
        that is not final never touches the host: its sampled token (and
        its model counters) stay on the device, unfetched, so the
        sync-free certification holds (one fetch per decode step + one
        per COMPLETED prefill). A launch that completes the prompt leaves
        its token where the decode launch behind it reads it (the
        program's output becomes ``_prev_toks``), seats the request with
        that token in flight, and queues the token's fetch
        (``_unfetched``) for the decode phase to make behind its launch:
        nothing here waits for the device. Where the engine wants the
        token on the host at once (speculation, ``debug_checks``) the
        same fetch is made here, blocking. A failure of the request's own
        retires it FAILED (engine-fatal ones raise). True when the
        prefill completed."""
        att, tr = self._attr, self._tracer
        chunked = bool(self.config.chunk_size)
        start = req.prefilled_tokens
        final = start + n >= req.prompt_len
        prog = self._prefill_program(n)
        bucket = prog.tokens
        with att.span("prefill.upload", awaited=self._prev_toks,
                      rid=req.rid, bytes=4 * bucket + 16
                      + self.cache.tables[..., req.slot, :].nbytes):
            args = self._prefill_args(prog, req.slot, req.rid,
                                      req.prompt[start:start + n], start)
        if tr is not None and not chunked:  # a chunked one: at admission
            tr.event(req.rid, "prefill_start", tokens=n, cached=start,
                     bucket=bucket)
        out = self._launch(prog, args, start, tokens=n, isolate=req,
                           rid=req.rid)
        if out is None:
            return False
        req.prefilled_tokens = start + n
        # the launch that read them is dispatched: what lies behind the
        # window of the request's next query goes back to its group
        self._release_behind([(req.slot, start + n)])
        if chunked:
            self.metrics.on_prefill_chunk(n)
            # stamped AFTER the dispatch succeeded, so the trace's chunk
            # count, the Chrome-export chunk spans, and the
            # serving_prefill_chunks_total counter can never disagree
            # about a chunk whose jit call failed
            if tr is not None:
                tr.event(req.rid, "prefill_chunk", start=start, tokens=n,
                         bucket=bucket, final=final)
        if not final:
            return False
        # the sampled token is in the request's slot of the program's
        # output: the next launch's prev_toks. The request takes its seat
        # with that token in flight
        self._prev_toks = out
        req.tokens_in_flight += 1
        self._seat(req)
        first = _FirstToken(req, prog, out, n)
        if self._first_token_at_once:
            self._first_token(first, finished_now, overlapped=False)
        else:  # behind the decode launch that follows (_decode_phase)
            self._unfetched.append(first)
        return True

    def _first_token(self, first: _FirstToken, finished_now: list,
                     overlapped: bool) -> None:
        """Fetch a completed prefill's first token (its ONE sanctioned
        device->host sync) and hand it over: append, count, trace, index
        the prompt's pages, retire a request that finishes with it.
        ``overlapped``: a decode was launched behind the prefill before
        this fetch, so the fetch's wait and tail lie under that decode
        (``serving_prefill_overlapped_total``)."""
        req, prog, out, n = first
        tr = self._tracer
        chunked = bool(self.config.chunk_size)
        slot = req.slot
        with self._attr.span("prefill.fetch", awaited=out, rid=req.rid):
            tok = int(self._fetch(prog, out)[slot])
        req.tokens_in_flight -= 1
        req.generated.append(tok)
        req.tokens_emitted += 1
        self._last_tok[slot] = tok
        self._hist_sync(req)
        if tr is not None:
            # prefill_end IS first-token time: the prefill pass samples
            # the request's first output token from its last logit.
            # Accounting reads prefix_hit_tokens, not cached_tokens: a
            # mid-prefill swap restore zeroes the latter, but this
            # prefill attempt's cache hit still served those tokens
            tr.event(req.rid, "prefill_end",
                     tokens=req.prompt_len - req.prefix_hit_tokens)
            tr.event(req.rid, "first_token")
        # every full prompt page is resident: index it for reuse
        self.cache.register_prefix(slot, req.prompt)
        # chunks counted their tokens themselves
        self.metrics.on_prefill(0 if chunked else n, overlapped=overlapped)
        if self.config.enable_prefix_caching:
            if req.prefix_hit_tokens > 0:
                self.metrics.on_prefix_hit(req.prefix_hit_tokens)
            else:
                self.metrics.on_prefix_miss()
        self.metrics.on_tokens(1)
        if self._maybe_finish(req, tok):
            finished_now.append(req.rid)

    def _fetch_first_tokens(self, finished_now: list,
                            overlapped: bool) -> None:
        """Fetch and hand over the first token of every completed prefill
        that is still unfetched, in admission order."""
        while self._unfetched:
            self._first_token(self._unfetched.popleft(), finished_now,
                              overlapped)

    def _inject_decode_faults(self, inj, step_idx: int) -> None:
        """The armed injector's step-boundary consults before the decode
        (or verify) launches: ``decode_fail`` / ``verify_fail`` retire the
        named requests FAILED, ``pool_exhausted`` preempts a victim."""
        for slot in np.nonzero(self._active)[0]:
            req = self.scheduler.running.get(int(slot))
            if req is None or req.all_launched:
                continue  # no decode is launched for it: nothing to fail
            if inj.hit("decode_fail", step=step_idx, rid=req.rid):
                # before the decode launches: the failed request leaves
                # with every token it has (the drain may even finish it),
                # the rest of the batch decodes normally this very step
                self._drain("fault")
                if req.state == RUNNING:
                    self._fail_injected("decode_fail", step_idx, req)
                continue
            if self._spec is not None and \
                    inj.hit("verify_fail", step=step_idx, rid=req.rid):
                # before the verify dispatch: the faulted request
                # retires FAILED with its pages — including any
                # speculative over-reservation — draining via the
                # normal evict path (the draft proposer holds no
                # per-request state to clean); survivors verify this
                # very step
                self._fail_injected("verify_fail", step_idx, req)
        if self.scheduler.running and \
                inj.hit("pool_exhausted", step=step_idx):
            self._drain("fault")
            if self.scheduler.running:
                self._preempt_one(self.scheduler.pick_victim())

    def _release_behind(self, slots) -> None:
        """For each ``(slot, position of its next query)``: free the
        slot's window-group pages that this query and every later one
        cannot see (``PagedKVCache.release_behind``). The
        ``serve.window_release`` span and
        ``serving_kv_window_pages_released_total``; one attribute check
        for a model without a window group."""
        if not self.cache.has_windows:
            return
        with self._attr.span("window_release"):
            freed = sum(self.cache.release_behind(slot, pos)
                        for slot, pos in slots)
        if freed:
            self.metrics.on_window_release(freed)

    def _count_attention_pages(self, ctx, s: int, tokens: int | None = None,
                               live_rows=None) -> None:
        """One launch's attention, counted on the host from the
        ``ctx_lens`` it uploads: the pages that hold what a row's
        ``tokens`` real new tokens attend to (``s`` but for a padded
        bucket; ``live_rows``: the rows that are real, all by default),
        and the pages the attention copies out of the pool for the ``s``
        queries of every row it runs, padding and dead slots too
        (``PagedCacheSpec.pages_staged``; a model that does not say is not
        counted)."""
        fn = self._pages_staged.get(s)
        if fn is None:
            make = self._cache_spec.pages_staged
            if make is None:
                return
            fn = self._pages_staged[s] = make(
                s, self.cache.cfg.pages_per_seq, self.config.page_size)
        ctx = np.atleast_1d(ctx)
        new = s if tokens is None else tokens
        if self._cache_spec.pages_live is not None:
            # over the layers that pages_staged counts, by their kinds
            live = self._cache_spec.pages_live(
                s, self.config.page_size)(ctx, new)
        else:
            live = -(-(ctx + new) // self.config.page_size)
        if live_rows is not None:
            live = live[live_rows]
        self.metrics.on_attention_pages(int(live.sum()), int(fn(ctx).sum()))

    # -------------------------------------- a step program: operands, launch
    def _prefill_program(self, n: int) -> _Program:
        """The prefill program of the smallest pad bucket that holds ``n``
        tokens."""
        return next(p for p in self._programs.values()
                    if p.phase == "prefill" and p.tokens >= n)

    def _prefill_args(self, prog: _Program, slot: int, rid: int, ids,
                      start: int) -> tuple:
        """The prefill program's operands: ``ids`` (the prompt tokens this
        launch computes, for the request in ``slot``) right-padded to the
        program's bucket, their count, the tokens already resident
        (``start``: the queries enter there), the slot's page-table row,
        the request id (its PRNG stream), the slot itself and the last
        launch's tokens where they are, on the device."""
        padded = np.full(prog.tokens, self.config.pad_token_id, np.int32)
        padded[:len(ids)] = ids
        return (self._p, self.cache.pools, jnp.asarray(padded),
                jnp.asarray(len(ids), jnp.int32),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(self.cache.tables[..., slot, :]),
                jnp.asarray(rid, jnp.int32), jnp.asarray(slot, jnp.int32),
                self._prev_toks)

    def _decode_args(self, active=None, override=None) -> tuple:
        """The decode program's operands as a launch uploads them: the
        whole page table and the five per-slot vectors from the host, and
        the previous launch's tokens where they are, on the device. The
        defaults are a drained engine's launch: every active slot with the
        last token the host knows."""
        # a private copy of each: the transfer may read its source after
        # jnp.asarray returns, and the host writes _ctx and _gen right
        # after the dispatch, the page table and the rest while it runs
        up = lambda a: jnp.asarray(a.copy())  # noqa: E731
        return (self._p, self.cache.pools, up(self.cache.tables),
                up(self._ctx), self._prev_toks,
                up(self._last_tok if override is None else override),
                up(self._active if active is None else active),
                up(self._rids), up(self._gen))

    def _verify_args(self) -> tuple:
        """The verify program's operands: the page table, the five
        per-slot vectors, the proposers' token history and, for the draft
        proposer, its parameters. No copies: the verify phase fetches its
        own launch before the host writes any of them."""
        args = (self._p, self.cache.pools,
                jnp.asarray(self.cache.tables),
                jnp.asarray(self._ctx), jnp.asarray(self._last_tok),
                jnp.asarray(self._active), jnp.asarray(self._rids),
                jnp.asarray(self._gen), jnp.asarray(self._spec_hist()))
        if self._spec.method == "draft":
            args += (self._draft_p,)
        return args

    def _launch(self, prog: _Program, args: tuple, ctx, tokens=None,
                live_rows=None, isolate: Request | None = None, **attrs):
        """THE launch of a compiled step program; what is true of every
        launch is written here. Audit it under ``debug_checks``, dispatch
        it inside its ``serve.<phase>.dispatch`` span (``attrs``), rebind
        the donated pools and count the launch's attention pages from the
        ``ctx`` (ctx_lens) it uploaded (``tokens``, ``live_rows``: as
        ``_count_attention_pages`` takes them). Returns the program's
        output, still on the device. A failed dispatch raises — but for
        the launch of one request (``isolate``) whose failure is its own:
        that request retires FAILED, None is returned and the rest keep
        being served."""
        # read at the launch: a test, and the benchmark's correctness
        # check, put a callable of their own in the guard's place
        guard = getattr(self, f"_{prog.phase}_jit")
        if self.config.debug_checks:
            self._audit_step(prog, guard, args)
        try:
            with self._attr.span(prog.phase + ".dispatch",
                                 awaited=self._prev_toks, **attrs):
                pools, out = guard(*args)
        except Exception as e:  # noqa: BLE001 — isolate the request
            # a strict-guard refusal is an AUDIT failure — the contract
            # debug_checks exists to surface — not a request's fault; and
            # once donation has consumed the pools every sequence's KV is
            # gone, so "retire one request and keep serving" would hand
            # the rest deleted buffers: both engine-fatal
            if isolate is None \
                    or isinstance(e, (RetraceError, DonationViolation)) \
                    or any(arr.is_deleted() for pl in self.cache.pools
                           for arr in pl.values()):
                raise
            self._fail(isolate, e)
            return None
        self.cache.pools = pools
        self._count_attention_pages(ctx, prog.tokens, tokens, live_rows)
        return out

    def _fetch(self, prog: _Program, out) -> np.ndarray:
        """THE device->host copy of a step program's output, inside the
        caller's ``*.fetch`` span: the one sanctioned sync (PT005 polices
        this function; a bare ``int()`` coercion would sync invisibly to
        the linter). Blocks for what is left of the launch. What the model
        counted rides behind the tokens in the same array: split off here
        and fed to the metrics. Returns the launch's tokens."""
        out = np.asarray(out)  # lint: disable=PT005
        if prog.counters:
            self.metrics.on_model_counters(self._cache_spec.counters,
                                           out[-prog.counters:])
            out = out[:-prog.counters]
        return out

    def _decode_phase(self, finished_now: list) -> int:
        """Launch one decode step for the whole batch, THEN fetch and emit
        the tokens of the decode that the previous step launched, THEN
        fetch the first token of each prefill this step completed: the
        ``serve.decode`` span and its parts (upload, dispatch, the fetch
        of the previous launch and the per-slot bookkeeping of those
        tokens, a ``prefill.fetch`` a completed prefill: one sanctioned
        device->host sync a decode step and one a completed prefill, as
        ever). What does not depend on a token's value advances at the
        launch (``_ctx``, ``_gen``; the page was reserved by
        ``ensure_decode_pages``), what does advances at the fetch, one
        step later. A slot whose last token the host has not seen (a
        decode's or a prefill's, in flight) takes it from ``_prev_toks``
        on the device (``override`` -1); a slot whose last token is in
        flight already (finish by length) is left out of the launch; with
        nothing to launch the phase only fetches. Returns the slots
        launched."""
        att = self._attr
        prev = self._inflight
        active = np.zeros_like(self._active)
        override = self._last_tok.copy()
        launched = []
        for slot in np.nonzero(self._active)[0]:
            req = self.scheduler.running[int(slot)]
            if req.all_launched:
                continue  # finish by length: it retires at the fetch below
            active[slot] = True
            if req.tokens_in_flight:
                override[slot] = -1  # its last token is in _prev_toks
            launched.append((int(slot), req))
        with att.span("decode", batch=len(launched)):
            if launched:
                with att.span("decode.upload", awaited=self._prev_toks,
                              bytes=self._decode_upload_bytes):
                    args = self._decode_args(active, override)
                toks = self._launch(self._programs["decode"], args,
                                    self._ctx, live_rows=active)
                self._prev_toks = toks
                self.metrics.on_decode_step(overlapped=prev is not None)
                if self._layer_group is not None:
                    self.metrics.on_kv_residency(*self.cache.residency())
                for slot, req in launched:
                    req.tokens_in_flight += 1
                    self._ctx[slot] += 1
                    self._gen[slot] += 1
                self._inflight = (toks, self._now_step, launched)
            else:
                self._inflight = None
            if prev is not None:
                self._fetch_and_emit(prev, finished_now)
            # this step's prefills, behind its launch: their wait and tail
            # lie under the decode just launched
            self._fetch_first_tokens(finished_now, overlapped=bool(launched))
            if self.config.debug_checks:
                # the invariant sweep and the sync tally that follow read
                # a whole step: nothing stays in flight under debug_checks
                self._drain("debug_checks")
        return len(launched)

    def _fetch_and_emit(self, inflight: tuple, finished_now: list) -> None:
        """Fetch one decode launch's tokens and hand them over: append,
        count, trace, retire finishers. A slot whose request has left it
        since the launch (finish by EOS is known one step late) computed a
        surplus token: dropped here, never appended, counted or indexed;
        its KV write went to a page of the request's own, freed with it."""
        att, tr = self._attr, self._tracer
        toks, step, launched = inflight
        with att.span("decode.fetch", awaited=toks, of_step=step):
            try:
                toks = self._fetch(self._programs["decode"], toks)
            except Exception as e:
                e.add_note(f"raised at the fetch of the decode that step "
                           f"{step} launched")
                raise
        n_new = 0
        with att.span("decode.emit"):
            for slot, req in launched:
                req.tokens_in_flight -= 1
                if self.scheduler.running.get(slot) is not req:
                    continue
                tok = int(toks[slot])
                req.generated.append(tok)
                req.tokens_emitted += 1
                req.fresh = False  # it has decoded: preemptible now
                self._last_tok[slot] = tok
                n_new += 1
                if tr is not None and \
                        len(req.generated) % tr.mark_every == 0:
                    tr.event(req.rid, "decode_mark",
                             tokens=len(req.generated))
                if self._maybe_finish(req, tok):
                    finished_now.append(req.rid)
            self.metrics.on_tokens(n_new)

    def _drain(self, reason: str) -> bool:
        """Fetch and emit what is in flight, now (the first tokens of
        the prefills this step completed and has not fetched, then the
        decode): for a site that needs the host's view whole before it
        acts (``DRAIN_REASONS``). A drained engine is the engine that
        fetched every step. Requests it finishes are reported by the step
        that is running, or the next. True when something was in
        flight."""
        inflight, self._inflight = self._inflight, None
        if inflight is None and not self._unfetched:
            return False
        with self._attr.span("drain", reason=reason):
            # a first token before the decode token that follows it
            self._fetch_first_tokens(self._drained_finished,
                                     overlapped=False)
            if inflight is not None:
                self._fetch_and_emit(inflight, self._drained_finished)
        self.metrics.on_decode_drain(reason)
        return True

    def _take_drained(self) -> list[int]:
        done, self._drained_finished = self._drained_finished, []
        return done

    def _verify_phase(self, finished_now: list) -> tuple[int, int]:
        """The speculative twin of the decode phase, inside the caller's
        ``serve.verify`` span: ONE verify launch for the whole batch, ONE
        packed fetch of that same launch (the decode token fetch, renamed
        — the SyncTally formula is unchanged), then each slot emits its
        accepted candidates plus the target's own next token (1..K+1
        tokens) and the pages its rejected span over-reserved recycle
        through the refcounted allocator. Returns (active slots,
        candidates accepted)."""
        K = self._spec.depth
        tr = self._tracer
        prog = self._programs["verify"]
        out = self._launch(prog, self._verify_args(), self._ctx,
                           live_rows=self._active)
        # the step's ONE sanctioned device->host sync: the packed (target
        # tokens, accept count) fetch
        with self._attr.span("verify.fetch", awaited=out):
            packed = self._fetch(prog, out)
        self.metrics.on_decode_step()
        n_slots = n_new = n_accepted = 0
        for slot in np.nonzero(self._active)[0]:
            req = self.scheduler.running[int(slot)]
            a = int(packed[slot, K + 1])
            n_slots += 1
            n_accepted += a
            req.fresh = False
            if tr is not None:
                tr.event(req.rid, "spec_verify", proposed=K, accepted=a)
            emitted = 0
            finished = False
            for tok in packed[slot, :a + 1]:
                # the accepted candidates ARE the target's tokens at
                # positions 0..a-1, position a is the target's own next
                # token after the accepted span — emit them in order,
                # stopping at eos/budget exactly like sequential decode
                tok = int(tok)
                req.generated.append(tok)
                req.tokens_emitted += 1
                emitted += 1
                if tr is not None and \
                        len(req.generated) % tr.mark_every == 0:
                    tr.event(req.rid, "decode_mark",
                             tokens=len(req.generated))
                if self._maybe_finish(req, tok):
                    finished_now.append(req.rid)
                    finished = True
                    break
            n_new += emitted
            if finished:
                continue
            self._ctx[slot] += emitted
            self._last_tok[slot] = req.generated[-1]
            self._gen[slot] += emitted
            # speculative rewind: pages reserved for the rejected span
            # return to the allocator now that the accept count is known
            self.cache.shrink(slot, req.tokens_resident)
            # history append: only the emitted span is new — the full-row
            # rebuild (_hist_sync) runs only at prefill-end/swap-in, so
            # the hot loop's host work stays O(emitted), not O(seq_len)
            self._hist[slot, req.tokens_resident - emitted:
                       req.tokens_resident] = req.generated[-emitted:]
        self.metrics.on_tokens(n_new)
        self.metrics.on_spec(proposed=K * n_slots, accepted=n_accepted)
        return n_slots, n_accepted

    def run(self, max_steps: int = 100000,
            budget_s: float | None = None) -> dict[int, np.ndarray]:
        """Drive step() until every queued request finished; returns
        {request_id: [prompt + generated] token array} for the requests that
        finished during THIS call (not historical completions).

        ``budget_s`` is a wall-clock budget on engine time (now()): when it
        elapses, admission pauses and the in-flight batch — including any
        preemption victims, which still resume while paused — drains
        gracefully; never-admitted requests stay queued for a later
        run()/step(). A caller-set ``admit_paused`` is honored the same way
        (drain and return) and survives the call. The step budget remains a
        hard backstop against a stuck engine."""
        steps = 0
        done: dict[int, np.ndarray] = {}
        stop_at = self.now() + budget_s if budget_s is not None else None
        paused_before = self.admit_paused
        try:
            while not self.scheduler.all_done:
                if stop_at is not None and self.now() >= stop_at:
                    self.admit_paused = True
                if self.admit_paused and not self.scheduler.running \
                        and not self.scheduler.inflight_waiting:
                    break  # drained: leave the queue for a later call
                for rid in self.step():
                    done[rid] = self._finished[rid]
                steps += 1
                if steps > max_steps:
                    err = RuntimeError(
                        f"serving loop exceeded {max_steps} steps without "
                        f"draining: {self._state_summary()}")
                    try:
                        # a wedged engine is exactly what the black box
                        # exists for: dump before the backstop raises
                        self._flight_auto("stuck-engine")
                    except Exception:  # noqa: BLE001 — backstop wins
                        pass
                    raise err
        finally:
            self.admit_paused = paused_before
        # a finish by EOS leaves its surplus launch in flight
        self._drain("run_end")
        for rid in self._take_drained():
            done[rid] = self._finished[rid]
        return done

    # -------------------------------------------------------- observability
    def _watchdog_counters(self, retraces: int) -> dict:
        """The monotonic totals the watchdog rules window over — every
        value already host-resident (the monitor registry is a python
        dict; zero device syncs)."""
        return {
            "retraces": retraces,
            "fallbacks": monitor.stat_get(
                "serving_pallas_fallback_total", 0),
            "proposed": monitor.stat_get(
                "serving_spec_proposed_tokens_total", 0),
            "accepted": monitor.stat_get(
                "serving_spec_accepted_tokens_total", 0),
            "evictions": monitor.stat_get("serving_prefix_evictions", 0),
            "spills": monitor.stat_get(
                "serving_host_tier_spills_total", 0),
            # slo_burn: the ledger's per-tenant (violations, retired)
            # monotonic totals — plain python ints off host dicts
            "tenant_slo": self._tenants.burn_totals()
            if self._tenants is not None else {},
        }

    def alerts(self) -> list:
        """The watchdog alert history (obs.alerts.Alert), oldest first —
        empty with tracing or watchdogs off."""
        return self._watchdog.alerts() if self._watchdog is not None else []

    def flight_record(self, reason: str = "manual") -> dict:
        """Assemble (but do not write) the black-box flight record
        (schema v2): the newest ``flight_record_steps`` step records,
        the alert history, a full gauge snapshot, the per-program
        hlocheck audit roll-ups, the per-request latency summaries, the
        per-tenant goodput roll-ups, and a bounded ring of wire
        journeys — schema-versioned, JSON-ready. Drains a decode in
        flight first: the record holds every token computed."""
        self._drain("flight_record")
        self._settle_stalls()
        cfg = self.config
        programs = {
            label: {"flops": r.flops, "peak_hbm_bytes": r.peak_bytes,
                    "collective_ops": len(r.collectives),
                    "host_transfers": len(r.host_transfers)}
            for label, r in self._hlo_audits.items()}
        return build_flight_record(
            reason=reason, now=self.now(), step=self._step_idx,
            config={"max_batch": cfg.max_batch,
                    "num_pages": cfg.num_pages,
                    "page_size": cfg.page_size,
                    "max_prompt_len": cfg.max_prompt_len,
                    "chunk_size": cfg.chunk_size,
                    "kv_dtype": cfg.kv_dtype,
                    "tensor_parallel": cfg.tensor_parallel,
                    "spec_depth": cfg.spec.depth if cfg.spec else 0,
                    "preemption_mode": cfg.preemption_mode,
                    "debug_checks": cfg.debug_checks},
            timeline=self._timeline, alerts=self.alerts(),
            gauges=self.metrics.snapshot(), programs=programs,
            requests=self.latency_summaries(),
            tenants=self.tenant_report() or {},
            # serialize only what the record will keep — a fatal-path
            # dump must be O(kept journeys), not O(trace_capacity)
            journeys=self._journeys.wire_records(
                limit=_MAX_FLIGHT_JOURNEYS)
            if self._journeys is not None else (),
            stalls=self.stalls, max_steps=cfg.flight_record_steps)

    def dump_flight_record(self, path, reason: str = "manual") -> dict:
        """Write the flight record as JSON to ``path``; returns it."""
        return _write_flight_record(path, self.flight_record(reason))

    def _flight_auto(self, reason: str) -> None:
        """The automatic dump (fatal paths, stuck-engine backstop, any
        FAILED retirement): records to ``last_flight_record`` always and
        to ``flight_record_path`` when configured."""
        rec = self.flight_record(reason)
        self.last_flight_record = rec
        if self.config.flight_record_path:
            _write_flight_record(self.config.flight_record_path, rec)

    def _on_fatal(self, exc: BaseException) -> None:
        """An exception is escaping the step body. Whatever the
        half-built step accumulated would die with the engine: close the
        open attribution into a partial StepRecord (counts unknowable —
        zeros — but timing, queue and page state are real, and ``extra``
        names the fatal), flush it into the ring, and dump the flight
        record. Best-effort: nothing here may mask the original
        exception."""
        try:
            self._drain("fatal")
        except Exception:  # noqa: BLE001 — the fetch may be what raised
            pass
        try:
            att = self._attr
            # a device error of decode k surfaces at its fetch in step
            # k+1: the note names the step that launched it
            fatal = {"fatal": "; ".join(
                [f"{type(exc).__name__}: {exc}",
                 *getattr(exc, "__notes__", ())])}
            if self._timeline is not None and att.open:
                t_end, phase_s = att.finish()
                st = dict(step=self._step_idx - 1, t_start=att.t0,
                          t_end=t_end, admitted=0, prefills=0, batch=0,
                          finished=0, preemptions=0,
                          queue_depth=self.scheduler.queue_depth,
                          pages_in_use=self.cache.allocator.pages_in_use,
                          phase_s=phase_s, span_s=att.span_s, extra=fatal)
                self._close_stalls(st)
                self._timeline.append(StepRecord(**st))
                self._step_stats = None
            elif self._timeline is not None and self._step_stats is not None:
                # _step completed (attribution closed, full stats built)
                # but a post-step debug sweep — check_invariants — raised
                # before step() could append the record: the step that
                # broke the engine must not be the one the black box
                # misses
                st, self._step_stats = self._step_stats, None
                st["extra"] = fatal
                self._close_stalls(st)
                self._timeline.append(StepRecord(**st))
            self._flight_auto(f"engine-fatal: {type(exc).__name__}")
        except Exception:  # noqa: BLE001 — the original fatal wins
            pass

    def _audit_donation(self, guard: CompileGuard, args) -> None:
        """debug_checks satellite: before a guarded step's FIRST trace,
        audit it at jaxpr level (analysis.donation_audit) with the real
        call's arguments — the wrapped impl and its ``donate_argnums``
        are read off the guard itself, so the audit can never
        desynchronize from what the jit actually donates. A donated leaf
        the computation never consumes can alias nothing into any output
        — a wrong ``donate_argnums`` that silently forfeits the in-place
        pool update — and raises DonationViolation naming the leaf.
        Identity pass-through reports are recorded
        (``engine._donation_audits``) but not fatal."""
        reports = donation_audit(guard.fn, guard.donate_argnums, *args)
        dead = [r for r in reports if "never consumed" in r]
        if dead:
            raise DonationViolation(
                f"donation audit of {guard.name!r} jitted step: "
                + "; ".join(dead))
        self._donation_audits[guard.name] = reports

    def _audit_step(self, prog: _Program, guard: CompileGuard,
                    args) -> None:
        """debug_checks: the pre-dispatch audits for one launch. The
        jaxpr-level donation audit runs once per GUARD (at its first
        trace); the hlocheck compiled-artifact audit runs once per
        COMPILED PROGRAM (per prefill bucket + decode, keyed by its label)
        — the step is AOT-lowered and its optimized HLO enforced against
        the single-chip budget: zero collectives, zero host transfers,
        every donated pool honored with input-output aliasing. Violations
        raise (engine-fatal — an audit failure is the contract
        debug_checks exists to surface, not a request fault); clean
        reports land in ``hlo_audits`` and the ``serving_hlo_*``
        metrics. One extra AOT compile per program, never a serving-path
        cost."""
        label = prog.label
        if not guard.traces:
            self._audit_donation(guard, args)
        if label in self._hlo_audits:
            return
        report = hlocheck.audit_guard(guard, args, name=label)
        report.enforce(self._step_budget(label))
        self._hlo_audits[label] = report
        self.metrics.on_hlo_audit(
            collective_ops=len(report.collectives),
            host_transfers=len(report.host_transfers),
            peak_hbm_bytes=report.peak_bytes, flops=report.flops)
        if self._tp is not None:
            # the EQuARX baseline gauges, fed straight from the census:
            # collective ops per step and collective bytes per token this
            # program advances (decode: max_batch tokens; prefill[N]: up
            # to N prompt tokens)
            n_tokens = prog.rows * prog.tokens
            self.metrics.on_tp_audit(
                collective_ops=len(report.collectives),
                bytes_per_token=report.collective_bytes / n_tokens,
                overlap_frac=report.overlap_frac)
            # meshcheck placement: attribute every collective to its mesh
            # axis on the declared topology (default: single-host over
            # the tp degree), classify ICI vs DCN, and feed the
            # per-medium gauges. A DECLARED topology is also enforced —
            # per-medium budget arms, zero-DCN binding when single-host —
            # so a misdeclared mesh fails here, not in production
            from ..analysis import meshcheck

            topology = self.config.mesh_topology
            if topology is None:
                topology = meshcheck.single_host_topology(self._tp.degree)
            mesh_report = meshcheck.analyze(
                report.collectives, topology, name=label)
            if self.config.mesh_topology is not None:
                budget = self._step_budget(label)
                if topology.cluster.n_hosts == 1:
                    budget = dataclasses.replace(
                        budget,
                        max_ici_bytes=budget.max_collective_bytes,
                        max_dcn_bytes=0, max_dcn_ops=0)
                mesh_report.check(budget)
            self.metrics.on_mesh_audit(
                ici_bytes_per_token=mesh_report.ici_bytes / n_tokens,
                dcn_bytes_per_token=mesh_report.dcn_bytes / n_tokens,
                predicted_s=mesh_report.predicted_s)

    def _step_shape(self, label: str) -> tuple[int, int]:
        """(rows, tokens a row) of the compiled program under an audit
        label, off its record — ``decode`` runs the whole batch one token
        wide, ``verify`` the whole batch depth + 1 tokens wide,
        ``prefill[N]`` one request N padded tokens wide."""
        prog = self._programs[label]
        return prog.rows, prog.tokens

    def _step_budget(self, label: str) -> hlocheck.CollectiveBudget:
        """The per-program hlocheck budget ``debug_checks`` enforces:
        single-chip steps certify at the all-zero SINGLE_CHIP budget;
        tensor-parallel steps at exactly the collectives their Megatron
        partitioning implies (2 all-reduces per block + 1 for the logits,
        byte-capped — serving/tp.py)."""
        if self._tp is None:
            return hlocheck.SINGLE_CHIP
        b, s = self._step_shape(label)
        itemsize = np.dtype(self._cache_spec.dtype).itemsize
        return self._tp.step_budget(batch=b, seq=s, itemsize=itemsize)

    @property
    def hlo_audits(self) -> dict:
        """Per-compiled-program hlocheck reports recorded under
        ``debug_checks`` — one per prefill pad bucket (``prefill[N]``)
        plus ``decode``. Empty with debug checks off."""
        return dict(self._hlo_audits)

    @property
    def stalls(self) -> list[dict]:
        """The newest 64 stall records (obs/stall.py), oldest first: each
        blocking wait (a ``*.fetch``, ``*.upload`` or ``*.dispatch`` span)
        that outlasted its name's norm, with the evidence of
        what held it and the one word ``held_by``. Empty with
        ``enable_tracing=False``."""
        self._settle_stalls()
        return list(self._stalls.ring) if self._stalls is not None else []

    def close(self) -> None:
        """End what the engine runs beside its caller's thread: the
        stall sampler. The engine serves on without it (a stall is still
        flagged, its record reads ``unsampled``); a collected engine's
        sampler ends by itself."""
        if self._stalls is not None:
            self._stalls.stop()

    @property
    def timeline(self) -> StepTimeline | None:
        """The bounded per-step ring (obs.StepTimeline); None when
        ``enable_tracing=False``."""
        return self._timeline

    def trace(self, rid: int):
        """The request's lifecycle trace (obs.RequestTrace) — live or
        retained-terminal — or None when tracing is off or the trace was
        evicted under the retention bound."""
        return self._tracer.get(rid) if self._tracer is not None else None

    def journey(self, rid: int):
        """The request's journey (obs.Journey) — hop list with engine-
        step refs, wire-exportable via ``.to_wire()`` — or None when
        tracing is off or the journey was evicted under the retention
        bound (the obs-off contract: None, never a raise)."""
        return self._journeys.get(rid) if self._journeys is not None \
            else None

    def journeys(self) -> list:
        """Every retained journey, oldest first (empty with tracing
        off)."""
        return self._journeys.journeys() if self._journeys is not None \
            else []

    def tenant_report(self) -> dict | None:
        """The per-tenant goodput roll-up (obs.TenantLedger.rollup
        merged with the observed per-tenant p99s) — the flight record's
        ``tenants`` section and the CLI ``--tenant-table`` input. None
        with tracing off (the obs-off contract)."""
        if self._tenants is None:
            return None
        return self._tenants.rollup(self.metrics.tenant_hists)

    def traces(self) -> list:
        """Every retained RequestTrace, oldest first (empty with tracing
        off)."""
        return self._tracer.traces() if self._tracer is not None else []

    def latency_summaries(self) -> list[dict]:
        """Per-request latency decompositions (queue_wait / prefill_time /
        ttft / tpot / e2e + state/tokens/preemptions) for every retained
        trace."""
        return self._tracer.summaries() if self._tracer is not None else []

    def export_chrome_trace(self, path=None) -> dict:
        """Chrome ``trace_event`` JSON of every retained request trace
        plus the engine step timeline — with per-step counter tracks
        (pages_in_use / batch / queue_depth), an instant per watchdog
        alert, and one track per tenant of retirement instants —
        loadable in chrome://tracing and ui.perfetto.dev. Writes to
        ``path`` when given; returns the document either way
        (empty-track document with tracing off)."""
        traces = self.traces()
        alerts = self.alerts()
        journeys = self.journeys()
        if path is not None:
            return write_chrome_trace(path, traces, self._timeline,
                                      alerts, journeys)
        return chrome_trace(traces, self._timeline, alerts, journeys)

    def result(self, rid: int) -> np.ndarray:
        return self._finished[rid]

    def pop_finished(self) -> dict[int, np.ndarray]:
        """Drain and return every completed output. A long-lived server must
        call this (or ``result`` + its own eviction) — ``_finished`` retains
        outputs until drained, so never draining grows memory with every
        request ever served."""
        done, self._finished = self._finished, {}
        return done

    def pop_retired(self) -> dict[int, Request]:
        """Drain and return every cancelled/expired/failed/shed request —
        the non-completion analog of pop_finished(), with the same long-
        lived-server memory contract."""
        done, self._retired = self._retired, {}
        return done

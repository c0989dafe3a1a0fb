"""Serving observability, surfaced through utils/monitor.py.

Every gauge/counter is a ``serving_*`` stat in the process-wide monitor
registry (so existing stat tooling and the profiler's host-trace view see
them with no new plumbing):

- serving_queue_depth       gauge: waiting requests
- serving_active_requests   gauge: running decode slots
- serving_page_utilization  gauge: used / usable pages (0..1)
- serving_tokens_total      counter: generated tokens (monotonic)
- serving_tokens_per_sec    gauge: windowed decode throughput
- serving_prefills_total    counter: prefills completed (first token
                            handed over)
- serving_prefill_overlapped_total counter: completed prefills whose
                            first token was fetched after the decode
                            behind them was launched (the fetch's wait
                            and tail lay under that decode and not in
                            series with its upload and dispatch); over
                            serving_prefills_total, the overlapped share.
                            A speculative or debug_checks engine, which
                            fetches a first token at once, and a prefill
                            that a drain caught count none
- serving_prefill_tokens_total counter: tokens actually prefilled (a prefix
                            cache hit prefills only the uncached tail, so
                            this is the FLOPs-weighted prefill cost)
- serving_decode_steps      counter: decode programs launched
- serving_decode_overlapped_total counter: launches made while the
                            previous launch's tokens were still to be
                            fetched (the host's work between two launches
                            ran under a decode program); over
                            serving_decode_steps, the overlapped share
- serving_decode_drains_total{reason=} counter family: decodes in flight
                            fetched before the next launch, by the site
                            that needed the host's view whole (preempt /
                            cancel / deadline / fault / debug_checks /
                            flight_record / fatal / run_end)
- serving_attention_pages_live_total counter: pool pages that hold a
                            context the queries of a launch attend to,
                            ``ceil((ctx + s) / page_size)`` summed over
                            the launch's real rows (decode, prefill,
                            chunk and verify launches; a layer's worth)
- serving_attention_pages_staged_total counter: pool pages the attention
                            of those launches copies out of the pool, as
                            its dispatch stages them: the ragged kernel's
                            whole chunks up to the last position a query
                            tile sees, per query tile (a prefill re-reads
                            its prefix a tile), every row it runs; the
                            table's width a row where the composite
                            gathers. live / staged is the share of the
                            attention's KV traffic that a context needs
- serving_kv_window_pages_released_total counter: pages of a window page
                            group (kv_cache.PageGroup) returned to their
                            allocator behind the window of their slot's
                            next query
- serving_kv_resident_page_layers_total,
  serving_kv_one_lifetime_page_layers_total counters: at each decode
                            launch of a model of several page groups, the
                            pages in use in each group times its layers,
                            and what ONE table and lifetime would hold for
                            the same contexts (their pages times every
                            layer that pages); the first over the second
                            is the share of the one-lifetime cache that
                            the groups keep resident
- serving_preemptions_total counter

Resilience counters (pre-seeded to 0 so they always appear in snapshots):

- serving_rejected   admissions refused by the bounded queue (reject policy)
- serving_shed       requests evicted from a full queue (shed-oldest policy)
- serving_expired    requests retired by a deadline sweep
- serving_cancelled  requests retired by engine.cancel()
- serving_failed     requests retired FAILED (injected or real step fault)
- serving_swap_outs  swap-mode preemptions (KV paged out to host memory)
- serving_swap_ins   swapped requests restored and resumed

Prefix-cache counters/gauges (pre-seeded like the resilience set):

- serving_prefix_hits          admissions that reused >= 1 cached page
- serving_prefix_misses        cold admissions with caching enabled
- serving_prefix_tokens_saved  prompt tokens served from cache, not prefill
- serving_prefix_shared_pages  gauge: pages mapped by > 1 page table now
- serving_prefix_cached_pages  gauge: refcount-0 reusable pages resident
- serving_prefix_cow_copies    shared pages privatized before a write
- serving_prefix_evictions     reusable pages reclaimed under pool pressure

KV quantization + host cache tier (pre-seeded like everything else):

- serving_moe_assignments_total, serving_moe_local_assignments_total,
  serving_moe_expert_slots_total, serving_moe_expert_hits_total
                                  an expert-layer model's counts, summed
                                  over its expert layers a launch and
                                  fetched with the launch's tokens: tokens
                                  x top-k; those routed to experts held
                                  here; held experts x layers x launches;
                                  of those, the ones that got a token
- serving_ssm_state_rows_live_total, serving_ssm_state_rows_moved_total
                                  a recurrent-state model's counts, summed
                                  over its state layers a launch and
                                  fetched with the launch's tokens: slots
                                  whose state the launch advanced, and the
                                  slots' state rows the update read and
                                  wrote (equal where dead slots are
                                  skipped)
- serving_kv_bytes_per_token      gauge: device bytes one resident token
                                  costs across the layers that page (codes
                                  + amortized scales), set at construction
                                  — 4x lower under kv_dtype="int8"
- serving_state_bytes_per_slot    gauge: device bytes a slot keeps whatever
                                  its length (a recurrent model's state
                                  leaves over its state layers; 0 for a
                                  pool of pages alone), set at construction
- serving_host_tier_pages         gauge: spilled prefix pages resident in
                                  the host tier now
- serving_host_tier_bytes         gauge: host bytes the tier holds now
- serving_host_tier_hits_total    admissions that restored >= 1 page
- serving_host_tier_spills_total  pages spilled at eviction sweeps
- serving_host_tier_restores_total pages restored on prefix hits

Speculative decoding (pre-seeded like everything else):

- serving_spec_depth                  gauge: the configured speculation
                                      depth K (0 = speculation off), set
                                      at construction
- serving_spec_proposed_tokens_total  candidate tokens proposed (K per
                                      running request per verify step)
- serving_spec_accepted_tokens_total  candidates the target accepted
- serving_spec_acceptance_rate        gauge: accepted / proposed over the
                                      engine's lifetime (each verify step
                                      ALSO emits the target's own next
                                      token — tokens/step = rate*K + 1)

Chunked prefill + SLO admission (pre-seeded like everything else):

- serving_prefill_chunks_total  prefill chunks executed (a full prefill
                                in chunked mode is >= 1 chunk; unchunked
                                prefills don't count here)
- serving_chunk_limit           gauge: the SLO controller's current
                                chunks-admitted-per-step (0 when no
                                controller is installed)
- serving_slo_throttles_total   controller windows that LOWERED the limit

Kernel-dispatch counters (pre-seeded):

- serving_pallas_fallback_total  always 0: nothing increments it since
                                 the kernel dispatch stopped catching a
                                 failing Pallas kernel (an eligible
                                 kernel that fails to trace or lower now
                                 raises). The name and its watchdog rule
                                 stay only because scrape goldens carry
                                 them (ROADMAP D9 removes both).
- serving_flash_pad_total        flash dispatch SITES that took the
                                 causal pad-to-block route (the seq %512
                                 edge, e.g. 640 -> 1024): exact results,
                                 visible pad presence. Counted where the
                                 dispatch Python runs — once per traced
                                 program under jit, per call when eager:
                                 a growth signal, NOT a
                                 per-inference-dispatch count
- serving_flash_edge_fallback_total  flash-shaped dispatch sites (seqs
                                 >= 128, 64-aligned head_dim, TPU, flag
                                 on) with NO kernel route — the loudly-
                                 counted composite fallback the coverage
                                 report's flash edge rows name (same
                                 trace-time counting contract as above)

Analysis counters (paddle_tpu.analysis integration, pre-seeded):

- serving_analysis_retraces_total    CompileGuard traces beyond the
                                     declared compile budgets (0 = the
                                     compile-once contract held)
- serving_analysis_host_syncs_total  host-sync events tallied inside
                                     step() under debug_checks (one per
                                     step boundary — the token fetch — is
                                     the sanctioned floor)

hlocheck roll-up (compiled-artifact audits under debug_checks, one per
compiled program — per prefill bucket + decode; pre-seeded):

- serving_hlo_collective_ops   total collective ops across audited
                               programs (single-chip contract: 0)
- serving_hlo_host_transfers   total infeed/outfeed/host-callback ops
                               compiled into audited programs (floor: 0)
- serving_hlo_peak_hbm_bytes   max per-step resident bytes (args + temp
                               arena + outputs - aliased) over programs
- serving_hlo_flops_per_step   max XLA cost_analysis flops over programs

Tensor-parallel serving (pre-seeded; fed from the hlocheck census at
each sharded program's first-trace audit — the EQuARX baseline numbers):

- serving_tp_degree                      gauge: ServingConfig
                                         tensor_parallel (1 = single
                                         chip), set at construction
- serving_tp_collective_ops_per_step     max collective ops in one
                                         audited sharded program
                                         (2*layers + 1 by declaration)
- serving_tp_collective_bytes_per_token  max collective payload bytes
                                         per token a program advances

Collective placement (pre-seeded; fed from the meshcheck attribution at
the same first-trace audit — per-medium split on the declared, or
default single-host, MeshTopology):

- serving_ici_bytes_per_token            max per-token collective bytes
                                         riding ICI (within a host)
- serving_dcn_bytes_per_token            max per-token collective bytes
                                         riding DCN (across hosts) —
                                         0.0 IS the single-host contract
- serving_collective_time_predicted_s    max link-time-model predicted
                                         collective seconds per step

Latency histograms (paddle_tpu.obs integration): fixed-bucket streaming
histograms — bounded memory, O(log buckets) per observation — feed the
percentile gauges ``serving_<hist>_p50/p90/p99`` (+ ``_count``) for:

- serving_ttft_s           enqueue -> first token (time to first token)
- serving_tpot_s           mean seconds per output token over decode
- serving_queue_wait_s     enqueue -> admission
- serving_e2e_s            enqueue -> retirement
- serving_step_duration_s  one engine step, engine-clock seconds
- serving_batch_occupancy  active decode slots per step

The request-latency histograms are fed from request traces at retirement
(``observe_request``), the step histograms at every step boundary
(``observe_step``); all percentile gauges are pre-seeded to 0 at reset
and recomputed lazily at ``snapshot()`` — the hot path only pays the
bisect+add of the observation itself. High-watermark gauges
``serving_queue_depth_peak`` / ``serving_page_pool_peak`` keep the spikes
a sampled gauge misses.

Goodput attribution + watchdogs + flight recorder (PR 12):

- serving_step_phase_s{phase=}    histogram family: per-phase step
                                  wall-time attribution (admit / swap /
                                  prefill / chunk_prefill / decode /
                                  verify / evict / other). Inside
                                  "decode", the decode.fetch span
                                  (StepRecord.span_s) is the wait for
                                  the PREVIOUS step's launch, which has
                                  been running since; the launch of
                                  this step is fetched by the next
                                  (``_count`` and ``_sum`` of each
                                  child are mirrored beside the
                                  percentiles: sum / count is a phase's
                                  mean)
- serving_step_seconds_total      counter: ``t_end - t_start`` of every
                                  step record: the seconds inside
                                  ``engine.step()`` on the engine's
                                  clock, over whatever window a reader
                                  differences it
- serving_step_span_seconds_total{span=}  counter family: each entry of
                                  ``StepRecord.span_s``, summed
                                  (``decode.upload`` / ``.dispatch`` /
                                  ``.fetch`` / ``.emit``, ``prefill.*``,
                                  ``verify.*``, ``account``,
                                  ``window_release``, ``cow_copy``,
                                  ``drain``)
- serving_step_host_seconds_total counter: the step's seconds less its
                                  ``*.fetch`` spans: what the host itself
                                  does in a step (admit, uploads,
                                  dispatches, emit, evict, account) as
                                  against waiting for the device. An
                                  upload or a dispatch that BLOCKS while
                                  the device's queue is full (a window's
                                  first step of 256 prefills) counts as
                                  the host's here: take
                                  ``{span=prefill.upload}`` off to read
                                  without it
- serving_step_unstalled_seconds_total  counter: the step's seconds less
                                  its stall seconds (the excess of each
                                  blocking span that obs/stall.py
                                  flagged over its name's norm)
- serving_stalls_total{held_by=},
  serving_stall_seconds_total{held_by=}  counter families: the stall
                                  records completed, and their excess
                                  seconds, by what held the wait (frozen
                                  / device / cpu_queue / memory / io /
                                  runtime_busy / asleep / unsampled:
                                  obs/stall.py)
  These six ride ``enable_tracing``: the engine seeds them when tracing
  is on (``seed_step_seconds``) and an engine without has none of them.
  They are plain floats on ``ServingMetrics`` that ``snapshot()``
  mirrors into the registry, so a step pays float additions and not the
  registry's lock.
- serving_alerts_total{rule=}     counter family: watchdog firings per
                                  rule (retrace_after_warmup /
                                  pallas_fallback /
                                  spec_acceptance_collapse /
                                  eviction_thrash / queue_stall /
                                  slo_burn)

Per-tenant SLO observability (PR 15 — the goodput/badput ledger,
obs/tenant.py; every family pre-seeded for the declared tenants +
"default" at engine construction, ad-hoc tenants on first sight):

- serving_tenant_goodput_tokens_total{tenant=}  tokens emitted by
                                  requests that retired in_slo —
                                  the tenant's useful work
- serving_tenant_badput_tokens_total{tenant=}   tokens emitted by every
                                  other retirement (late / shed /
                                  expired / cancelled / failed); the
                                  two families together reconcile
                                  EXACTLY with serving_tokens_total
                                  once every request has retired
- serving_tenant_retired_total{tenant=,class=}  multi-label counter:
                                  retirements per terminal class
                                  (in_slo / ttft_late / tpot_late /
                                  shed / expired / cancelled / failed)
                                  — the badput breakdown the CLI
                                  --tenant-table renders
- serving_ttft_s{tenant=} / serving_tpot_s{tenant=} /
  serving_queue_delay_s{tenant=}  histogram families: the per-tenant
                                  latency classes (percentile mirrors +
                                  real labeled bucket series, like the
                                  phase family)

Fleet router (PR 16 — serving/fleet.py; N replicas share this ONE
process-global registry, so the fleet counters are fleet-wide totals and
token reconciliation across replicas is automatic):

- serving_fleet_replicas          gauge: live replicas behind the router
                                  (set at construction, lowered by a
                                  replica_down fault)
- serving_fleet_prefix_affinity_hits_total  requests routed to a replica
                                  whose gossiped digest set held a warm
                                  prefix match
- serving_fleet_spills_total      requests spilled off their warm (or
                                  dead) replica to the least-loaded
                                  survivor
- serving_fleet_tenant_weight{tenant=}  gauge family: the router's
                                  per-tenant admission weight — 1.0 at
                                  seed, multiplied by weight_gain once
                                  per slo_burn onset (the outer loop
                                  actuating PR 15's ledger)

Every counter incremented here is pre-seeded in ``_SEEDED`` — lint rule
PT003 (this module shipped unseeded counters once) enforces it; every
``stat_set``/``stat_max`` gauge likewise, per the mirror rule PT008.
Labeled-family names (``base{label=value}`` registry keys — one label,
or an ORDERED label tuple for multi-label families like
``tenant_retired_total{tenant=,class=}``) are declared in ``_FAMILIES``
and their label values seeded at engine construction via
:meth:`ServingMetrics.seed_family` — lint rule PT012 flags any labeled
stat call whose base is in neither registry, and (since the multi-label
extension) any call whose statically visible label keys disagree with
the declaration — a reordered ``{class=,tenant=}`` write would build a
registry key the seeding never created.
"""
from __future__ import annotations

import time
from collections import deque

from ..obs.attribution import PHASES
from ..obs.histogram import (LATENCY_EDGES_S, OCCUPANCY_EDGES, QUANTILES,
                             Histogram, HistogramFamily)
from ..obs.tenant import CLASSES as TENANT_CLASSES
from ..utils import monitor

PREFIX = "serving_"

# always-visible counters and gauges (a snapshot taken before the first
# event must still show the zeros — dashboards key on presence; lint rule
# PT003 flags any stat_add of a name missing here, PT008 any
# stat_set/stat_max)
_SEEDED = ("tokens_total", "prefills_total", "prefill_overlapped_total",
           "prefill_tokens_total",
           "prefill_chunks_total", "chunk_limit", "slo_throttles_total",
           "decode_steps", "decode_overlapped_total", "preemptions_total",
           "attention_pages_live_total", "attention_pages_staged_total",
           "rejected", "shed", "expired", "cancelled", "failed",
           "swap_outs", "swap_ins",
           "prefix_hits", "prefix_misses", "prefix_tokens_saved",
           "prefix_shared_pages", "prefix_cached_pages",
           "prefix_cow_copies", "prefix_evictions",
           "spec_depth", "spec_proposed_tokens_total",
           "spec_accepted_tokens_total", "spec_acceptance_rate",
           "kv_bytes_per_token", "state_bytes_per_slot",
           "kv_window_pages_released_total",
           "kv_resident_page_layers_total",
           "kv_one_lifetime_page_layers_total",
           "host_tier_pages", "host_tier_bytes",
           "host_tier_hits_total", "host_tier_spills_total",
           "host_tier_restores_total",
           "moe_assignments_total", "moe_local_assignments_total",
           "moe_expert_slots_total", "moe_expert_hits_total",
           "ssm_state_rows_live_total", "ssm_state_rows_moved_total",
           "pallas_fallback_total",
           "flash_pad_total", "flash_edge_fallback_total",
           "analysis_retraces_total", "analysis_host_syncs_total",
           "hlo_collective_ops", "hlo_host_transfers",
           "hlo_peak_hbm_bytes", "hlo_flops_per_step",
           "tp_degree", "tp_collective_ops_per_step",
           "tp_collective_bytes_per_token", "tp_collective_overlap_frac",
           "ici_bytes_per_token", "dcn_bytes_per_token",
           "collective_time_predicted_s",
           "tokens_per_sec", "queue_depth", "active_requests",
           "page_utilization",
           "fleet_replicas", "fleet_prefix_affinity_hits_total",
           "fleet_spills_total",
           "fleet_goodput_tokens_total", "fleet_inflight_exchanges",
           "wire_tx_bytes_total", "wire_rx_bytes_total",
           "wire_retries_total", "wire_hedge_wins_total",
           "wire_refetch_fallback_total",
           "queue_depth_peak", "page_pool_peak")

# labeled stat families: base name -> label key, or an ORDERED tuple of
# label keys for multi-label families. Members live in the monitor
# registry as ``serving_<base>{<l1>=<v1>,<l2>=<v2>}`` keys (labels in
# declared order — seeding and every write site must agree, which the
# PT012 label-key check enforces); label VALUES are seeded at engine
# construction (seed_family) since most are only known then (prefill
# bucket labels, registered kernels, declared tenants). Lint rule PT012
# checks every statically visible labeled stat call against this
# registry — the dynamically-formatted-name blind spot of PT003/PT008.
_FAMILIES = {
    "step_phase_s": "phase",              # histogram family (below)
    "step_span_seconds_total": "span",    # counter: StepRecord.span_s
    "stalls_total": "held_by",            # counter: stall records, and
    "stall_seconds_total": "held_by",     # their excess (obs/stall.py)
    "alerts_total": "rule",               # counter: watchdog firings
    "decode_drains_total": "reason",      # counter: early fetches of the
    # decode in flight, by site (engine.DRAIN_REASONS)
    "tenant_goodput_tokens_total": "tenant",   # in_slo tokens per tenant
    "tenant_badput_tokens_total": "tenant",    # everything-else tokens
    "tenant_retired_total": ("tenant", "class"),  # retirements per
    # terminal class — the one multi-label family (badput breakdown)
    "fleet_tenant_weight": "tenant",      # router admission weight (the
    # slo_burn-actuated outer-loop gain; 1.0 until a burn onset)
    "wire_corrupt_total": "kind",         # counter: decode failures by
    # WireError taxonomy kind (truncated / corrupt / bad_version)
    "breaker_open_total": "peer",         # counter: circuit-breaker
    # open transitions per peer replica index
    "breaker_state": "peer",              # gauge: current breaker state
    # per peer (closed/half_open/open as 0/1/2 — every transition
    # metered, the gauge can never skip a state)
    "wire_bytes_total": "type",           # counter: exchange tx bytes
    # by frame type (page / digests / rehome), fed from ExchangeInfo
    "wire_rtt_s": "peer",                 # histogram family (below):
    "wire_attempts": "peer",              # per-peer exchange round-trip
    # time and copies-sent count, fed from ExchangeInfo post-exchange
    "ttft_s": "tenant",                   # histogram family (per-tenant
    "tpot_s": "tenant",                   # latency classes; the plain
    "queue_delay_s": "tenant",            # serving_ttft_s etc. hist
    # keeps the engine-wide view, these children split it by tenant)
}

# histogram name -> bucket edges; percentile gauges <name>_{p50,p90,p99}
# and <name>_count are seeded for each (dynamically — same presence
# contract as _SEEDED)
_HISTOGRAMS = (("ttft_s", LATENCY_EDGES_S),
               ("tpot_s", LATENCY_EDGES_S),
               ("queue_wait_s", LATENCY_EDGES_S),
               ("e2e_s", LATENCY_EDGES_S),
               ("step_duration_s", LATENCY_EDGES_S),
               ("batch_occupancy", OCCUPANCY_EDGES))

# trace-summary key -> histogram it feeds
_SUMMARY_HISTS = (("ttft", "ttft_s"), ("tpot", "tpot_s"),
                  ("queue_wait", "queue_wait_s"), ("e2e", "e2e_s"))

# Prometheus exposition types for the monotonic stats; unlisted serving_*
# scalars export as gauges, the histograms as real bucket series
COUNTER_STATS = frozenset(
    PREFIX + k for k in _SEEDED
    if k.endswith("_total") or k in (
        "decode_steps", "rejected", "shed", "expired", "cancelled",
        "failed", "swap_outs", "swap_ins", "prefix_hits", "prefix_misses",
        "prefix_tokens_saved", "prefix_cow_copies", "prefix_evictions",
        "hlo_collective_ops", "hlo_host_transfers")) \
    | frozenset(PREFIX + k for k in (  # seeded with tracing on only
        "step_seconds_total", "step_host_seconds_total",
        "step_unstalled_seconds_total")) \
    | frozenset({  # labeled counter family bases
        PREFIX + "step_span_seconds_total",
        PREFIX + "stalls_total",
        PREFIX + "stall_seconds_total",
        PREFIX + "alerts_total",
        PREFIX + "decode_drains_total",
        PREFIX + "tenant_goodput_tokens_total",
        PREFIX + "tenant_badput_tokens_total",
        PREFIX + "tenant_retired_total",
        PREFIX + "wire_corrupt_total",
        PREFIX + "breaker_open_total",
        PREFIX + "wire_bytes_total"})

#: serving_breaker_state{peer=} gauge values — the breaker state
#: machine's three states in escalation order
BREAKER_STATE_VALUES = {"closed": 0, "half_open": 1, "open": 2}

# the step's seconds (registry keys of ServingMetrics._seconds)
_STEP_S = PREFIX + "step_seconds_total"
_HOST_S = PREFIX + "step_host_seconds_total"
_UNSTALLED_S = PREFIX + "step_unstalled_seconds_total"


class ServingMetrics:
    """Writes the serving stats; a sliding window over (time, tokens_total)
    yields tokens/s without a background thread."""

    def __init__(self, window_s: float = 10.0):
        self.window_s = window_s
        self._samples: deque[tuple[float, float]] = deque()
        self.hists = {name: Histogram(PREFIX + name, edges)
                      for name, edges in _HISTOGRAMS}
        # the per-phase step-time histogram family (label-generic: the
        # mechanism the per-tenant latency classes below reuse)
        self.phase_hist = HistogramFamily(
            PREFIX + "step_phase_s", "phase", LATENCY_EDGES_S,
            values=PHASES)
        # per-tenant latency classes: children of the SAME base names as
        # the engine-wide hists (plus queue_delay_s), split by tenant —
        # children are created by seed_tenants / first observation
        self.tenant_hists = {
            "ttft_s": HistogramFamily(PREFIX + "ttft_s", "tenant",
                                      LATENCY_EDGES_S),
            "tpot_s": HistogramFamily(PREFIX + "tpot_s", "tenant",
                                      LATENCY_EDGES_S),
            "queue_delay_s": HistogramFamily(PREFIX + "queue_delay_s",
                                             "tenant", LATENCY_EDGES_S),
        }
        # per-peer transport families, fed from ExchangeInfo after every
        # exchange — children created by seed_wire_peers at router
        # construction (or on first sight of a peer)
        self.wire_hists = {
            "wire_rtt_s": HistogramFamily(PREFIX + "wire_rtt_s",
                                          "peer", LATENCY_EDGES_S),
            "wire_attempts": HistogramFamily(PREFIX + "wire_attempts",
                                             "peer", OCCUPANCY_EDGES),
        }
        # scalar family members seeded so far: base -> ordered values
        # (str, or a tuple matching a multi-label declaration;
        # seed_family records them so reset() can replay the zeros)
        self._family_values: dict[str, list] = {}
        # the step's seconds and the stalls (seed_step_seconds): registry
        # key -> running sum, added to without the registry's lock and
        # mirrored by snapshot(); empty on an engine without tracing
        self._seconds: dict[str, float] = {}
        self._span_keys: dict[str, str] = {}  # span -> its member's key
        self.reset()

    def _hist_families(self):
        return (self.phase_hist, *self.tenant_hists.values(),
                *self.wire_hists.values())

    @staticmethod
    def _family_key(base: str, value) -> str:
        """The registry key of one family member: ``base{l=v}`` for a
        single label, ``base{l1=v1,l2=v2}`` in DECLARED label order for
        a multi-label family (every write site must render the same
        order — the PT012 label-key check pins the statically visible
        ones)."""
        label = _FAMILIES[base]  # KeyError = undeclared family
        if isinstance(label, tuple):
            if not isinstance(value, tuple) or len(value) != len(label):
                raise ValueError(
                    f"family {base!r} declares labels {label} — seed "
                    f"values must be {len(label)}-tuples, got {value!r}")
            body = ",".join(f"{k}={v}" for k, v in zip(label, value))
        else:
            body = f"{label}={value}"
        return PREFIX + f"{base}{{{body}}}"

    def reset(self) -> None:
        for k in list(monitor.stats_with_prefix(PREFIX)):
            monitor.stat_reset(k)
        for k in _SEEDED:
            monitor.stat_set(PREFIX + k, 0)
        for h in self.hists.values():
            h.reset()
        for fam in self._hist_families():
            fam.reset()
        for base, values in self._family_values.items():
            for v in values:
                monitor.stat_set(self._family_key(base, v), 0)
        self._publish_hists()  # seed the percentile gauges at 0
        self._seconds = dict.fromkeys(self._seconds, 0.0)
        self._publish_seconds()
        self._samples.clear()
        self._samples.append((time.perf_counter(), 0.0))

    def seed_family(self, base: str, values) -> None:
        """Pre-seed labeled family members at 0 — the presence contract
        ``_SEEDED`` gives scalars, for label values only known at engine
        construction (prefill buckets, watchdog rules, banked kernels,
        declared tenants). ``base`` must be declared in ``_FAMILIES``
        (the runtime complement of lint rule PT012); a multi-label base
        takes value TUPLES in declared label order."""
        seen = self._family_values.setdefault(base, [])
        for v in values:
            v = tuple(str(x) for x in v) if isinstance(v, tuple) \
                else str(v)
            key = self._family_key(base, v)
            if v not in seen:
                seen.append(v)
            # seeding declares PRESENCE — it must never erase history.
            # Replicas share the one monitor registry, and a second
            # replica first seeing an ad-hoc tenant mid-run would
            # otherwise zero counts the first replica already accrued
            # (found by the chaos soak's trickled arrivals).
            if monitor.stat_get(key, None) is None:
                monitor.stat_set(key, 0)

    def seed_tenants(self, tenants) -> None:
        """Pre-seed every per-tenant surface for the given tenant names:
        the goodput/badput counter families, the (tenant, class)
        retirement grid, and the three latency histogram-family
        children — called at engine construction for the declared
        tenants + "default", and on first sight of an ad-hoc tenant."""
        tenants = [str(t) for t in tenants]
        self.seed_family("tenant_goodput_tokens_total", tenants)
        self.seed_family("tenant_badput_tokens_total", tenants)
        self.seed_family("tenant_retired_total",
                         [(t, c) for t in tenants for c in TENANT_CLASSES])
        for fam in self.tenant_hists.values():
            for t in tenants:
                fam.child(t)

    def seed_wire_peers(self, peers) -> None:
        """Pre-seed every per-peer transport surface for the given
        replica indices: the ``breaker_state`` gauge family (at 0 =
        closed) and the ``wire_rtt_s`` / ``wire_attempts`` histogram
        children — called at router construction."""
        peers = [str(p) for p in peers]
        self.seed_family("breaker_state", peers)
        for fam in self.wire_hists.values():
            for p in peers:
                fam.child(p)

    # ------------------------------------------------------------- updates
    def on_prefill(self, tokens: int = 0, overlapped: bool = False) -> None:
        """One completed prefill; ``overlapped`` when its first token was
        fetched behind the launch of the decode that follows it."""
        monitor.stat_add(PREFIX + "prefills_total", 1)
        monitor.stat_add(PREFIX + "prefill_tokens_total", int(tokens))
        if overlapped:
            monitor.stat_add(PREFIX + "prefill_overlapped_total", 1)

    def on_prefix_hit(self, tokens_saved: int) -> None:
        monitor.stat_add(PREFIX + "prefix_hits", 1)
        monitor.stat_add(PREFIX + "prefix_tokens_saved", int(tokens_saved))

    def on_prefill_chunk(self, tokens: int) -> None:
        """One chunk of a chunked prefill: the chunk counter plus the
        FLOPs-weighted token count (the final chunk's ``on_prefill(0)``
        then adds only the per-request prefill count)."""
        monitor.stat_add(PREFIX + "prefill_chunks_total", 1)
        monitor.stat_add(PREFIX + "prefill_tokens_total", int(tokens))

    def on_chunk_limit(self, limit: int, throttled: bool = False) -> None:
        """Mirror the SLO controller's chunks-per-step limit; a window
        that lowered it also counts a throttle."""
        monitor.stat_set(PREFIX + "chunk_limit", int(limit))
        if throttled:
            monitor.stat_add(PREFIX + "slo_throttles_total", 1)

    def on_prefix_miss(self) -> None:
        monitor.stat_add(PREFIX + "prefix_misses", 1)

    def on_preempt(self) -> None:
        monitor.stat_add(PREFIX + "preemptions_total", 1)

    def on_rejected(self) -> None:
        monitor.stat_add(PREFIX + "rejected", 1)

    def on_shed(self) -> None:
        monitor.stat_add(PREFIX + "shed", 1)

    def on_expired(self) -> None:
        monitor.stat_add(PREFIX + "expired", 1)

    def on_cancelled(self) -> None:
        monitor.stat_add(PREFIX + "cancelled", 1)

    def on_failed(self) -> None:
        monitor.stat_add(PREFIX + "failed", 1)

    def on_swap_out(self) -> None:
        monitor.stat_add(PREFIX + "swap_outs", 1)

    def on_swap_in(self) -> None:
        monitor.stat_add(PREFIX + "swap_ins", 1)

    def on_tokens(self, n: int) -> None:
        total = monitor.stat_add(PREFIX + "tokens_total", int(n))
        now = time.perf_counter()
        self._samples.append((now, float(total)))
        while len(self._samples) > 2 and \
                now - self._samples[0][0] > self.window_s:
            self._samples.popleft()
        t0, n0 = self._samples[0]
        rate = (total - n0) / (now - t0) if now > t0 else 0.0
        monitor.stat_set(PREFIX + "tokens_per_sec", rate)

    def on_decode_step(self, overlapped: bool = False) -> None:
        """One decode (or verify) launch; ``overlapped`` when the previous
        launch was still in flight."""
        monitor.stat_add(PREFIX + "decode_steps", 1)
        if overlapped:
            monitor.stat_add(PREFIX + "decode_overlapped_total", 1)

    def on_attention_pages(self, live: int, staged: int) -> None:
        """One launch's attention: pages its contexts hold, and pages it
        copied out of the pool (a layer's worth of each)."""
        monitor.stat_add(PREFIX + "attention_pages_live_total", live)
        monitor.stat_add(PREFIX + "attention_pages_staged_total", staged)

    def on_window_release(self, pages: int) -> None:
        """Pages of a window group returned to its allocator because no
        later query of their slot can see them."""
        monitor.stat_add(PREFIX + "kv_window_pages_released_total",
                         int(pages))

    def on_kv_residency(self, resident: int, one_lifetime: int) -> None:
        """At one decode launch of a model of several page groups: the
        pages resident in each group times the group's layers, and what
        one table and one lifetime would hold for the same contexts."""
        monitor.stat_add(PREFIX + "kv_resident_page_layers_total",
                         int(resident))
        monitor.stat_add(PREFIX + "kv_one_lifetime_page_layers_total",
                         int(one_lifetime))

    def on_decode_drain(self, reason: str) -> None:
        """One decode in flight fetched before the next launch."""
        monitor.stat_add(PREFIX + f"decode_drains_total{{reason={reason}}}",
                         1)

    def on_spec_depth(self, depth: int) -> None:
        """The configured speculation depth K (0 = speculation off), set
        once at engine construction."""
        monitor.stat_set(PREFIX + "spec_depth", int(depth))

    def on_spec(self, proposed: int, accepted: int) -> None:
        """One verify step's speculation outcome: candidates proposed
        (depth per active slot) and accepted; the lifetime acceptance
        rate is recomputed off the running totals stat_add returns."""
        p = monitor.stat_add(PREFIX + "spec_proposed_tokens_total",
                             int(proposed))
        a = monitor.stat_add(PREFIX + "spec_accepted_tokens_total",
                             int(accepted))
        monitor.stat_set(PREFIX + "spec_acceptance_rate",
                         a / p if p else 0.0)

    def on_model_counters(self, names, values) -> None:
        """What the model's layers counted in one launch, summed over the
        layers and fetched with the launch's tokens (``PagedCacheSpec.
        counters`` names them; every name a model may give is seeded). An
        expert layer's: token-to-expert assignments, those to experts held
        here, held experts x layers, and of those the ones that got a
        token."""
        for name, value in zip(names, values):
            monitor.stat_add(PREFIX + name, int(value))

    def on_kv_bytes_per_token(self, nbytes: int) -> None:
        """Device bytes one resident token costs (set once at engine
        construction — a static consequence of kv_dtype + the model
        shape, the denominator capacity dashboards divide HBM by)."""
        monitor.stat_set(PREFIX + "kv_bytes_per_token", int(nbytes))

    def on_state_bytes_per_slot(self, nbytes: int) -> None:
        """Device bytes a slot keeps whatever its length (set once at
        engine construction, from the pool's per-slot leaves)."""
        monitor.stat_set(PREFIX + "state_bytes_per_slot", int(nbytes))

    def on_state(self, queue_depth: int, active: int, pages_used: int,
                 usable_pages: int, shared_pages: int = 0,
                 cached_pages: int = 0, cow_copies: int = 0,
                 evictions: int = 0, host_tier_pages: int = 0,
                 host_tier_bytes: int = 0, host_tier_hits: int = 0,
                 host_tier_spills: int = 0,
                 host_tier_restores: int = 0) -> None:
        monitor.stat_set(PREFIX + "queue_depth", queue_depth)
        monitor.stat_set(PREFIX + "active_requests", active)
        monitor.stat_set(PREFIX + "page_utilization",
                         pages_used / max(1, usable_pages))
        monitor.stat_max(PREFIX + "queue_depth_peak", queue_depth)
        monitor.stat_max(PREFIX + "page_pool_peak", pages_used)
        monitor.stat_set(PREFIX + "prefix_shared_pages", shared_pages)
        monitor.stat_set(PREFIX + "prefix_cached_pages", cached_pages)
        # cache-owned monotonic counters, mirrored as absolute values
        monitor.stat_set(PREFIX + "prefix_cow_copies", cow_copies)
        monitor.stat_set(PREFIX + "prefix_evictions", evictions)
        monitor.stat_set(PREFIX + "host_tier_pages", host_tier_pages)
        monitor.stat_set(PREFIX + "host_tier_bytes", host_tier_bytes)
        monitor.stat_set(PREFIX + "host_tier_hits_total", host_tier_hits)
        monitor.stat_set(PREFIX + "host_tier_spills_total",
                         host_tier_spills)
        monitor.stat_set(PREFIX + "host_tier_restores_total",
                         host_tier_restores)

    def on_analysis(self, retraces: int, host_syncs: int) -> None:
        """CompileGuard/SyncTally totals, mirrored as absolute values (the
        guards own the monotonic counts)."""
        monitor.stat_set(PREFIX + "analysis_retraces_total", retraces)
        monitor.stat_set(PREFIX + "analysis_host_syncs_total", host_syncs)

    def on_tp_degree(self, degree: int) -> None:
        """The engine's tensor-parallel degree (1 = single-chip), set at
        construction so dashboards can segment every other gauge by it."""
        monitor.stat_set(PREFIX + "tp_degree", int(degree))

    def on_tp_audit(self, collective_ops: int, bytes_per_token: float,
                    overlap_frac: float = 0.0) -> None:
        """One tensor-parallel hlocheck audit (debug_checks, once per
        compiled program): the per-step collective op count, the
        collective payload bytes per token the program advances — the
        baseline numbers EQuARX-style quantized collectives get measured
        against — and the overlap census fraction (overlapped / async
        collectives; 0.0 where the backend compiled everything sync).
        stat_max keeps the steady-state (decode) worst case across
        programs (for overlap, the best program observed — the gauge
        answers \"did the latency-hiding scheduler engage at all\")."""
        monitor.stat_max(PREFIX + "tp_collective_ops_per_step",
                         int(collective_ops))
        monitor.stat_max(PREFIX + "tp_collective_bytes_per_token",
                         float(bytes_per_token))
        monitor.stat_max(PREFIX + "tp_collective_overlap_frac",
                         float(overlap_frac))

    def on_mesh_audit(self, ici_bytes_per_token: float,
                      dcn_bytes_per_token: float,
                      predicted_s: float) -> None:
        """One meshcheck placement audit (debug_checks, once per compiled
        program): the per-token collective payload split by the link it
        rides — ICI within a host vs DCN across hosts, attributed by
        analysis/meshcheck against the declared (or default single-host)
        MeshTopology — and the link-time model's predicted collective
        seconds per step. stat_max keeps the worst program observed;
        dcn_bytes_per_token staying 0.0 IS the single-host contract."""
        monitor.stat_max(PREFIX + "ici_bytes_per_token",
                         float(ici_bytes_per_token))
        monitor.stat_max(PREFIX + "dcn_bytes_per_token",
                         float(dcn_bytes_per_token))
        monitor.stat_max(PREFIX + "collective_time_predicted_s",
                         float(predicted_s))

    def on_hlo_audit(self, collective_ops: int, host_transfers: int,
                     peak_hbm_bytes: int, flops: float) -> None:
        """One hlocheck compiled-artifact audit (debug_checks, once per
        compiled program): collective/host-transfer ops accumulate across
        programs, peak HBM and flops keep the per-program maximum."""
        monitor.stat_add(PREFIX + "hlo_collective_ops", int(collective_ops))
        monitor.stat_add(PREFIX + "hlo_host_transfers", int(host_transfers))
        monitor.stat_max(PREFIX + "hlo_peak_hbm_bytes", int(peak_hbm_bytes))
        monitor.stat_max(PREFIX + "hlo_flops_per_step", float(flops))

    # ------------------------------------------- attribution + watchdogs
    def on_phase(self, phase: str, seconds: float) -> None:
        """One phase's share of one step's wall time (attribution layer;
        zero-time phases are not observed — the StepRecord keeps the
        exact split)."""
        self.phase_hist.observe(phase, seconds)

    def seed_step_seconds(self, spans, held_by) -> None:
        """Bring the step's seconds counters and the stall families into
        being at 0 (an engine with tracing on, at construction): the
        three totals, ``step_span_seconds_total{span=}`` over ``spans``
        and the two stall families over ``held_by``."""
        for k in (_STEP_S, _HOST_S, _UNSTALLED_S,
                  *(self._family_key("step_span_seconds_total", v)
                    for v in spans),
                  *(self._family_key(base, v) for v in held_by
                    for base in ("stalls_total", "stall_seconds_total"))):
            self._seconds.setdefault(k, 0.0)
        self._publish_seconds()

    def on_step_seconds(self, step_s: float, span_s: dict,
                        stall_s: float) -> None:
        """One step record's seconds: the step, each of its spans, the
        step less its ``*.fetch`` spans, the step less its stalls. Float
        additions on this object: no lock, nothing published."""
        sec = self._seconds
        sec[_STEP_S] += step_s
        sec[_UNSTALLED_S] += step_s - stall_s
        for span, dt in span_s.items():
            key = self._span_keys.get(span)
            if key is None:
                key = self._span_keys[span] = self._family_key(
                    "step_span_seconds_total", span)
            sec[key] = sec.get(key, 0.0) + dt
            if span.endswith(".fetch"):
                step_s -= dt
        sec[_HOST_S] += step_s

    def on_stall(self, held_by: str, seconds: float) -> None:
        """One completed stall record: what held it, its excess."""
        self._seconds[self._family_key("stalls_total", held_by)] += 1
        self._seconds[self._family_key("stall_seconds_total",
                                       held_by)] += seconds

    def on_alert(self, rule: str) -> None:
        """One watchdog firing (the rule's family member is pre-seeded
        at engine construction)."""
        monitor.stat_add(PREFIX + f"alerts_total{{rule={rule}}}", 1)

    # ------------------------------------------------- per-tenant ledger
    def on_tenant_retire(self, tenant: str, cls: str, tokens: int) -> None:
        """One classified retirement from the tenant ledger: bump the
        (tenant, class) retirement counter and accrue the request's
        emitted tokens to goodput (``in_slo``) or badput (anything
        else). Family members are pre-seeded for declared tenants; the
        engine seeds ad-hoc tenants on first sight."""
        monitor.stat_add(
            PREFIX + f"tenant_retired_total{{tenant={tenant},class={cls}}}",
            1)
        if cls == "in_slo":
            monitor.stat_add(
                PREFIX + f"tenant_goodput_tokens_total{{tenant={tenant}}}",
                int(tokens))
        else:
            monitor.stat_add(
                PREFIX + f"tenant_badput_tokens_total{{tenant={tenant}}}",
                int(tokens))

    # ------------------------------------------------------ fleet router
    def on_fleet_replicas(self, n: int) -> None:
        """Live replica count — set at router construction and again when
        a ``replica_down`` fault retires a replica."""
        monitor.stat_set(PREFIX + "fleet_replicas", int(n))

    def on_fleet_affinity_hit(self) -> None:
        """One request routed to a replica with a warm prefix match."""
        monitor.stat_add(PREFIX + "fleet_prefix_affinity_hits_total", 1)

    def on_fleet_spill(self) -> None:
        """One request spilled off its warm replica (or re-homed off a
        dead one) to the least-loaded survivor."""
        monitor.stat_add(PREFIX + "fleet_spills_total", 1)

    def on_fleet_tenant_weight(self, tenant: str, weight: float) -> None:
        """The router's admission weight for one tenant (family member
        pre-seeded at router construction)."""
        monitor.stat_set(
            PREFIX + f"fleet_tenant_weight{{tenant={tenant}}}",
            float(weight))

    # ------------------------------------------------------ wire transport
    def on_wire_tx(self, nbytes: int) -> None:
        """Frame bytes handed to the channel (counted per attempt —
        a retried or hedged frame pays its bytes again, the real cost)."""
        monitor.stat_add(PREFIX + "wire_tx_bytes_total", int(nbytes))

    def on_wire_rx(self, nbytes: int) -> None:
        """Frame bytes of a SUCCESSFUL exchange's winning copy, decoded
        clean (corrupt arrivals count in the corrupt family instead)."""
        monitor.stat_add(PREFIX + "wire_rx_bytes_total", int(nbytes))

    def on_wire_retry(self) -> None:
        """One transport retry (the attempt after a backoff)."""
        monitor.stat_add(PREFIX + "wire_retries_total", 1)

    def on_wire_corrupt(self, kind: str) -> None:
        """One frame that failed to decode, by WireError taxonomy kind
        (family pre-seeded at router construction for the three
        kinds)."""
        monitor.stat_add(
            PREFIX + f"wire_corrupt_total{{kind={kind}}}", 1)

    def on_wire_hedge_win(self) -> None:
        """One hedged read won by the hedge copy (the second transfer
        completed first or alone)."""
        monitor.stat_add(PREFIX + "wire_hedge_wins_total", 1)

    def on_wire_refetch_fallback(self) -> None:
        """One cross-replica page fetch that failed (corrupt / timed
        out / breaker open) and degraded to local re-prefill instead of
        failing the request."""
        monitor.stat_add(PREFIX + "wire_refetch_fallback_total", 1)

    def on_breaker_open(self, peer) -> None:
        """One circuit-breaker open transition for ``peer`` (family
        pre-seeded at router construction for every replica index)."""
        monitor.stat_add(
            PREFIX + f"breaker_open_total{{peer={peer}}}", 1)

    def on_breaker_state(self, peer, state: str) -> None:
        """The breaker's CURRENT state for ``peer`` as a gauge
        (closed/half_open/open as 0/1/2) — fed on every transition, so
        a scrape between transitions always shows the true state and
        the gauge can never skip half_open on the way back to
        closed."""
        monitor.stat_set(
            PREFIX + f"breaker_state{{peer={peer}}}",
            BREAKER_STATE_VALUES[state])

    def on_wire_exchange(self, peer, *, rtt_s: float,
                         attempts: int) -> None:
        """One finished exchange (success or failure), fed from
        ``Transport.last``: whole-exchange round-trip time (backoffs
        included) and copies sent, both split per peer."""
        peer = str(peer)
        self.wire_hists["wire_rtt_s"].observe(peer, float(rtt_s))
        self.wire_hists["wire_attempts"].observe(peer, int(attempts))

    def on_wire_frame_bytes(self, kind: str, nbytes: int) -> None:
        """Exchange tx bytes attributed to their frame type (family
        pre-seeded at router construction for the three kinds)."""
        monitor.stat_add(
            PREFIX + f"wire_bytes_total{{type={kind}}}", int(nbytes))

    def on_fleet_inflight(self, delta: int) -> None:
        """Exchanges currently on the wire — +1 at exchange entry, -1
        on return (a scrape mid-exchange shows 1)."""
        monitor.stat_add(PREFIX + "fleet_inflight_exchanges", int(delta))

    def on_fleet_goodput(self, tokens: int) -> None:
        """Fleet-wide goodput roll-up: the sum of every tenant's in-SLO
        tokens, mirrored as one counter (stat_set of a monotonic sum —
        the host_tier mirror idiom)."""
        monitor.stat_set(PREFIX + "fleet_goodput_tokens_total",
                         int(tokens))

    def observe_tenant(self, tenant: str, ttft, tpot,
                       queue_delay) -> None:
        """Feed the per-tenant latency histogram families at one
        retirement — None fields (milestones the lifecycle never
        reached) are skipped, the observe_request contract."""
        for key, v in (("ttft_s", ttft), ("tpot_s", tpot),
                       ("queue_delay_s", queue_delay)):
            if v is not None:
                self.tenant_hists[key].observe(tenant, v)

    # ---------------------------------------------------------- histograms
    def observe_request(self, summary: dict) -> None:
        """Feed the request-latency histograms from one trace summary
        (obs.trace.RequestTrace.summary). None fields — a milestone the
        lifecycle never reached, e.g. TTFT of a request cancelled while
        waiting — are skipped, not recorded as zeros."""
        for key, hist in _SUMMARY_HISTS:
            v = summary.get(key)
            if v is not None:
                self.hists[hist].observe(v)

    def observe_step(self, duration_s: float, occupancy: int) -> None:
        """One engine step: duration (engine-clock seconds) and the number
        of active decode slots it served."""
        self.hists["step_duration_s"].observe(duration_s)
        self.hists["batch_occupancy"].observe(occupancy)

    def _publish_hists(self) -> None:
        """Mirror percentiles + counts into the monitor registry. Called
        lazily from snapshot()/reset(), never on the serving hot path —
        observation stays O(log buckets). Family children mirror as
        ``<base>_<suffix>{<label>=<value>}`` — the phase family and
        every per-tenant family through the same loop."""
        for name, h in self.hists.items():
            for suffix, q in QUANTILES:
                monitor.stat_set(f"{PREFIX}{name}_{suffix}",
                                 h.percentile(q))
            monitor.stat_set(f"{PREFIX}{name}_count", h.count)
            monitor.stat_set(f"{PREFIX}{name}_sum", h.sum)
        for fam in self._hist_families():
            for value, h in fam.children().items():
                lab = f"{{{fam.label}={value}}}"
                for suffix, q in QUANTILES:
                    monitor.stat_set(f"{fam.name}_{suffix}" + lab,
                                     h.percentile(q))
                monitor.stat_set(f"{fam.name}_count" + lab, h.count)
                monitor.stat_set(f"{fam.name}_sum" + lab, h.sum)

    def _publish_seconds(self) -> None:
        """Mirror the step's seconds and the stall families into the
        registry (from snapshot(), as the histograms are)."""
        for key, value in self._seconds.items():
            monitor.stat_set(key, value)

    # ------------------------------------------------------------ querying
    def snapshot(self) -> dict:
        self._publish_hists()
        self._publish_seconds()
        return monitor.stats_with_prefix(PREFIX)

    def prometheus(self) -> str:
        """Prometheus text exposition of every serving stat: scalars typed
        counter/gauge (labeled family members rendered with proper
        sample labels through the sorted/escaped label renderer), the
        obs histograms — including the per-phase family's children and
        the per-tenant latency families — as cumulative bucket series.
        Histograms sharing a base name (the plain ``serving_ttft_s`` and
        its ``{tenant=}`` children) are emitted adjacent, so the
        ``# TYPE`` header appears exactly once per family."""
        from ..obs.export import prometheus_text

        types = {k: "counter" for k in COUNTER_STATS}
        hists = []
        for name, h in self.hists.items():
            hists.append(h)
            fam = self.tenant_hists.get(name)
            if fam is not None:  # tenant children ride under the same base
                hists.extend(fam.children().values())
        for name, fam in self.tenant_hists.items():
            if name not in self.hists:  # queue_delay_s: family-only base
                hists.extend(fam.children().values())
        hists.extend(self.phase_hist.children().values())
        for fam in self.wire_hists.values():
            hists.extend(fam.children().values())
        return prometheus_text(self.snapshot(), hists, types)

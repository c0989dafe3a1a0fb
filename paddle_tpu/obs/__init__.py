"""paddle_tpu.obs — serving-grade observability.

The layer that answers the operational questions the serving invariants
(compile-once, sync-free decode — paddle_tpu.analysis) cannot: where did a
request spend its time, what are TTFT/TPOT at p50/p99, what did the
engine's step timeline look like when tail latency spiked — and, since
the goodput-attribution layer, WHERE each step's wall time went, whether
the analytic cost models still predict reality, and what the engine was
doing right before it died.

- :mod:`~paddle_tpu.obs.trace` — per-request lifecycle traces
  (:class:`Tracer`, :class:`RequestTrace`): timestamped events from the
  pluggable engine clock, summarized into queue_wait / prefill_time /
  TTFT / TPOT / e2e. O(1) per event, bounded retention.
- :mod:`~paddle_tpu.obs.histogram` — fixed-bucket streaming
  :class:`Histogram` (bounded memory, pre-seeded presence) backing the
  ``serving_ttft_s`` / ``serving_tpot_s`` / ``serving_queue_wait_s`` /
  ``serving_e2e_s`` / ``serving_step_duration_s`` /
  ``serving_batch_occupancy`` percentile gauges, plus
  :class:`HistogramFamily` — label-keyed families
  (``serving_step_phase_s{phase=}``, and the per-tenant latency classes
  the fleet router will reuse).
- :mod:`~paddle_tpu.obs.timeline` — the engine loop's bounded per-step
  ring (:class:`StepTimeline`): phase mix, batch size, page pressure,
  preemptions, per-phase wall-time attribution, host syncs under
  ``debug_checks``.
- :mod:`~paddle_tpu.obs.attribution` — goodput attribution:
  :class:`PhaseAccumulator`, the one span mechanism inside
  ``engine.step()``: each boundary writes its seconds on the engine's
  clock (exact per-phase split + the sub-spans' own extents) and a
  ``serve.*`` ``TraceAnnotation`` into the profiler's own trace.
- :mod:`~paddle_tpu.obs.stall` — the stall record (:class:`StallWatch`):
  a blocking span (``*.fetch`` / ``*.upload`` / ``*.dispatch``) that
  outlasts its name's norm is flagged on the engine's thread,
  a sampler thread reads what the device, the process's threads and the
  machine did meanwhile, and one word (``held_by``) sums it up.
- :mod:`~paddle_tpu.obs.alerts` — anomaly watchdogs (:class:`Watchdog`):
  edge-triggered rules over host-resident step state — retrace after
  warmup, Pallas fallback, speculative-acceptance collapse, eviction
  thrash, queue stall — each firing a structured :class:`Alert`.
- :mod:`~paddle_tpu.obs.journey` — request-journey records
  (:class:`Journey`, :class:`JourneyBook`): every request's
  enqueue → admit → chunk/decode/verify → preempt/swap → retire hop
  list with engine-step refs, folded off the tracer's event stream and
  exportable as the schema-versioned ``paddle-tpu/journey/v1`` wire
  dict (:func:`validate_journey`) — the trace-export-over-the-wire
  format the multi-host arc consumes.
- :mod:`~paddle_tpu.obs.tenant` — per-tenant SLO classes
  (:class:`TenantSLO`) and the goodput/badput ledger
  (:class:`TenantLedger`): every retirement classified into one of
  seven terminal classes, emitted tokens accrued per class, observe-only
  (weighted admission stays with the fleet router).
- :mod:`~paddle_tpu.obs.recorder` — the black-box flight recorder:
  bounded schema-versioned JSON dumps (v2: + per-tenant roll-ups and a
  journey ring; v1 dumps stay readable) of the step ring + alerts +
  gauges + audit roll-ups, written automatically on engine-fatal paths
  and request failures.
- :mod:`~paddle_tpu.obs.export` — Chrome ``trace_event`` JSON (one track
  per request + the engine loop + counter tracks + alert instants; loads
  in Perfetto) and Prometheus text exposition with labeled families.
- :mod:`~paddle_tpu.obs.fleetscope` — cluster-grain observability:
  cross-replica exchange spans (:class:`FleetScope`, deterministic
  :func:`span_id`, Chrome flow events via :func:`flow_events`),
  fleet-wide scrape merging (:class:`FleetMetrics`, ``replica=``
  labels), and the schema-versioned ``paddle-tpu/fleet-record/v1``
  cluster flight recorder (:func:`validate_fleet_record`) bundling
  per-replica flight records + router state + the exchange-span ring.

``python -m paddle_tpu.obs --flight-record DUMP`` pretty-prints a flight
record (``--prometheus`` / ``--latency-table`` render its gauge and
latency sections); ``--fleet-record DUMP`` pretty-prints a cluster
record (``--span RID`` renders one request's exchange span trees,
``--prometheus`` the merged ``replica=``-labeled exposition); exit 0
clean, 1 alerts/fatal recorded, 2 bad usage.

Imports nothing from ``paddle_tpu.serving`` — serving imports us. Tracing
is on by default in the engine (``ServingConfig(enable_tracing=)``); the
off path costs one attribute check per event site and the on path adds no
host syncs to the decode loop (the SyncTally certification is unchanged).
"""
from .alerts import RULES as ALERT_RULES  # noqa: F401
from .alerts import Alert, Watchdog, WatchdogConfig  # noqa: F401
from .attribution import (NO_SPAN, PHASES, SPAN_PREFIX,  # noqa: F401
                          SPANS, PhaseAccumulator)
from .export import (chrome_trace, latency_table,  # noqa: F401
                     prometheus_text, write_chrome_trace)
from .fleetscope import (FLEET_RECORD_SCHEMA,  # noqa: F401
                         FleetMetrics, FleetScope, build_fleet_record,
                         dump_fleet_record, flow_events,
                         format_fleet_record, format_span_tree,
                         span_id, span_key, validate_fleet_record)
from .histogram import (LATENCY_EDGES_S, OCCUPANCY_EDGES,  # noqa: F401
                        QUANTILES, Histogram, HistogramFamily,
                        split_labels)
from .journey import (JOURNEY_SCHEMA, Journey, JourneyBook,  # noqa: F401
                      format_journey, validate_journey)
from .recorder import (FLIGHT_RECORD_SCHEMA,  # noqa: F401
                       FLIGHT_RECORD_SCHEMA_V1, build_flight_record,
                       dump_flight_record, format_flight_record,
                       validate_flight_record)
from .stall import HELD_BY, StallWatch, stall_table  # noqa: F401
from .tenant import TENANT_CLASSES  # noqa: F401
from .tenant import (TenantLedger, TenantSLO,  # noqa: F401
                     check_tenant_name, tenant_table)
from .timeline import StepRecord, StepTimeline  # noqa: F401
from .trace import RequestTrace, TraceEvent, Tracer  # noqa: F401

__all__ = ["Histogram", "HistogramFamily", "LATENCY_EDGES_S",
           "OCCUPANCY_EDGES", "QUANTILES", "split_labels",
           "Tracer", "RequestTrace", "TraceEvent",
           "StepTimeline", "StepRecord",
           "PHASES", "SPANS", "SPAN_PREFIX", "NO_SPAN", "PhaseAccumulator",
           "HELD_BY", "StallWatch", "stall_table",
           "Alert", "ALERT_RULES", "Watchdog", "WatchdogConfig",
           "JOURNEY_SCHEMA", "Journey", "JourneyBook",
           "validate_journey", "format_journey",
           "TENANT_CLASSES", "TenantSLO", "TenantLedger",
           "check_tenant_name", "tenant_table",
           "FLIGHT_RECORD_SCHEMA", "FLIGHT_RECORD_SCHEMA_V1",
           "build_flight_record", "dump_flight_record",
           "format_flight_record", "validate_flight_record",
           "chrome_trace", "write_chrome_trace", "prometheus_text",
           "latency_table",
           "FLEET_RECORD_SCHEMA", "FleetScope", "FleetMetrics",
           "span_id", "span_key", "flow_events", "build_fleet_record",
           "dump_fleet_record", "validate_fleet_record",
           "format_fleet_record", "format_span_tree"]

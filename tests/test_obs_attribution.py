"""Goodput attribution layer: per-phase step accounting, anomaly
watchdogs, and the black-box flight recorder (the spans of the same
mechanism in the profiler's trace: tests/test_serve_spans.py).

Four layers of coverage:

- attribution exactness: per-phase times sum to the step's wall time on a
  virtual clock (exact — the PhaseAccumulator mark construction), and the
  phase vocabulary matches what the step actually did.
- watchdogs: every rule fired deterministically exactly once (synthetic
  step feeds for the windowed rules, live engines for queue_stall and
  pallas_fallback) and quiescent on a clean run; zero added host syncs
  (the SyncTally formula is byte-identical with attribution + watchdogs
  ON, pinned here as in bench and the demo).
- flight recorder: ring bound, dump schema, the automatic dumps on
  request failure (every ``-m faults`` scenario doubles as a recorder
  test), on engine-fatal exceptions (the step ring flushed BEFORE the
  re-raise — the satellite fix), and on the stuck-engine backstop
  (a ``pool_exhausted`` preemption livelock).
- surfaces: Chrome counter tracks + alert instants schema, labeled-family
  pre-seeding and Prometheus rendering, CLI exit codes 0/1/2.

Everything runs on a virtual clock — sleep-free, deterministic.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import SyncTally
from paddle_tpu.obs import (ALERT_RULES, NO_SPAN, PHASES, PhaseAccumulator,
                            StepRecord, Watchdog, WatchdogConfig,
                            validate_flight_record)
from paddle_tpu.obs.__main__ import main as obs_main
from paddle_tpu.serving import FaultInjector, ServingConfig, ServingEngine
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.utils import monitor

pytestmark = pytest.mark.obs


class VirtualClock:
    """Integer-stepped fake engine clock: 1.0 s per read, so phase sums
    are EXACT float arithmetic (no rounding slop to hide behind)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(scope="module")
def model():
    paddle.seed(29)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=48, dropout=0.0))
    m.eval()
    return m


def _engine(model, clock=None, fault_injector=None, **overrides):
    kw = dict(max_batch=2, num_pages=20, page_size=4, max_prompt_len=8)
    kw.update(overrides)
    return ServingEngine(model, ServingConfig(**kw),
                         clock=clock or VirtualClock(),
                         fault_injector=fault_injector)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


def _record(step, queue_depth=0, admitted=0, batch=0, chunks=0):
    return StepRecord(step=step, t_start=float(step), t_end=step + 1.0,
                      admitted=admitted, prefills=0, batch=batch,
                      finished=0, preemptions=0, queue_depth=queue_depth,
                      pages_in_use=0, chunks=chunks)


# ------------------------------------------------------- phase attribution
def test_phase_accumulator_marks_and_exact_sum():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    acc = PhaseAccumulator(clock)
    t0 = acc.begin()
    assert acc.open and t0 == 1.0
    assert acc.mark("admit") == 1.0
    assert acc.mark("decode", t=5.0) == 3.0
    assert acc.mark("decode", t=6.0) == 1.0  # accumulates, not replaces
    t_end, phases = acc.finish(t=10.0)
    assert not acc.open
    assert phases == {"admit": 1.0, "decode": 4.0, "other": 4.0}
    assert sum(phases.values()) == t_end - t0


def test_engine_phase_times_sum_to_step_wall_time_exactly(model):
    engine = _engine(model)
    for i in range(3):
        engine.add_request(_prompt(5, seed=i), 6)
    engine.run()
    records = engine.timeline.records()
    assert records
    for rec in records:
        assert sum(rec.phase_s.values()) == rec.duration, rec
        assert set(rec.phase_s) <= set(PHASES)
    # a decoding step attributes decode time; admission work is visible
    assert any(rec.phase_s.get("decode", 0) > 0 for rec in records)
    assert any(rec.phase_s.get("prefill", 0) > 0 for rec in records)
    assert all(rec.phase_s.get("admit", 0) > 0 for rec in records)
    # a first token is fetched behind the step's decode launch: its seconds
    # lie in the decode phase, and a prefill's phase is its upload and launch
    firsts = [rec for rec in records if "prefill.fetch" in rec.span_s]
    assert firsts
    for rec in firsts:
        assert rec.span_s["prefill.fetch"] + rec.span_s["decode.dispatch"] \
            <= rec.phase_s["decode"]
        assert rec.span_s["prefill.upload"] + rec.span_s["prefill.dispatch"] \
            <= rec.phase_s["prefill"]


def test_phase_family_histograms_fed_and_pre_seeded(model):
    engine = _engine(model)
    snap = engine.metrics.snapshot()
    # presence before the first step, for every phase label
    for phase in PHASES:
        assert snap[f"serving_step_phase_s_count{{phase={phase}}}"] == 0
    engine.add_request(_prompt(5), 4)
    engine.run()
    snap = engine.metrics.snapshot()
    assert snap["serving_step_phase_s_count{phase=decode}"] > 0
    assert snap["serving_step_phase_s_p99{phase=decode}"] > 0
    # prometheus renders the family as real labeled bucket series (the
    # label-set renderer emits sorted k="v" pairs: le before phase)
    prom = engine.metrics.prometheus()
    assert '_bucket{le="' in prom and ',phase="decode"}' in prom
    assert "# TYPE serving_step_phase_s histogram" in prom


# --------------------------------------------------------------- watchdogs
def test_watchdog_retrace_and_fallback_rules_edge_trigger():
    wd = Watchdog(WatchdogConfig(warmup_steps=2))
    # a retrace during warmup only moves the baseline
    assert wd.on_step(_record(0), {"retraces": 1}) == []
    assert wd.on_step(_record(1), {"retraces": 1}) == []
    fired = wd.on_step(_record(2), {"retraces": 2})
    assert [a.rule for a in fired] == ["retrace_after_warmup"]
    # persisting at the new total stays quiet; growth fires again
    assert wd.on_step(_record(3), {"retraces": 2}) == []
    fired = wd.on_step(_record(4), {"retraces": 3, "fallbacks": 1})
    assert sorted(a.rule for a in fired) == ["pallas_fallback",
                                             "retrace_after_warmup"]
    assert wd.fired_total["retrace_after_warmup"] == 2


def test_watchdog_acceptance_collapse_latches():
    cfg = WatchdogConfig(acceptance_floor=0.5, acceptance_min_proposed=8,
                         acceptance_window_steps=4)
    wd = Watchdog(cfg)
    # 8 proposed / 1 accepted inside the window -> collapse, fired ONCE
    assert wd.on_step(_record(0), {"proposed": 4, "accepted": 1}) == []
    fired = wd.on_step(_record(1), {"proposed": 8, "accepted": 1})
    assert [a.rule for a in fired] == ["spec_acceptance_collapse"]
    assert wd.on_step(_record(2), {"proposed": 12, "accepted": 1}) == []
    # a healthy window re-arms, a second collapse fires again
    for step, (p, a) in enumerate([(24, 13), (36, 25), (48, 37),
                                   (60, 49)], start=3):
        assert wd.on_step(_record(step), {"proposed": p, "accepted": a}) \
            == []
    fired = wd.on_step(_record(9), {"proposed": 120, "accepted": 49})
    assert [a.rule for a in fired] == ["spec_acceptance_collapse"]


def test_watchdog_thrash_and_stall_rules():
    cfg = WatchdogConfig(thrash_window_steps=4, thrash_events=6,
                         stall_steps=3)
    wd = Watchdog(cfg)
    assert wd.on_step(_record(0), {"evictions": 3}) == []
    fired = wd.on_step(_record(1), {"evictions": 4, "spills": 2})
    assert [a.rule for a in fired] == ["eviction_thrash"]
    # the window cleared: the same totals don't re-fire
    assert wd.on_step(_record(2), {"evictions": 4, "spills": 2}) == []
    # queue stall: 3 consecutive no-progress steps with waiters
    assert wd.on_step(_record(3, queue_depth=2), {}) == []
    assert wd.on_step(_record(4, queue_depth=2), {}) == []
    fired = wd.on_step(_record(5, queue_depth=2), {})
    assert [a.rule for a in fired] == ["queue_stall"]
    # a persisting stall does NOT re-fire (edge, not level)
    assert wd.on_step(_record(6, queue_depth=2), {}) == []
    # progress resets the streak; a NEW stall episode fires again
    assert wd.on_step(_record(7, queue_depth=2, admitted=1), {}) == []
    for step in (8, 9):
        assert wd.on_step(_record(step, queue_depth=1), {}) == []
    assert [a.rule for a in
            wd.on_step(_record(10, queue_depth=1), {})] == ["queue_stall"]


def test_engine_queue_stall_fires_once_and_counts(model):
    engine = _engine(model, watchdog=WatchdogConfig(stall_steps=3))
    engine.add_request(_prompt(5), 4)
    engine.admit_paused = True  # wedge: queued work, no admission
    for _ in range(6):
        engine.step()
    alerts = engine.alerts()
    assert [a.rule for a in alerts] == ["queue_stall"]  # exactly once
    snap = engine.metrics.snapshot()
    assert snap["serving_alerts_total{rule=queue_stall}"] == 1
    # the firing renders as a global instant on the engine track
    doc = engine.export_chrome_trace()
    instants = [e for e in doc["traceEvents"]
                if e["ph"] == "i" and e["name"] == "alert:queue_stall"]
    assert len(instants) == 1 and instants[0]["s"] == "g"


def test_engine_pallas_fallback_watchdog_fires(model):
    engine = _engine(model)
    engine.add_request(_prompt(5), 3)
    engine.step()
    # simulate a dispatch degrading mid-serve: the kernel layer counts
    # the pre-seeded gauge, the watchdog sees the delta next boundary
    monitor.stat_add("serving_pallas_fallback_total", 1)
    engine.run()
    assert [a.rule for a in engine.alerts()] == ["pallas_fallback"]
    assert engine.metrics.snapshot()[
        "serving_alerts_total{rule=pallas_fallback}"] == 1


def test_clean_run_is_quiescent_and_families_pre_seeded(model):
    engine = _engine(model)
    snap = engine.metrics.snapshot()
    for rule in ALERT_RULES:  # presence before anything happens
        assert snap[f"serving_alerts_total{{rule={rule}}}"] == 0
    for i in range(3):
        engine.add_request(_prompt(5, seed=i), 5)
    engine.run()
    assert engine.alerts() == []
    snap = engine.metrics.snapshot()
    assert all(v == 0 for k, v in snap.items()
               if k.startswith("serving_alerts_total"))


def test_attribution_and_watchdogs_add_zero_host_syncs(model):
    # the acceptance pin: the SyncTally certification formula (one token
    # fetch per decode step + one per completed prefill) is UNCHANGED
    # with attribution + watchdogs ON — they are clock reads and host
    # dict lookups only
    engine = _engine(model)
    assert engine.config.enable_tracing and engine.config.enable_watchdogs
    for i in range(3):
        engine.add_request(_prompt(4, seed=i), 4)
    with SyncTally() as tally:
        engine.run()
    snap = engine.metrics.snapshot()
    fetches = int(snap["serving_decode_steps"]
                  + snap["serving_prefills_total"])
    assert tally.count == fetches, (tally.events, fetches)
    assert engine.timeline.records()[-1].phase_s  # attribution really on


def test_obs_off_surfaces_are_none_and_watchdog_off(model):
    engine = _engine(model, enable_tracing=False)
    assert not engine._attr.enabled and engine._watchdog is None
    assert engine.alerts() == []
    engine.add_request(_prompt(5), 3)
    engine.run()
    rec = engine.flight_record()
    assert rec["steps"] == []  # documented: no ring with tracing off


def test_disabled_accumulator_is_no_span_and_records_nothing(model):
    # whether tracing is on is decided behind the accumulator, once: a
    # disabled one (no clock) hands every site the one shared do-nothing
    # context and keeps no seconds, and an engine with tracing off steps
    # through the same call sites without opening a record or reading
    # the clock for one
    acc = PhaseAccumulator()
    assert not acc.enabled
    assert acc.span("decode.fetch", of_step=3) is NO_SPAN
    acc.enter_step(7)
    assert acc.begin() == 0.0 and not acc.open
    assert acc.mark("admit") == 0.0
    acc.account()
    assert acc.finish() == (0.0, {}) and acc.span_s == {}
    acc.exit_step()
    assert acc._step_ann is None and acc._account is None

    clock = VirtualClock()
    engine = _engine(model, clock=clock, enable_tracing=False)
    engine.add_request(_prompt(5), 3)
    reads = clock.t
    engine.run()
    assert clock.t == reads  # not one clock read for a span or a phase
    assert engine.timeline is None and engine._step_stats is None
    assert not engine._attr.open and engine._attr.span_s == {}


# ---------------------------------------------------------- flight recorder
def test_flight_record_schema_ring_bound_and_dump(model, tmp_path):
    engine = _engine(model, flight_record_steps=4, debug_checks=True)
    for i in range(3):
        engine.add_request(_prompt(5, seed=i), 6)
    engine.run()
    assert len(engine.timeline) > 4
    path = tmp_path / "dump.json"
    rec = engine.dump_flight_record(path)
    validate_flight_record(rec)
    assert rec["reason"] == "manual"
    assert len(rec["steps"]) == 4  # the ring bound
    # the newest records, with their attribution riding along
    assert rec["steps"][-1]["step"] == engine.timeline.last.step
    assert rec["steps"][-1]["phase_s"]
    assert set(rec["programs"]) == {"prefill[8]", "decode"}
    assert rec["requests"] and rec["requests"][-1]["state"] == "finished"
    loaded = validate_flight_record(json.loads(path.read_text()))
    assert loaded["steps"] == json.loads(json.dumps(rec))["steps"]


def test_fault_failure_auto_dumps_flight_record(model, tmp_path):
    path = tmp_path / "auto.json"
    inj = FaultInjector().arm("decode_fail", step=2)
    engine = _engine(model, fault_injector=inj,
                     flight_record_path=str(path))
    for i in range(2):
        engine.add_request(_prompt(5, seed=i), 6)
    engine.run()
    assert engine.last_flight_record is not None
    assert engine.last_flight_record["reason"] == "request-failure"
    loaded = validate_flight_record(json.loads(path.read_text()))
    assert any(r["state"] == "failed" for r in loaded["requests"])


def test_engine_fatal_flushes_partial_step_into_ring(model):
    # the satellite fix: a step dying mid-body used to vanish — now the
    # open attribution closes into a partial StepRecord (extra names the
    # fatal) and the flight record dumps BEFORE the re-raise
    engine = _engine(model)
    engine.add_request(_prompt(5), 6)
    engine.step()
    n_before = len(engine.timeline)

    def boom(*args, **kwargs):
        raise RuntimeError("induced decode failure")

    engine._decode_jit = boom
    with pytest.raises(RuntimeError, match="induced decode failure"):
        engine.step()
    records = engine.timeline.records()
    assert len(records) == n_before + 1
    fatal = records[-1]
    assert fatal.extra["fatal"].startswith("RuntimeError")
    assert sum(fatal.phase_s.values()) == fatal.duration  # still exact
    rec = engine.last_flight_record
    assert rec is not None and rec["reason"] == "engine-fatal: RuntimeError"
    validate_flight_record(rec)
    assert rec["steps"][-1]["extra"]["fatal"].startswith("RuntimeError")


def test_engine_fatal_after_step_body_keeps_completed_record(model):
    # the debug sweep (check_invariants) runs AFTER _step returned: the
    # attribution is closed and the full step stats are built but not
    # yet appended — a fatal there must flush THAT record (real counts,
    # extra names the fatal), not silently drop the step that broke the
    # engine
    engine = _engine(model, debug_checks=True)
    engine.add_request(_prompt(5), 6)
    engine.step()
    n_before = len(engine.timeline)

    def boom():
        raise RuntimeError("induced invariant failure")

    engine.cache.check_invariants = boom
    with pytest.raises(RuntimeError, match="induced invariant failure"):
        engine.step()
    records = engine.timeline.records()
    assert len(records) == n_before + 1
    fatal = records[-1]
    assert fatal.extra["fatal"].startswith("RuntimeError")
    assert fatal.batch == 1  # the completed step's REAL counts survive
    assert sum(fatal.phase_s.values()) == fatal.duration
    assert engine._step_stats is None  # no stale handoff for a later step
    rec = engine.last_flight_record
    assert rec is not None and rec["reason"] == "engine-fatal: RuntimeError"
    assert rec["steps"][-1]["extra"]["fatal"].startswith("RuntimeError")


def test_pool_exhausted_livelock_dumps_on_stuck_backstop(model, tmp_path):
    # a pool_exhausted fault armed every step preempts the victim before
    # it ever decodes: admit -> prefill -> preempt forever. The stuck-
    # engine backstop fires, and the black box captures the preemption
    # storm that explains it.
    path = tmp_path / "stuck.json"
    inj = FaultInjector().arm("pool_exhausted", times=-1)
    engine = _engine(model, fault_injector=inj,
                     flight_record_path=str(path))
    engine.add_request(_prompt(5), 6)
    with pytest.raises(RuntimeError, match="exceeded"):
        engine.run(max_steps=6)
    rec = validate_flight_record(json.loads(path.read_text()))
    assert rec["reason"] == "stuck-engine"
    assert sum(s["preemptions"] for s in rec["steps"]) >= 5
    assert engine.last_flight_record["reason"] == "stuck-engine"


# ------------------------------------------------------ exporters + CLI
def test_chrome_counter_tracks_schema(model):
    engine = _engine(model)
    engine.add_request(_prompt(5), 4)
    engine.run()
    doc = engine.export_chrome_trace()
    json.loads(json.dumps(doc))
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in counters} == {"pages_in_use", "batch",
                                             "queue_depth"}
    # one sample per track per retained step, single numeric series each
    assert len(counters) == 3 * len(engine.timeline)
    for ev in counters:
        assert ev["pid"] == 1 and ev["ts"] >= 0.0
        assert list(ev["args"]) == [ev["name"]]
        assert isinstance(ev["args"][ev["name"]], (int, float))
    # engine spans carry the attribution alongside the counters
    spans = [e for e in doc["traceEvents"]
             if e.get("cat") == "engine" and e["ph"] == "X"]
    assert all("phases" in e["args"] for e in spans)


def test_obs_cli_exit_codes(model, tmp_path, capsys):
    clean = tmp_path / "clean.json"
    engine = _engine(model)
    engine.add_request(_prompt(5), 4)
    engine.run()
    engine.dump_flight_record(clean)

    assert obs_main(["--flight-record", str(clean)]) == 0
    out = capsys.readouterr().out
    assert "flight record" in out and "alerts (0)" in out

    assert obs_main(["--flight-record", str(clean), "--prometheus"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE serving_tokens_total counter" in out
    assert 'serving_alerts_total{rule="queue_stall"} 0' in out
    # dump typing matches the live ServingMetrics.prometheus() typing:
    # suffix-less counters (COUNTER_STATS) must not export as gauges
    assert "# TYPE serving_failed counter" in out
    assert "# TYPE serving_prefix_hits counter" in out

    assert obs_main(["--flight-record", str(clean),
                     "--latency-table"]) == 0
    out = capsys.readouterr().out
    assert "ttft" in out and "tpot" in out

    # findings: a dump that recorded alerts exits 1
    dirty = tmp_path / "dirty.json"
    stalled = _engine(model, watchdog=WatchdogConfig(stall_steps=2))
    stalled.add_request(_prompt(5), 4)
    stalled.admit_paused = True
    for _ in range(3):
        stalled.step()
    stalled.dump_flight_record(dirty)
    assert obs_main(["--flight-record", str(dirty)]) == 1
    assert "queue_stall" in capsys.readouterr().out

    # ... and so does a fatal/failure-reason dump with no alerts
    auto = tmp_path / "auto.json"
    engine.dump_flight_record(auto, reason="request-failure")
    assert obs_main(["--flight-record", str(auto)]) == 1
    capsys.readouterr()

    # bad usage / unreadable input
    assert obs_main(["--flight-record", str(tmp_path / "nope.json")]) == 2
    bad = tmp_path / "bad.json"
    bad.write_text("{\"schema\": \"wrong\"}")
    assert obs_main(["--flight-record", str(bad)]) == 2
    assert obs_main([]) == 2
    assert obs_main(["--no-such-flag"]) == 2
    capsys.readouterr()
    # --prometheus with no dump reads the live registry (this process),
    # with the SAME counter typing as the dump path — no type-flap
    # between a live scrape and a dump scrape of one process
    assert obs_main(["--prometheus"]) == 0
    out = capsys.readouterr().out
    assert "serving_" in out
    assert "# TYPE serving_tokens_total counter" in out

"""paddle_tpu.obs — per-request tracing, latency histograms, timeline
export.

Four layers of coverage:

- histogram goldens: bucket-edge ownership, percentile interpolation math,
  overflow clamping, pre-seeded presence (zeros before the first sample).
- trace completeness: every terminal state (finished / cancelled-waiting /
  cancelled-running / expired / failed / shed) leaves a summarizable
  trace, and BOTH preemption modes (recompute and swap) leave resumable
  traces whose TTFT stays anchored to the first token the client saw.
- exporters: Chrome trace_event JSON schema validation (the document
  Perfetto loads), Prometheus text exposition shape.
- overhead contract: tracing off costs ONE attribute check per event site
  (pinned by counting property reads, the fault-injector pin's idiom) and
  tracing ON adds ZERO host syncs to the decode loop (SyncTally pin).

Every engine scenario runs on a virtual clock — sleep-free, deterministic
timestamps.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import SyncTally
from paddle_tpu.obs import (Histogram, RequestTrace, StepTimeline, Tracer,
                            chrome_trace, latency_table, prometheus_text)
from paddle_tpu.serving import (FaultInjector, ServingConfig, ServingEngine,
                                ServingMetrics)
from paddle_tpu.text.gpt import GPTConfig, GPTForCausalLM

pytestmark = pytest.mark.obs


class VirtualClock:
    """Strictly increasing fake engine clock: 1 ms per read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


def _toy_model():
    paddle.seed(29)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=48, dropout=0.0))
    model.eval()
    return model


def _engine(model=None, clock=None, fault_injector=None, **overrides):
    kw = dict(max_batch=2, num_pages=20, page_size=4, max_prompt_len=8)
    kw.update(overrides)
    return ServingEngine(model or _toy_model(), ServingConfig(**kw),
                         clock=clock or VirtualClock(),
                         fault_injector=fault_injector)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 97, (n,)).astype(np.int32)


# -------------------------------------------------------------- histograms
def test_histogram_bucket_edges_golden():
    h = Histogram("h", (1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 4.0, 8.0):
        h.observe(v)
    # bucket i owns (edges[i-1], edges[i]]: exact edge values fall LOW
    assert h.counts == [2, 1, 2, 1]
    assert h.count == 6 and h.sum == pytest.approx(18.0)
    assert h.mean == pytest.approx(3.0)


def test_histogram_percentile_interpolation_golden():
    h = Histogram("h", (10.0, 20.0, 30.0))
    for _ in range(10):
        h.observe(5.0)  # all ten samples in (0, 10]
    # rank q*count interpolated linearly inside the owning bucket
    assert h.percentile(0.50) == pytest.approx(5.0)
    assert h.percentile(0.90) == pytest.approx(9.0)
    assert h.percentile(0.99) == pytest.approx(9.9)
    assert h.percentile(1.00) == pytest.approx(10.0)
    for _ in range(10):
        h.observe(15.0)  # ten more in (10, 20]
    assert h.percentile(0.50) == pytest.approx(10.0)
    assert h.percentile(0.75) == pytest.approx(15.0)


def test_histogram_overflow_clamps_to_top_edge():
    h = Histogram("h", (1.0, 8.0))
    h.observe(1e9)
    # a runaway sample must not paint p50 as infinity
    assert h.percentile(0.5) == 8.0
    assert h.cumulative_buckets()[-1] == (float("inf"), 1)


def test_histogram_empty_and_validation():
    h = Histogram("h", (1.0, 2.0))
    assert h.percentile(0.99) == 0.0 and h.mean == 0.0
    snap = h.snapshot()
    assert snap == {"p50": 0.0, "p90": 0.0, "p99": 0.0,
                    "count": 0, "sum": 0.0, "mean": 0.0}
    with pytest.raises(ValueError):
        h.percentile(1.5)
    with pytest.raises(ValueError):
        Histogram("bad", (2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", (1.0,))


def test_histogram_cumulative_buckets_monotone():
    h = Histogram("h", (1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 9.0):
        h.observe(v)
    cums = [c for _, c in h.cumulative_buckets()]
    assert cums == sorted(cums) and cums[-1] == h.count


def test_metrics_percentile_gauges_pre_seeded():
    m = ServingMetrics()
    snap = m.snapshot()
    for hist in ("ttft_s", "tpot_s", "queue_wait_s", "e2e_s",
                 "step_duration_s", "batch_occupancy"):
        for q in ("p50", "p90", "p99"):
            assert snap[f"serving_{hist}_{q}"] == 0.0, (hist, q)
        assert snap[f"serving_{hist}_count"] == 0
    assert snap["serving_queue_depth_peak"] == 0
    assert snap["serving_page_pool_peak"] == 0


def test_metrics_observe_request_skips_none_fields():
    m = ServingMetrics()
    m.observe_request({"queue_wait": 0.5, "ttft": None, "tpot": None,
                       "e2e": 1.0})
    snap = m.snapshot()
    assert snap["serving_queue_wait_s_count"] == 1
    assert snap["serving_e2e_s_count"] == 1
    assert snap["serving_ttft_s_count"] == 0  # None skipped, not zero


# ------------------------------------------------------ trace completeness
def test_finished_trace_full_lifecycle():
    engine = _engine()
    rid = engine.add_request(_prompt(4), 4)
    engine.run()
    tr = engine.trace(rid)
    names = [e.name for e in tr.events]
    assert names == ["enqueued", "admitted", "prefill_start",
                     "prefill_end", "first_token", "retired"]
    assert tr.state == "finished" and tr.terminal
    s = tr.summary()
    assert s["state"] == "finished" and s["tokens"] == 4
    for k in ("queue_wait", "prefill_time", "ttft", "tpot", "e2e"):
        assert s[k] is not None and s[k] >= 0.0, k
    # the decomposition is internally consistent on a monotone clock
    assert s["e2e"] >= s["ttft"] >= s["queue_wait"]


def test_cancelled_while_waiting_trace_has_no_ttft():
    engine = _engine(max_batch=1)
    r1 = engine.add_request(_prompt(4), 8)
    r2 = engine.add_request(_prompt(5, seed=1), 8)
    engine.step()  # r1 occupies the only slot; r2 still queued
    assert engine.cancel(r2)
    tr = engine.trace(r2)
    assert [e.name for e in tr.events] == ["enqueued", "retired"]
    s = tr.summary()
    assert s["state"] == "cancelled"
    assert s["ttft"] is None and s["tpot"] is None \
        and s["queue_wait"] is None
    assert s["e2e"] is not None and s["e2e"] > 0.0
    engine.run()
    assert engine.trace(r1).state == "finished"


def test_cancelled_while_running_trace():
    engine = _engine()
    rid = engine.add_request(_prompt(4), 16)
    engine.step()
    engine.step()  # > 1 token generated before the cancel
    assert engine.cancel(rid)
    tr = engine.trace(rid)
    assert tr.state == "cancelled"
    assert tr.first("first_token") is not None
    s = tr.summary()
    assert s["ttft"] is not None and s["e2e"] is not None
    # TPOT is a decode-speed figure: a non-finished retirement happens at
    # an arbitrary later sweep, so it must NOT be derived from it even
    # with >= 2 tokens on record
    assert s["tokens"] > 1 and s["tpot"] is None


def test_expired_trace():
    clock = VirtualClock()
    engine = _engine(clock=clock)
    rid = engine.add_request(_prompt(4), 16, deadline_s=5.0)
    engine.step()
    clock.t += 60.0  # blow the deadline, sleep-free
    engine.step()
    tr = engine.trace(rid)
    assert tr.state == "expired"
    assert tr.summary()["e2e"] is not None


def test_failed_trace_prefill_fault():
    inj = FaultInjector().arm("prefill_fail", step=0)
    engine = _engine(fault_injector=inj)
    rid = engine.add_request(_prompt(4), 4)
    engine.run()
    tr = engine.trace(rid)
    assert tr.state == "failed"
    # the fault fires BEFORE the jitted prefill: no prefill span opened
    assert tr.first("prefill_start") is None
    assert tr.summary()["ttft"] is None


def test_shed_trace():
    engine = _engine(max_batch=1, max_waiting=1,
                     shed_policy="shed-oldest")
    engine.add_request(_prompt(4), 8)
    r2 = engine.add_request(_prompt(5, seed=1), 8)  # fills the queue
    r3 = engine.add_request(_prompt(6, seed=2), 8)  # sheds r2
    tr = engine.trace(r2)
    assert tr.state == "shed"
    assert [e.name for e in tr.events] == ["enqueued", "retired"]
    assert engine.trace(r3).state is None  # the newcomer lives


def _preemption_scenario(mode):
    # 3 usable pages of 8 tokens; r1 (4+8=12 tok -> 2 pages) and r2
    # (7+10=17 tok -> 3 pages) can't both peak: one MUST be preempted
    engine = _engine(max_batch=2, num_pages=4, page_size=8,
                     max_prompt_len=16, preemption_mode=mode)
    r1 = engine.add_request(_prompt(4), 8)
    r2 = engine.add_request(_prompt(7, seed=1), 10)
    outs = engine.run()
    assert set(outs) == {r1, r2}
    victim = next(t for t in (engine.trace(r1), engine.trace(r2))
                  if t.count("preempted"))
    return engine, victim


def test_recompute_preemption_leaves_resumable_trace():
    engine, tr = _preemption_scenario("recompute")
    assert tr.first("preempted").arg("mode") == "recompute"
    # the victim replayed from prefill: one more prefill span and one
    # more admission per preemption — one request, one trace, the whole
    # story
    n = tr.count("preempted")
    assert n >= 1
    assert tr.count("prefill_start") == n + 1
    assert tr.count("admitted") == n + 1
    assert tr.state == "finished"
    s = tr.summary()
    assert s["preemptions"] == n
    # TTFT anchors to the FIRST token the client saw, not the replay
    first_tok = tr.first("first_token")
    assert s["ttft"] == pytest.approx(
        first_tok.t - tr.first("enqueued").t)


def test_swap_preemption_leaves_resumable_trace():
    engine, tr = _preemption_scenario("swap")
    assert tr.first("preempted").arg("mode") == "swap"
    assert tr.count("swap_out") == 1 and tr.count("swap_in") == 1
    assert tr.count("resumed") == 1
    # swap keeps the generated tokens: no second prefill
    assert tr.count("prefill_start") == 1
    assert tr.state == "finished"
    assert tr.summary()["preemptions"] == 1
    snap = engine.metrics.snapshot()
    assert snap["serving_swap_outs"] == snap["serving_swap_ins"] >= 1


def test_decode_mark_cadence():
    engine = _engine(decode_mark_every=2)
    rid = engine.add_request(_prompt(4), 6)
    engine.run()
    tr = engine.trace(rid)
    marks = [e.arg("tokens") for e in tr.events
             if e.name == "decode_mark"]
    assert marks == [2, 4, 6]


def test_histograms_fed_from_traces():
    engine = _engine()
    for i in range(3):
        engine.add_request(_prompt(4, seed=i), 4)
    engine.run()
    snap = engine.metrics.snapshot()
    for hist in ("ttft_s", "tpot_s", "queue_wait_s", "e2e_s"):
        assert snap[f"serving_{hist}_count"] == 3, hist
        assert snap[f"serving_{hist}_p99"] > 0.0, hist
    assert snap["serving_step_duration_s_count"] > 0
    assert snap["serving_batch_occupancy_count"] > 0


# ----------------------------------------------------------- trace store
def test_tracer_evicts_only_terminal_traces():
    clock = VirtualClock()
    t = Tracer(clock, capacity=2)
    t.begin(1)
    t.event(1, "retired", state="finished", tokens=1)
    t.begin(2)  # live
    t.begin(3)  # over capacity: evicts rid 1 (oldest terminal)
    assert t.get(1) is None and t.evicted == 1
    assert t.get(2) is not None and t.get(3) is not None
    t.begin(4)  # all retained traces live: grows, corrupts nothing
    assert len(t) == 3 and t.evicted == 1
    # once the live burst retires, the store RECLAIMS down to capacity
    # (not one-per-insert: the high-water mark must not stick)
    for rid in (2, 3, 4):
        t.event(rid, "retired", state="finished", tokens=1)
    t.begin(5)
    assert len(t) == 2 and t.evicted == 3
    assert t.get(4) is not None and t.get(5) is not None  # newest survive


def test_tracer_ignores_unknown_rid():
    t = Tracer(VirtualClock(), capacity=2)
    t.event(99, "decode_mark")  # evicted/unknown: dropped, not raised
    assert len(t) == 0


def test_request_trace_helpers():
    tr = RequestTrace(7)
    tr.add("enqueued", 1.0)
    tr.add("decode_mark", 2.0, {"tokens": 2})
    tr.add("decode_mark", 3.0, {"tokens": 4})
    assert tr.first("decode_mark").t == 2.0
    assert tr.last("decode_mark").t == 3.0
    assert tr.count("decode_mark") == 2
    assert tr.first("missing") is None
    assert not tr.terminal


# -------------------------------------------------------------- exporters
def _chrome_doc(engine):
    doc = engine.export_chrome_trace()
    json.loads(json.dumps(doc))  # round-trips as real JSON
    return doc


def test_chrome_trace_schema():
    engine = _engine()
    rids = [engine.add_request(_prompt(4, seed=i), 4) for i in range(2)]
    engine.run()
    doc = _chrome_doc(engine)
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms" and events
    for ev in events:
        assert ev["ph"] in ("X", "i", "M", "C"), ev
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["pid"] == 1
        assert isinstance(ev["tid"], int)
        if ev["ph"] != "M":
            assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "i":
            # request-track instants are thread-scoped; watchdog alert
            # instants on the engine track are global
            assert ev["s"] in ("t", "g")
        if ev["ph"] == "C":
            # counter tracks: one numeric series per args key
            assert all(isinstance(v, (int, float))
                       for v in ev["args"].values()), ev
    # one named track per request + the engine loop
    threads = {ev["tid"]: ev["args"]["name"] for ev in events
               if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert threads[0] == "engine loop"
    for rid in rids:
        assert threads[rid + 1] == f"request {rid}"
    # the request phase spans and the engine step spans are all present
    span_names = {ev["name"] for ev in events if ev["ph"] == "X"}
    assert {"queued", "prefill", "decode"} <= span_names
    assert any(n in span_names for n in ("prefill+decode", "idle"))
    retired = [ev for ev in events if ev["ph"] == "i"
               and ev["name"].startswith("retired")]
    assert len(retired) == len(rids)


def test_chrome_trace_write_and_engine_track_args(tmp_path):
    engine = _engine()
    engine.add_request(_prompt(4), 3)
    engine.run()
    path = tmp_path / "trace.json"
    doc = engine.export_chrome_trace(path)
    assert json.loads(path.read_text()) == json.loads(json.dumps(doc))
    steps = [ev for ev in doc["traceEvents"]
             if ev.get("cat") == "engine" and ev["ph"] == "X"]
    assert len(steps) == len(engine.timeline)
    for ev in steps:
        for key in ("step", "batch", "prefills", "pages_in_use",
                    "queue_depth", "preemptions"):
            assert key in ev["args"], key


def test_prometheus_exposition_shape():
    engine = _engine()
    engine.add_request(_prompt(4), 4)
    engine.run()
    text = engine.metrics.prometheus()
    lines = text.strip().splitlines()
    assert "# TYPE serving_tokens_total counter" in lines
    assert "# TYPE serving_queue_depth gauge" in lines
    assert "# TYPE serving_ttft_s histogram" in lines
    # cumulative bucket series ends at +Inf == count
    inf = next(ln for ln in lines
               if ln.startswith('serving_ttft_s_bucket{le="+Inf"}'))
    count = next(ln for ln in lines if ln.startswith("serving_ttft_s_count"))
    assert inf.split()[-1] == count.split()[-1] == "1"
    # percentile mirrors are NOT double-exported as scalars
    assert not any(ln.startswith("serving_ttft_s_p50 ") for ln in lines)


def test_latency_table_renders():
    engine = _engine()
    engine.add_request(_prompt(4), 4)
    engine.run()
    table = latency_table(engine.latency_summaries())
    assert "queue_wait" in table and "ttft" in table
    assert "finished" in table


def test_chrome_trace_empty_inputs():
    doc = chrome_trace()
    assert [ev["ph"] for ev in doc["traceEvents"]] == ["M", "M"]
    assert prometheus_text({}).strip() == ""


# -------------------------------------------------------------- timeline
def test_timeline_ring_is_bounded():
    engine = _engine(timeline_capacity=4)
    engine.add_request(_prompt(4), 12)
    engine.run()
    tl = engine.timeline
    assert tl.total_steps > 4  # 12 decode steps happened...
    assert len(tl) == 4        # ...but only the newest 4 are retained
    recs = tl.records()
    assert [r.step for r in recs] == sorted(r.step for r in recs)
    assert recs[-1] is tl.last
    for r in recs:
        assert r.t_end >= r.t_start
        assert r.duration == r.t_end - r.t_start


def test_timeline_records_step_shape():
    engine = _engine()
    engine.add_request(_prompt(4), 3)
    engine.step()
    rec = engine.timeline.last
    assert rec.prefills == 1 and rec.admitted == 1 and rec.batch == 1
    assert rec.phase_mix() == "prefill+decode"
    assert rec.pages_in_use > 0
    assert rec.host_syncs is None  # debug_checks off
    engine.run()
    assert engine.timeline.last.finished == 1


@pytest.mark.slow  # re-tiered 2026-08 (PR 8): tier-1 budget; debug-checks host-sync counting stays
# pinned tier-1 by test_analysis's sync-accounting test and test_serving_tp's sync-free cert
def test_timeline_host_syncs_under_debug_checks():
    engine = _engine(debug_checks=True)
    engine.add_request(_prompt(4), 3)
    engine.step()
    # the step's syncs: the prefill first-token fetch + the decode fetch
    assert engine.timeline.last.host_syncs == 2
    engine.step()
    assert engine.timeline.last.host_syncs == 1  # decode fetch only
    with pytest.raises(ValueError):
        StepTimeline(0)


# ------------------------------------------------------ overhead contract
def test_obs_off_engine_surfaces_are_none():
    engine = _engine(enable_tracing=False)
    rid = engine.add_request(_prompt(4), 3)
    outs = engine.run()
    assert rid in outs
    assert engine.trace(rid) is None and engine.timeline is None
    assert engine.traces() == [] and engine.latency_summaries() == []
    doc = engine.export_chrome_trace()
    assert all(ev["ph"] == "M" for ev in doc["traceEvents"])
    snap = engine.metrics.snapshot()
    assert snap["serving_ttft_s_count"] == 0  # histograms ride traces


def test_obs_off_is_one_attribute_check_per_event_site():
    # the tracing analog of the fault-injector zero-overhead pin: with
    # tracing off, each event site costs exactly one read of ._tracer
    # (which is None) and nothing else
    class CountingEngine(ServingEngine):
        reads = 0

        @property
        def _tracer(self):
            CountingEngine.reads += 1
            return self.__dict__.get("_tracer_value")

        @_tracer.setter
        def _tracer(self, value):
            self.__dict__["_tracer_value"] = value

    engine = CountingEngine(_toy_model(), ServingConfig(
        max_batch=2, num_pages=20, page_size=4, max_prompt_len=8,
        enable_tracing=False), clock=VirtualClock())
    CountingEngine.reads = 0
    engine.add_request(_prompt(4), 3)
    assert CountingEngine.reads == 1  # the enqueue site
    CountingEngine.reads = 0
    # the prefill's two sites (prefill_start at its launch, prefill_end /
    # first_token at its fetch behind the decode launch, which has none)
    engine.step()
    assert CountingEngine.reads == 2
    CountingEngine.reads = 0
    engine.step()  # the emit site of the decode launched one step before
    assert CountingEngine.reads == 1
    CountingEngine.reads = 0
    engine.step()  # emit site + the finish (retire) site
    assert CountingEngine.reads == 2


def test_tracing_on_adds_zero_host_syncs_to_decode_loop():
    # the acceptance pin: the SyncTally certification is UNCHANGED with
    # tracing enabled — one token fetch per step boundary, nothing else
    engine = _engine()
    assert engine.config.enable_tracing  # on by default
    for i in range(3):
        engine.add_request(_prompt(4, seed=i), 4)
    with SyncTally() as tally:
        engine.run()
    snap = engine.metrics.snapshot()
    fetches = int(snap["serving_decode_steps"]
                  + snap["serving_prefills_total"])
    assert tally.count == fetches, (tally.events, fetches)
    assert len(engine.traces()) == 3  # tracing really was on
